"""Checkpoint round-trip, including restore into a different parallelism
layout (the schema-stability property the reference lacks, SURVEY §A.6)."""

import os

import jax
import numpy as np

from distributed_compute_pytorch_tpu.core.mesh import make_mesh
from distributed_compute_pytorch_tpu.models.convnet import ConvNet
from distributed_compute_pytorch_tpu.parallel.api import DataParallel, FSDP
from distributed_compute_pytorch_tpu.train import checkpoint
from distributed_compute_pytorch_tpu.train.optim import adadelta_steplr
from distributed_compute_pytorch_tpu.train.step import make_step_fns


def _fresh_state(mesh, strategy):
    model = ConvNet()
    tx = adadelta_steplr(0.1, 0.7, 10)
    init_fn, train_step, _ = make_step_fns(model, tx, mesh, strategy)
    return init_fn(jax.random.key(0)), train_step


def test_roundtrip(tmp_path, devices8):
    mesh = make_mesh("data=8", devices=devices8)
    state, train_step = _fresh_state(mesh, DataParallel())
    x = jax.random.normal(jax.random.key(1), (8, 28, 28, 1))
    y = jax.numpy.zeros((8,), jax.numpy.int32)
    state, _ = train_step(state, x, y)

    path = str(tmp_path / "ckpt.npz")
    checkpoint.save(path, state, epoch=4, extra={"note": "t"})
    assert os.path.exists(path)
    manifest = checkpoint.load_manifest(path)
    assert manifest["epoch"] == 4

    template, _ = _fresh_state(mesh, DataParallel())
    restored = checkpoint.restore(path, template)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state.params)),
                    jax.tree_util.tree_leaves(jax.device_get(restored.params))):
        np.testing.assert_array_equal(a, b)
    assert int(restored.step) == 1


def test_restore_across_strategies(tmp_path, devices8):
    """Save under FSDP, restore under DP (and the layouts differ)."""
    mesh_fsdp = make_mesh("data=2,fsdp=4", devices=devices8)
    state_f, step_f = _fresh_state(mesh_fsdp, FSDP(min_size_to_shard=64))
    x = jax.random.normal(jax.random.key(1), (8, 28, 28, 1))
    y = jax.numpy.zeros((8,), jax.numpy.int32)
    state_f, _ = step_f(state_f, x, y)
    path = str(tmp_path / "ckpt_fsdp.npz")
    checkpoint.save(path, state_f, epoch=0)

    mesh_dp = make_mesh("data=8", devices=devices8)
    template, _ = _fresh_state(mesh_dp, DataParallel())
    restored = checkpoint.restore(path, template)
    for a, b in zip(jax.tree_util.tree_leaves(jax.device_get(state_f.params)),
                    jax.tree_util.tree_leaves(jax.device_get(restored.params))):
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------- v2 sharded format


def _assert_states_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(jax.device_get(a)),
                    jax.tree_util.tree_leaves(jax.device_get(b))):
        import jax.numpy as jnp
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype,
                                                  jax.dtypes.prng_key):
            x, y = jax.random.key_data(x), jax.random.key_data(y)
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_sharded_save_writes_per_shard_entries(tmp_path, devices8):
    """FSDP save under the sharded format: sharded leaves are written as
    per-device-shard entries — never materialised whole — and no
    process_allgather of param-sized arrays happens (single-process here,
    but the structure proves the mechanism)."""
    mesh = make_mesh("data=2,fsdp=4", devices=devices8)
    state, step = _fresh_state(mesh, FSDP(min_size_to_shard=64))
    x = jax.random.normal(jax.random.key(1), (8, 28, 28, 1))
    y = jax.numpy.zeros((8,), jax.numpy.int32)
    state, _ = step(state, x, y)

    import unittest.mock as mock
    from jax.experimental import multihost_utils
    path = str(tmp_path / "ckpt_dir")
    with mock.patch.object(multihost_utils, "process_allgather",
                           side_effect=AssertionError("allgather called")):
        checkpoint.save_sharded(path, state, epoch=3)
    assert os.path.isdir(path)
    assert checkpoint.load_manifest(path)["epoch"] == 3

    entries = checkpoint._sharded_entry_map(path)
    # the fc1 kernel (9216x128, FSDP-sharded 4-way) must appear as 4
    # distinct span entries, each a quarter of the rows
    fc1 = [k for k in entries if k.endswith("fc1::kernel")]
    assert fc1, list(entries)[:10]
    spans = sorted(tuple(tuple(s) for s in piece[2])
                   for piece in entries[fc1[0]])
    assert len(spans) == 4
    assert spans[0][0] == (0, 9216 // 4)


def test_sharded_roundtrip_and_cross_layout(tmp_path, devices8):
    """Sharded save under FSDP -> restore under DP on the same mesh and
    into FSDP again: bit-exact both ways."""
    mesh = make_mesh("data=2,fsdp=4", devices=devices8)
    state, step = _fresh_state(mesh, FSDP(min_size_to_shard=64))
    x = jax.random.normal(jax.random.key(1), (8, 28, 28, 1))
    y = jax.numpy.zeros((8,), jax.numpy.int32)
    state, _ = step(state, x, y)
    path = str(tmp_path / "ckpt_dir")
    checkpoint.save_sharded(path, state, epoch=0)

    # back into the same FSDP layout
    template_f, _ = _fresh_state(mesh, FSDP(min_size_to_shard=64))
    shardings = jax.tree.map(lambda a: a.sharding, template_f)
    restored_f = checkpoint.restore(path, template_f, shardings=shardings)
    _assert_states_equal(state, restored_f)
    # restored leaves keep the FSDP sharding
    k = restored_f.params["fc1"]["kernel"]
    assert k.sharding == template_f.params["fc1"]["kernel"].sharding

    # into plain DP on a different mesh shape (elastic resize 8 -> 4)
    mesh4 = make_mesh("data=4", devices=devices8[:4])
    template_d, _ = _fresh_state(mesh4, DataParallel())
    shardings_d = jax.tree.map(lambda a: a.sharding, template_d)
    restored_d = checkpoint.restore(path, template_d, shardings=shardings_d)
    _assert_states_equal(state, restored_d)


def test_sharded_save_generations_and_stale_parts(tmp_path, devices8):
    """Generation protocol: re-saving bumps the generation, prunes dead
    parts, never consults leftovers, and an interrupted save (parts but no
    manifest) leaves the PREVIOUS checkpoint fully restorable."""
    import json

    mesh = make_mesh("data=8", devices=devices8)
    state, _ = _fresh_state(mesh, DataParallel())
    path = str(tmp_path / "ckpt_dir")
    os.makedirs(path)
    # fake leftovers from an interrupted save of an earlier layout
    with open(os.path.join(path, "part-g7-00001.json"), "w") as f:
        json.dump({"file": "part-g7-00001.npz", "entries": [
            {"key": "bogus", "entry": "bogus@full", "span": [[0, 1]]}]}, f)
    with open(os.path.join(path, "part-g7-00001.npz"), "wb") as f:
        np.savez(f, **{"bogus@full": np.zeros(1)})
    assert not checkpoint.exists(path)    # no manifest = no checkpoint

    checkpoint.save_sharded(path, state, epoch=1)
    man = checkpoint.load_manifest(path)
    assert man["num_parts"] == 1 and man["generation"] == 0
    assert not os.path.exists(os.path.join(path, "part-g7-00001.json"))
    assert "bogus" not in checkpoint._sharded_entry_map(path)

    template, _ = _fresh_state(mesh, DataParallel())
    restored = checkpoint.restore(path, template)
    _assert_states_equal(state, restored)

    # a second save bumps the generation and prunes generation 0
    checkpoint.save_sharded(path, state, epoch=2)
    man2 = checkpoint.load_manifest(path)
    assert man2["generation"] == 1 and man2["epoch"] == 2
    assert not os.path.exists(os.path.join(path, "part-g0-00000.npz"))
    # an interrupted NEXT save (parts written, manifest not yet replaced)
    # must leave generation 1 restorable
    with open(os.path.join(path, "part-g2-00000.json"), "w") as f:
        json.dump({"file": "part-g2-00000.npz", "entries": []}, f)
    restored2 = checkpoint.restore(path, template)
    _assert_states_equal(state, restored2)


def test_sharded_restore_rejects_shape_mismatch(tmp_path, devices8):
    """A template whose leaf shapes differ from the save must raise, not
    silently zero-fill the uncovered region."""
    import pytest

    mesh = make_mesh("data=8", devices=devices8)
    state, _ = _fresh_state(mesh, DataParallel())
    path = str(tmp_path / "ckpt_dir")
    checkpoint.save_sharded(path, state, epoch=0)

    # fake a model-size change by doubling one leaf in the template
    template, _ = _fresh_state(mesh, DataParallel())
    k = template.params["fc1"]["kernel"]
    template.params["fc1"]["kernel"] = jax.numpy.zeros(
        (k.shape[0] * 2, k.shape[1]), k.dtype)
    with pytest.raises(ValueError, match="saved with shape"):
        checkpoint.restore(path, template)


def test_sharded_restore_pre_generation_layout(tmp_path, devices8):
    """Checkpoints written before the generation protocol (unprefixed part
    names, no 'generation' manifest key) must still restore."""
    import json

    mesh = make_mesh("data=8", devices=devices8)
    state, _ = _fresh_state(mesh, DataParallel())
    path = str(tmp_path / "ckpt_dir")
    checkpoint.save_sharded(path, state, epoch=0)
    # rewrite to the old layout
    man_path = os.path.join(path, "manifest.json")
    with open(man_path) as f:
        man = json.load(f)
    gen = man.pop("generation")
    with open(man_path, "w") as f:
        json.dump(man, f)
    for ext in (".json", ".npz"):
        os.rename(os.path.join(path, f"part-g{gen}-00000{ext}"),
                  os.path.join(path, f"part-00000{ext}"))
    with open(os.path.join(path, "part-00000.json")) as f:
        part = json.load(f)
    part["file"] = "part-00000.npz"
    with open(os.path.join(path, "part-00000.json"), "w") as f:
        json.dump(part, f)

    template, _ = _fresh_state(mesh, DataParallel())
    restored = checkpoint.restore(path, template)
    _assert_states_equal(state, restored)


# ------------------------------------------- integrity + retention


def _corrupt_npz_entry(path, match):
    """Rewrite one entry of an npz with different bytes — a VALID zip
    container with wrong content, the corruption only the framework's
    own CRC-32 verification can catch (a truncated file would already
    trip the zip layer)."""
    with np.load(path, allow_pickle=False) as z:
        data = {k: z[k] for k in z.files}
    key = next(k for k in data if match in k)
    data[key] = np.zeros_like(data[key]) + 7
    with open(path, "wb") as f:
        np.savez(f, **data)


def test_v1_integrity_checksum_fallback_and_retention(tmp_path, devices8):
    """keep_last rotation + verify-on-restore + automatic fallback for
    the v1 single-file format: corrupting the newest checkpoint's bytes
    (valid zip, wrong content) raises a clear CheckpointCorruptError,
    and restore_with_fallback lands on the rotated previous good save,
    reporting ITS manifest."""
    import pytest

    mesh = make_mesh("data=8", devices=devices8)
    state, _ = _fresh_state(mesh, DataParallel())
    path = str(tmp_path / "ck.npz")
    checkpoint.save(path, state, epoch=1, keep_last=3)
    checkpoint.save(path, state, epoch=2, keep_last=3)
    assert os.path.exists(path + ".prev-1")      # retention rotated
    assert checkpoint.load_manifest(path)["checksums"]

    _corrupt_npz_entry(path, "fc1::kernel")
    template, _ = _fresh_state(mesh, DataParallel())
    with pytest.raises(checkpoint.CheckpointCorruptError,
                       match="CRC-32"):
        checkpoint.restore(path, template)
    restored, manifest = checkpoint.restore_with_fallback(path, template)
    assert manifest["epoch"] == 1                # the previous good save
    _assert_states_equal(state, restored)


def test_sharded_integrity_and_generation_fallback(tmp_path, devices8):
    """v2: per-entry CRCs verify on restore; with keep_last=2 the
    previous generation's parts survive the commit prune and a corrupt
    part in the newest generation falls back to it."""
    import pytest

    mesh = make_mesh("data=2,fsdp=4", devices=devices8)
    state, _ = _fresh_state(mesh, FSDP(min_size_to_shard=64))
    path = str(tmp_path / "ckdir")
    checkpoint.save_sharded(path, state, epoch=1, keep_last=2)
    checkpoint.save_sharded(path, state, epoch=2, keep_last=2)
    man = checkpoint.load_manifest(path)
    assert [h["epoch"] for h in man["history"]] == [2, 1]
    assert any(f.startswith("part-g0-") for f in os.listdir(path))

    part = next(f for f in os.listdir(path)
                if f.startswith("part-g1-") and f.endswith(".npz"))
    _corrupt_npz_entry(os.path.join(path, part), "fc1::kernel")
    template, _ = _fresh_state(mesh, FSDP(min_size_to_shard=64))
    shardings = jax.tree.map(lambda a: a.sharding, template)
    with pytest.raises(checkpoint.CheckpointCorruptError,
                       match="CRC-32"):
        checkpoint.restore(path, template, shardings=shardings)
    restored, manifest = checkpoint.restore_with_fallback(
        path, template, shardings)
    assert manifest["epoch"] == 1
    _assert_states_equal(state, restored)


def test_async_checkpointer_single_file(tmp_path, devices8):
    mesh = make_mesh("data=8", devices=devices8)
    state, step = _fresh_state(mesh, DataParallel())
    path = str(tmp_path / "ckpt_async.npz")
    with checkpoint.AsyncCheckpointer() as ck:
        ck.save(path, state, epoch=1)
        ck.save(path, state, epoch=2)    # joins the first write
    manifest = checkpoint.load_manifest(path)
    assert manifest["epoch"] == 2
    template, _ = _fresh_state(mesh, DataParallel())
    restored = checkpoint.restore(path, template)
    _assert_states_equal(state, restored)


def test_async_checkpointer_surfaces_write_errors(tmp_path, devices8):
    mesh = make_mesh("data=8", devices=devices8)
    state, _ = _fresh_state(mesh, DataParallel())
    bad = str(tmp_path / "collides")
    os.makedirs(bad)                 # os.replace(tmp, <dir>) -> OSError
    import pytest
    ck = checkpoint.AsyncCheckpointer()
    ck.save(bad, state, epoch=0)
    with pytest.raises(OSError):
        ck.close()
