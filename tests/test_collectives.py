"""ZeRO-1 collectives (parallel/collectives.py): exact reduce-scatter /
all-gather over the dp axis, the block-scaled int8 quantized
reduce-scatter's error bound on adversarial (large-dynamic-range)
gradients, the bf16 small-chunk fallback, and the update-shard spec
chooser the step functions and init shardings both rely on."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import make_mesh
from distributed_compute_pytorch_tpu.parallel import collectives as coll


def _run_manual(fn, mesh, partials, out_sharded=True):
    """Run ``fn(local_contribution)`` inside a shard_map manual over
    ``data`` where rank i's local value is ``partials[i]`` (leading dim
    = dp axis)."""
    body = jax.shard_map(
        lambda part: fn(part[0])[None],
        mesh=mesh, in_specs=P("data"),
        out_specs=P("data") if out_sharded else P(),
        axis_names={"data"})
    return jax.jit(body)(partials)


def _mesh4():
    return make_mesh("data=4", devices=jax.devices()[:4])


# ------------------------------------------------------------ exact RS/AG


def test_reduce_scatter_sums_partials(devices8):
    mesh = _mesh4()
    parts = jax.random.normal(jax.random.key(0), (4, 16, 8))
    out = _run_manual(lambda g: coll.reduce_scatter(g, "data", dim=0),
                      mesh, parts)
    # rank i's output is rows [4i, 4i+4) of the cross-rank sum
    np.testing.assert_allclose(np.asarray(out).reshape(16, 8),
                               np.asarray(parts).sum(0), rtol=1e-6)


def test_all_gather_inverts_shard_slice(devices8):
    mesh = _mesh4()
    parts = jax.random.normal(jax.random.key(1), (4, 8, 4))

    def body(g):
        mine = coll.shard_slice(g, "data", 4, dim=0)   # [2, 4] local
        return coll.all_gather(mine, "data", dim=0)    # back to [8, 4]

    # each rank slice-gathers ITS OWN value: rank i reassembles a mix of
    # every rank's slices — with identical inputs it is the identity
    same = jnp.broadcast_to(parts[0], parts.shape)
    out = _run_manual(body, mesh, same)
    np.testing.assert_allclose(np.asarray(out)[0], np.asarray(parts[0]),
                               rtol=1e-6)


# ------------------------------------------------- quantized reduce-scatter


def _adversarial_partials(key, n, shape, block):
    """Per-rank gradients with hostile dynamic range: magnitudes spanning
    ~8 decades BETWEEN blocks (so one global scale would destroy small
    blocks) and sign-mixed values within each block."""
    k1, k2 = jax.random.split(jax.random.key(key))
    vals = jax.random.normal(k1, (n, *shape))
    total = int(np.prod(shape))
    nblk = -(-total // block)
    exps = jax.random.randint(k2, (n, nblk), -4, 5).astype(jnp.float32)
    scale = jnp.repeat(10.0 ** exps, block, axis=1)[:, :total]
    return (vals.reshape(n, total) * scale).reshape(n, *shape)


def test_quantized_rs_error_bounded_adversarial(devices8):
    """|quantized RS - exact f32 reduce| <= sum over ranks of each
    rank's half-quantization-step for the block the element lives in —
    on gradients whose blocks span ~8 decades of magnitude."""
    mesh = _mesh4()
    n, shape, block = 4, (32, 256), 64
    parts = _adversarial_partials(5, n, shape, block)

    quant = _run_manual(
        lambda g: coll.quantized_reduce_scatter(
            g, "data", n, dim=0, block=block, min_int8_elems=1),
        mesh, parts)
    got = np.asarray(quant).reshape(shape)
    ref = np.asarray(parts, np.float64).sum(0)

    # elementwise bound: each rank contributes at most half its block's
    # quantization step (absmax/127)
    p = np.asarray(parts, np.float64).reshape(n, -1)
    pad = (-p.shape[1]) % block
    pb = np.pad(p, ((0, 0), (0, pad))).reshape(n, -1, block)
    step = np.abs(pb).max(axis=2, keepdims=True) / 127.0
    bound = np.broadcast_to(0.5 * step, pb.shape).reshape(
        n, -1)[:, :p.shape[1]].sum(0)
    err = np.abs(got.reshape(-1) - ref.reshape(-1))
    assert (err <= bound + 1e-12).all(), float((err - bound).max())
    # and quantization actually happened (this is not the exact path)
    assert err.max() > 0


def test_quantized_rs_bf16_fallback_small_chunks(devices8):
    """Chunks below min_int8_elems exchange bf16: no scale machinery,
    error at bf16 resolution of each contribution."""
    mesh = _mesh4()
    parts = jax.random.normal(jax.random.key(7), (4, 8, 16))
    out = _run_manual(
        lambda g: coll.quantized_reduce_scatter(
            g, "data", 4, dim=0, min_int8_elems=10_000),
        mesh, parts)
    ref = np.asarray(parts, np.float64).sum(0)
    # bf16 has ~3 decimal digits; 4 summed contributions of O(1) values
    np.testing.assert_allclose(np.asarray(out).reshape(8, 16), ref,
                               atol=0.05)


def test_quantized_rs_rejects_indivisible():
    mesh = _mesh4()
    with pytest.raises(ValueError, match="does not divide"):
        _run_manual(
            lambda g: coll.quantized_reduce_scatter(g, "data", 4, dim=0),
            mesh, jnp.ones((4, 6, 3)))


def test_quantized_rs_matches_exact_on_benign_grads(devices8):
    """Sanity: on O(1) same-scale gradients the int8 path lands within a
    small relative error of the exact reduce (the bound test above is
    the adversarial guarantee; this is the common case)."""
    mesh = _mesh4()
    parts = jax.random.normal(jax.random.key(9), (4, 64, 64))
    out = _run_manual(
        lambda g: coll.quantized_reduce_scatter(
            g, "data", 4, dim=0, block=128, min_int8_elems=1),
        mesh, parts)
    ref = np.asarray(parts).sum(0)
    err = np.abs(np.asarray(out).reshape(64, 64) - ref)
    assert err.max() < 0.15, err.max()   # 4 ranks x (absmax/127)/2 each


# ------------------------------------------------------------ spec chooser


def test_update_shard_spec_largest_divisible_dim():
    axes = ("data",)
    assert coll.update_shard_spec((9216, 128), 8, axes) == P("data", None)
    assert coll.update_shard_spec((2, 64, 192), 8, axes) == \
        P(None, None, "data")
    # indivisible everywhere -> replicated
    assert coll.update_shard_spec((7, 9, 11), 8, axes, min_size=1) == P()
    # tiny leaves stay replicated even when divisible
    assert coll.update_shard_spec((8, 8), 8, axes) == P()
    # scalars
    assert coll.update_shard_spec((), 8, axes) == P()
    # dp size 1 -> nothing to shard
    assert coll.update_shard_spec((9216, 128), 1, axes) == P()
    # multi-axis dp folds both names onto the chosen dim
    assert coll.update_shard_spec((4096,), 8, ("data", "fsdp")) == \
        P(("data", "fsdp"))


def test_spec_shard_dim():
    assert coll.spec_shard_dim(P("data", None)) == 0
    assert coll.spec_shard_dim(P(None, None, "data")) == 2
    assert coll.spec_shard_dim(P()) is None


def test_tree_update_specs_consistent_for_params_and_moments():
    params = {"w": jnp.zeros((512, 64)), "b": jnp.zeros((64,))}
    moments = jax.tree.map(jnp.zeros_like, params)
    sp = coll.tree_update_specs(params, 4, ("data",))
    sm = coll.tree_update_specs(moments, 4, ("data",))
    assert sp == sm
    assert sp["w"] == P("data", None) and sp["b"] == P()
