"""Quantized KV pool (ISSUE 16): int8 block-scaled K/V end-to-end.

``--kv_dtype int8`` stores every pool block as int8 with per-row f32
scales, quantizing on the admission/decode write and dequantizing
inside the gathered-attention read. Token-identical parity is
deliberately surrendered; the relaxed contract pinned here is

- bounded per-position error at the quantizer (round-trip unit test),
- high greedy agreement with the bf16 pool on real streams (gpt2 and
  llama, mesh and no-mesh),
- everything AROUND the numerics stays exact: COW-under-verify
  discipline, tier demote->promote returns the SAME int8 bytes and
  scales bit-for-bit (no requantization round trip), handoff payloads
  CRC their scales and decline (never raise) on corruption or a dtype
  mismatch, reconstruction-after-fault replays under int8, and the
  CLI/journal refuse inconsistent dtype configs up front.

Kept CPU-cheap per the tier-1 budget note: tiny models, starved pools,
shared compiled programs.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.kv_pool import (
    TIER_DEVICE, TIER_HOST)
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request)
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
from distributed_compute_pytorch_tpu.utils.quantize import quantize_kv


# ------------------------------------------------- unit: the quantizer


def test_quantize_kv_roundtrip_error_bound():
    """Per-row symmetric int8: |x - dequant(q)| <= scale/2 elementwise
    (half a quantization step), scales are per-(row) over the head dim,
    and an all-zero row round-trips to exactly zero (the 1e-12 floor
    never divides by zero)."""
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 3, 4, 8)).astype(np.float32) * 7.0
    x[0, 0, 0, :] = 0.0
    q, scale = quantize_kv(jnp.asarray(x))
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert q.shape == x.shape and scale.shape == x.shape[:-1] + (1,)
    deq = np.asarray(q, np.float32) * np.asarray(scale)
    err = np.abs(x - deq)
    assert (err <= np.asarray(scale) / 2 + 1e-7).all()
    assert (deq[0, 0, 0, :] == 0).all()
    # int8 range actually used: abs-max rows land on +-127
    assert int(np.abs(np.asarray(q)).max()) == 127


# ------------------------------------------- serving: greedy agreement
#
# bt=32 for BOTH engines (int8's Pallas window forces 32; pinning the
# bf16 engine to the same block size keeps the comparison apples to
# apples). 33-token heads end one token into their second block, so
# COW attaches run.

_COMMON = dict(slots=1, t_max=64, prompt_buf=40, segment=4,
               prefix_cache=True, pool_blocks=8, kv_block_tokens=32)


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=256))
    params, _ = model.init(jax.random.key(0))
    return model, params


def _hot(rng, n=3, ln=33):
    return [[int(t) for t in rng.integers(0, 256, ln)] for _ in range(n)]


def _reqs(heads, seed=1):
    r = np.random.default_rng(seed)
    return [Request(h + [int(t) for t in r.integers(0, 256, 2)], 6)
            for h in heads]


def _match_rate(want, got):
    """Positional token agreement across two serve outputs."""
    hit = total = 0
    for w, g in zip(want, got):
        for ws, gs in zip(w, g):
            total += len(ws)
            hit += sum(int(a == b) for a, b in zip(ws, gs))
    return hit / max(1, total)


def test_int8_pool_greedy_match_gpt2(gpt2):
    """The relaxed parity pin: an int8 pool serves the same greedy
    streams as bf16 at >=99% positional agreement (this fixed tiny
    stream agrees exactly), with the kvq counters live and zero
    leaks."""
    model, params = gpt2
    rng = np.random.default_rng(5)
    A, B = _hot(rng, 2)
    waves = [([A], 1), ([A, B], 2), ([B, A], 3)]
    bf = ContinuousBatcher(model, params, **_COMMON)
    q8 = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8")
    assert "scale" in q8._caches[0] and "scale" not in bf._caches[0]
    want = [bf.serve(_reqs(h, seed=s)) for h, s in waves]
    got = [q8.serve(_reqs(h, seed=s)) for h, s in waves]
    assert _match_rate(want, got) >= 0.99
    assert q8.kvq["quantized_blocks"] > 0
    assert q8.kvq["dequant_reads"] > 0
    assert q8.kvq["bytes_saved_hbm"] > 0
    assert q8.last_block_leaks == 0 and q8.last_slot_leaks == 0
    # the counters ride the public snapshot (heartbeats/metrics JSONL)
    snap = q8.stats_snapshot()
    assert snap["kvq"]["quantized_blocks"] == q8.kvq["quantized_blocks"]
    # bf16 engines keep the surface, all-zero (dashboards don't branch)
    assert bf.stats_snapshot()["kvq"]["quantized_blocks"] == 0


def test_int8_pool_greedy_match_llama():
    """Second model family (RoPE/GQA): rotary phases bake into the
    quantized K, so the dequantized read must reproduce them."""
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=256))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    A, B = _hot(rng, 2)
    bf = ContinuousBatcher(model, params, **_COMMON)
    q8 = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8")
    want = [bf.serve(_reqs([h], seed=i)) for i, h in enumerate((A, B, A))]
    got = [q8.serve(_reqs([h], seed=i)) for i, h in enumerate((A, B, A))]
    assert _match_rate(want, got) >= 0.99
    assert q8.last_block_leaks == 0


def test_int8_mesh_sharded(devices8, gpt2):
    """Under a data-sharded mesh the scale leaf shards beside the int8
    pool (same _POOL_SPEC, block axis over data/fsdp) and greedy
    agreement holds against the sharded bf16 engine."""
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model, params = gpt2
    mesh = make_mesh("data=2", devices=devices8[:2])
    sparams = shard_pytree(params, pick_strategy(mesh, model), mesh)
    rng = np.random.default_rng(13)
    A, B = _hot(rng, 2)
    common = dict(slots=2, t_max=64, prompt_buf=40, segment=4,
                  prefix_cache=True, pool_blocks=10, kv_block_tokens=32,
                  mesh=mesh)
    bf = ContinuousBatcher(model, sparams, **common)
    q8 = ContinuousBatcher(model, sparams, **common, kv_dtype="int8")
    assert not q8._caches[0]["kv"].sharding.is_fully_replicated
    assert not q8._caches[0]["scale"].sharding.is_fully_replicated
    want = [bf.serve(_reqs([h], seed=i))
            for i, h in enumerate((A, B, A))]
    got = [q8.serve(_reqs([h], seed=i))
           for i, h in enumerate((A, B, A))]
    assert _match_rate(want, got) >= 0.99
    assert q8.last_block_leaks == 0


def test_int8_pool_holds_1p8x_the_tokens_a_byte_at_hd64():
    """The capacity the int8 pool is for, from the live cache arrays: a
    cached token-head costs hd + 4 bytes under int8 (the +4 is its f32
    scale) against 2 * hd under bf16, so at a production-shaped hd = 64
    a pool byte holds 2 * 64 / 68 = 1.88x the resident prefix tokens
    (tiny()'s hd = 16 gives 1.6x by geometry, hence the wider model).
    Float K/V slabs count at the 2 bytes they ship as on the chip (the
    CPU holds f32 stand-ins); scales count at their full width."""
    cfg = dataclasses.replace(GPT2Config.tiny(), d_model=128, num_heads=2,
                              max_seq_len=256)
    model = GPT2(cfg)
    assert model.kv_cache_spec()[1] == 64
    params, _ = model.init(jax.random.key(1))
    bf = ContinuousBatcher(model, params, **_COMMON)
    q8 = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8")

    def block_bytes(cb):
        total = 0
        for cache in cb._caches:
            for name, leaf in cache.items():
                per_block = int(np.prod(leaf.shape)) // leaf.shape[1]
                ships_as_bf16 = (name == "kv" and jnp.issubdtype(
                    leaf.dtype, jnp.floating))
                total += per_block * (2 if ships_as_bf16
                                      else leaf.dtype.itemsize)
        return total

    assert bf.bt == q8.bt                 # the same tokens a block
    assert block_bytes(bf) / block_bytes(q8) >= 1.8


def test_logit_probe_finite_kl(gpt2):
    """The bounded-error gate: per-position KL between the
    bf16 and int8 probes is finite and small on a short stream, and
    the probe leaves the live pool untouched."""
    model, params = gpt2
    rng = np.random.default_rng(3)
    toks = [int(t) for t in rng.integers(0, 256, 9)]
    bf = ContinuousBatcher(model, params, **_COMMON)
    q8 = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8")
    lb, lq = bf.logit_probe(toks), q8.logit_probe(toks)
    assert lb.shape == lq.shape == (len(toks), 256)
    p = jax.nn.softmax(jnp.asarray(lb), axis=-1)
    kl = np.asarray((p * (jax.nn.log_softmax(jnp.asarray(lb), -1)
                          - jax.nn.log_softmax(jnp.asarray(lq), -1))
                     ).sum(-1))
    assert np.isfinite(kl).all() and kl.max() < 0.5
    # probe never touched pool accounting
    assert q8._pool.free_count == q8._pool.num_blocks - 1  # trash only


# ------------------------------------ speculation / COW under int8


def test_cow_under_verify_with_scales(gpt2):
    """Speculation's write-span COW must copy BOTH leaves: spec-on int8
    equals spec-off int8 token for token (the accept/reject rule is
    exact within one numeric regime), with COW copies exercised and
    zero leaks — a scale leaf left shared would let a rejected draft
    corrupt an attached prefix's dequant."""
    model, params = gpt2
    rng = np.random.default_rng(11)
    A, B = _hot(rng, 2)
    stream = [([A], 1), ([B], 2), ([A], 3), ([B], 4)]
    plain = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8")
    spec = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8",
                             speculate=3)
    want = [plain.serve(_reqs(h, seed=s)) for h, s in stream]
    got = [spec.serve(_reqs(h, seed=s)) for h, s in stream]
    assert got == want
    assert spec.spec["verify_segments"] >= 1
    assert spec.stats["cow_copies"] >= 1
    assert spec.kvq["dequant_reads"] >= 1
    assert spec.last_block_leaks == 0 and spec.last_slot_leaks == 0


# ------------------------------------------------ tiers under int8


def test_tier_demote_promote_int8_bit_exact(gpt2):
    """Demote->promote returns the SAME int8 payload: both the
    quantized bytes and the f32 scales restore bit-for-bit into new
    device blocks — the tier never requantizes, so spill depth adds
    zero numeric drift."""
    model, params = gpt2
    rng = np.random.default_rng(17)
    A, B, C = _hot(rng, 3)
    on = ContinuousBatcher(model, params,
                           **dict(_COMMON, pool_blocks=5),
                           kv_dtype="int8", host_cache_blocks=8)
    on.serve(_reqs([A], seed=1))
    (entry,) = on._radix.entries
    before = [(np.asarray(c["kv"][:, entry.blocks]),
               np.asarray(c["scale"][:, entry.blocks]))
              for c in on._caches]
    on.serve(_reqs([B], seed=2))
    on.serve(_reqs([C], seed=3))
    assert entry.tier == TIER_HOST and entry.blocks == []
    on.serve(_reqs([A], seed=4))
    assert entry.tier == TIER_DEVICE
    for li, (c, (bk, bs)) in enumerate(zip(on._caches, before)):
        np.testing.assert_array_equal(
            np.asarray(c["kv"][:, entry.blocks]), bk,
            err_msg=f"layer {li} kv")
        np.testing.assert_array_equal(
            np.asarray(c["scale"][:, entry.blocks]), bs,
            err_msg=f"layer {li} scale")
    assert on.kvq["bytes_saved_d2h"] > 0
    assert on.last_host_block_leaks == 0


def test_disk_spill_int8_with_scale_sidecars(gpt2, tmp_path):
    """Host pressure cascades int8 entries to disk with scale CRCs in
    the sidecars; disk hits promote back with the stream agreeing with
    an unspilled int8 run, and the sidecar records carry the scale
    geometry."""
    model, params = gpt2
    rng = np.random.default_rng(19)
    A, B, C = _hot(rng, 3)
    stream = [(h, i) for i, h in enumerate((A, B, C, A, B, C))]
    cfg = dict(_COMMON, kv_dtype="int8", pool_blocks=5)
    off = ContinuousBatcher(model, params, **cfg)
    want = [off.serve(_reqs([h], seed=s)) for h, s in stream]
    on = ContinuousBatcher(model, params, **cfg, host_cache_blocks=2,
                           disk_cache_dir=str(tmp_path))
    got = [on.serve(_reqs([h], seed=s)) for h, s in stream]
    assert got == want          # same numeric regime: exact agreement
    t = dict(on.tier)
    assert t["disk_spills"] >= 1 and t["disk_hits"] >= 1
    assert t["disk_crc_miss"] == 0
    for rec in on._tier.disk.index.values():
        assert isinstance(rec.get("scale_crc"), int)
        assert rec.get("scale_dtype") == "float32"
        assert rec.get("scale_shape", [])[-1] == 1
    assert on.last_block_leaks == 0 and on.last_host_block_leaks == 0


def test_adopt_refuses_cross_dtype_shards(gpt2, tmp_path):
    """Restart adoption is dtype-gated: a bf16 engine skips int8
    shards (scale sidecars present) and an int8 engine skips bf16
    shards — adopting either would feed the compiled promote wrong
    bytes. Declines, never raises."""
    model, params = gpt2
    rng = np.random.default_rng(23)
    A, B, C = _hot(rng, 3)
    cfg = dict(_COMMON, kv_dtype="int8", pool_blocks=5)
    on = ContinuousBatcher(model, params, **cfg, host_cache_blocks=2,
                           disk_cache_dir=str(tmp_path))
    for i, h in enumerate((A, B, C)):
        on.serve(_reqs([h], seed=i))
    on._tier._spill_one()
    on._tier.disk.drain()
    assert on._tier.disk.index     # int8 shards with scale sidecars
    # a bf16 engine over the same directory adopts nothing
    bf = ContinuousBatcher(model, params, **dict(_COMMON, pool_blocks=5),
                           host_cache_blocks=2,
                           disk_cache_dir=str(tmp_path))
    assert bf.tier["disk_adopted"] == 0
    # a fresh int8 engine adopts them all
    q8 = ContinuousBatcher(model, params, **cfg, host_cache_blocks=2,
                           disk_cache_dir=str(tmp_path))
    assert q8.tier["disk_adopted"] == len(on._tier.disk.index)


# ---------------------------------------------- handoff under int8


def test_handoff_int8_export_import(gpt2):
    """export_prefix carries the int8 blocks + scales with their own
    CRC; import lands them and the next admission attaches — serving
    agreement with the exporter, handoff bytes roughly halved
    (bytes_saved_handoff counts the bf16 payload it replaced)."""
    model, params = gpt2
    rng = np.random.default_rng(29)
    (A,) = _hot(rng, 1)
    cfg = dict(_COMMON, kv_dtype="int8")
    src = ContinuousBatcher(model, params, **cfg)
    dst = ContinuousBatcher(model, params, **cfg)
    src.serve(_reqs([A], seed=1))
    pay = src.export_prefix(A + [7])
    assert pay is not None and pay["kv_dtype"] == "int8"
    assert pay["kv"].dtype == np.int8
    assert pay["scale"].dtype == np.float32
    assert isinstance(pay["scale_crc"], int)
    assert src.kvq["bytes_saved_handoff"] > 0
    assert dst.import_prefix(pay)
    assert dst.serve(_reqs([A], seed=9)) == src.serve(_reqs([A], seed=9))
    assert dst.stats["prefix_hits"] >= 1
    assert dst.last_block_leaks == 0


def test_handoff_corrupt_scale_and_dtype_decline(gpt2):
    """The decline drills: a flipped scale byte fails scale_crc, a
    dtype-stamp mismatch hits its own counter — both decline to the
    replay fallback, neither raises, nothing changes in the
    importer."""
    model, params = gpt2
    rng = np.random.default_rng(31)
    (A,) = _hot(rng, 1)
    cfg = dict(_COMMON, kv_dtype="int8")
    src = ContinuousBatcher(model, params, **cfg)
    src.serve(_reqs([A], seed=1))
    pay = src.export_prefix(A + [7])
    sc = np.array(pay["scale"])
    sc.flat[0] += 1.0
    bad = {**pay, "scale": sc}
    dst = ContinuousBatcher(model, params, **cfg)
    assert not dst.import_prefix(bad)
    assert dst.prefill["handoff_declined"] == 1
    assert dst.kvq["handoff_dtype_declined"] == 0
    # int8 payload into a bf16 pool: the stamp declines before any
    # geometry work, on its own counter
    bf = ContinuousBatcher(model, params, **_COMMON)
    assert not bf.import_prefix(pay)
    assert bf.kvq["handoff_dtype_declined"] == 1
    assert bf.prefill["handoff_declined"] == 1
    # and the reverse: a bf16 payload never lands in an int8 pool
    bf.serve(_reqs([A], seed=2))
    bpay = bf.export_prefix(A + [7])
    assert bpay is not None and "scale" not in bpay
    q8 = ContinuousBatcher(model, params, **cfg)
    assert not q8.import_prefix(bpay)
    assert q8.kvq["handoff_dtype_declined"] == 1


def test_router_refuses_mixed_dtype_fleet(gpt2):
    """One kv_dtype per fleet: a mixed router would silently degrade
    every migration/handoff to full replay, so construction refuses."""
    from distributed_compute_pytorch_tpu.serve_router import ServeRouter
    model, params = gpt2
    bf = ContinuousBatcher(model, params, **_COMMON)
    q8 = ContinuousBatcher(model, params, **_COMMON, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        ServeRouter([bf, q8])
    r = ServeRouter([q8])
    assert r.kv_dtype == "int8"


# ------------------------------------- faults / recovery under int8


def test_reconstruction_after_fault_int8(gpt2):
    """A device fault mid-stream under int8: reconstruction replays
    host-tracked tokens through the quantized pool and the resumed
    streams equal a fault-free int8 run, zero leaks."""
    model, params = gpt2
    rng = np.random.default_rng(37)
    A, B = _hot(rng, 2)
    cfg = dict(_COMMON, kv_dtype="int8")
    plain = ContinuousBatcher(model, params, **cfg)
    want = plain.serve(_reqs([A, B], seed=1))
    rec = ContinuousBatcher(model, params, **cfg)
    res = rec.serve_detailed(
        _reqs([A, B], seed=1),
        chaos=ChaosInjector(fault_at_segment=2, fault_mode="raise"))
    assert rec.stats["reconstructions"] == 1
    assert [r.tokens for r in res] == want
    assert rec.last_block_leaks == 0 and rec.last_slot_leaks == 0


def test_journal_refuses_dtype_mismatch(gpt2, tmp_path):
    """Journal recovery under a different --kv_dtype is refused with a
    one-line error: the journaled streams were recorded under another
    numeric contract. Same dtype passes; a pre-config journal (no
    config frame) is treated as bf16."""
    from distributed_compute_pytorch_tpu import serve_journal
    j = serve_journal.ServeJournal(str(tmp_path))
    j.config({"kv_dtype": "int8"})
    j.admit("req-0", [1, 2, 3], 4)
    j.close()
    m = serve_journal.recover(str(tmp_path))
    assert m.config == {"kv_dtype": "int8"}
    assert "req-0" in m.incomplete
    # cli_serve's refusal path, drilled via the flag check itself
    from distributed_compute_pytorch_tpu.cli_serve import main
    base = ["--ckpt_path", "nope.npz", "--requests", "nope.txt",
            "--journal_dir", str(tmp_path)]
    with pytest.raises(SystemExit, match="kv_dtype"):
        main(base + ["--kv_dtype", "bf16"])


def test_constructor_and_cli_validation(gpt2):
    """--kv_dtype validation: the constructor rejects unknown dtypes,
    the CLI rejects them at argparse level."""
    model, params = gpt2
    with pytest.raises(ValueError, match="kv_dtype"):
        ContinuousBatcher(model, params, slots=1, t_max=64,
                          prompt_buf=40, segment=4, kv_dtype="fp8")
    from distributed_compute_pytorch_tpu.cli_serve import main
    with pytest.raises(SystemExit):
        main(["--ckpt_path", "x.npz", "--requests", "y.txt",
              "--kv_dtype", "fp8"])
