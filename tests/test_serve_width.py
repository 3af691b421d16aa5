"""Width-bucketed paged decode (ISSUE 19): every decode/verify dispatch
slices the block tables to the smallest power-of-two rung covering the
live working set, so per-tick KV gather traffic tracks live tokens, not
``t_max``. Bucketing must be a pure TRAFFIC optimisation — slots beyond
a row's live extent are mask-invalid either way — so every drill here
is a token-parity pin of bucketing-on against ``decode_width_buckets=1``
(a single full-horizon bucket: the pre-bucketing program, byte for
byte), across the paths that ship a table: plain decode crossing a
bucket edge mid-stream (greedy AND sampled), spec-verify windows at the
edge, the int8 ``scale`` leaf gathered through the same slice, a
mesh-sharded slice, tier promotion feeding a sliced dispatch, and
fault-reconstruction replay across a bucket growth. Expensive drills
(mesh, tier, faults, spec) ride the ``slow`` marker per the tier-1
budget note.
"""

import dataclasses

import jax
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request)
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
from distributed_compute_pytorch_tpu.spec_decode import SpecConfig


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return model, params


def _edge_requests(rng, n=4, long_new=30):
    """A mix whose longest row crosses at least one bucket edge
    mid-stream (bt=8 on the CPU f32 path: ~5 prompt + 30 new spans the
    2-block rung into the 8-block one) while short rows stay narrow."""
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 250, size=5)],
                    max_new=long_new)]
    for _ in range(n - 1):
        ln = int(rng.integers(2, 9))
        reqs.append(Request(
            tokens=[int(t) for t in rng.integers(1, 250, size=ln)],
            max_new=int(rng.integers(3, 9))))
    return reqs


def _clone(reqs):
    return [dataclasses.replace(r, tokens=list(r.tokens)) for r in reqs]


def test_ladder_shape_and_validation(gpt2):
    """The ladder is power-of-two block counts capped at (and always
    ending on) nb; decode_width_buckets keeps the widest N rungs, 1
    being the full-horizon-only off switch; <1 is refused — both here
    and at the CLI flag."""
    model, params = gpt2
    cb = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=10, segment=4)
    assert cb._width_ladder == (1, 2, 4, 8) and cb.nb == 8
    assert cb._width_ladder[-1] == cb.nb
    assert all(b % a == 0 for a, b in zip(cb._width_ladder,
                                          cb._width_ladder[1:]))
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=10, segment=4,
                            decode_width_buckets=1)
    assert off._width_ladder == (8,)      # bucketing off = widest only
    two = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=10, segment=4,
                            decode_width_buckets=2)
    assert two._width_ladder == (4, 8)
    # a non-power-of-two horizon still tops out exactly at nb
    ragged = ContinuousBatcher(model, params, slots=2, t_max=88,
                               prompt_buf=10, segment=4)
    assert ragged._width_ladder[-1] == ragged.nb == 11
    with pytest.raises(ValueError, match="decode_width_buckets"):
        ContinuousBatcher(model, params, slots=2, t_max=64,
                          prompt_buf=10, segment=4,
                          decode_width_buckets=0)
    # the smallest rung is exact: _bucket_width covers the need
    for need in (1, 7, 8, 9, 17, 63, 64):
        w = cb._bucket_width(need)
        assert w in cb._width_ladder and w * cb.bt >= need


def test_cli_rejects_bad_width_buckets():
    from distributed_compute_pytorch_tpu.cli_serve import main as serve_main
    with pytest.raises(SystemExit, match="decode_width_buckets"):
        serve_main(["--ckpt_path", "x", "--requests", "y",
                    "--decode_width_buckets", "0"])


@pytest.mark.parametrize("t_max,long_new,saving", [
    (64, 30, 1.0),
    # a horizon provisioned DEEP for its traffic (32 blocks, the long
    # row peaks near the 16-block rung): the waste the ladder is for,
    # so the reads the full width would have made are at least twice
    # the reads made
    (256, 96, 2.0),
])
def test_parity_crossing_bucket_edge_greedy_and_sampled(t_max, long_new,
                                                        saving):
    """The core contract: bucketing on vs off is token-identical while
    the long row GROWS its bucket mid-stream, with sampled rows amid
    greedy ones (the (seed, tokens-so-far) key schedule must not see
    the width), and the gather counters must show the traffic win."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(),
                                     max_seq_len=max(128, t_max)))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(19)
    reqs = _edge_requests(rng, long_new=long_new)
    for i in (1, 3):
        reqs[i].temperature = 0.9
        reqs[i].seed = 90 + i

    def run(**kw):
        cb = ContinuousBatcher(model, params, slots=2, t_max=t_max,
                               prompt_buf=10, segment=4, **kw)
        return cb, cb.serve(_clone(reqs))

    on, got = run()
    off, want = run(decode_width_buckets=1)
    assert got == want
    assert on.width["bucket_growths"] >= 1
    assert on.width["gathered_block_reads"] \
        < on.width["full_width_block_reads"]
    assert on.width["full_width_block_reads"] \
        >= saving * on.width["gathered_block_reads"]
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0
    assert off.last_slot_leaks == 0 and off.last_block_leaks == 0
    assert on.width["bytes_saved_vs_full"] > 0
    assert 0.0 < on.width["bucket_occupancy"] <= 1.0
    # every dispatched width is a ladder rung -> the compiled program
    # count is bounded by the ladder size
    assert on._widths_dispatched <= set(on._width_ladder)
    # the off engine only ever dispatched the full horizon
    assert off._widths_dispatched == {off.nb}
    assert off.width["gathered_block_reads"] \
        == off.width["full_width_block_reads"]
    # the counters ride the public snapshot
    assert on.stats_snapshot()["width"]["bucket_growths"] \
        == on.width["bucket_growths"]


def test_parity_llama_across_edge(gpt2):
    """Second model family (RoPE/GQA): absolute-position rotary keys
    must survive the narrowed gather unchanged."""
    del gpt2
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(23)
    reqs = _edge_requests(rng)

    def run(**kw):
        cb = ContinuousBatcher(model, params, slots=2, t_max=64,
                               prompt_buf=10, segment=4, **kw)
        return cb.serve(_clone(reqs))

    assert run() == run(decode_width_buckets=1)


def test_int8_scale_leaf_sliced_consistently(gpt2):
    """The int8 pool's ``scale`` leaf is gathered through the SAME
    sliced table as ``kv`` — int8-bucketed vs int8-full is therefore
    exactly token-identical (the relaxed bf16-vs-int8 contract is
    orthogonal: both sides here quantize identically)."""
    model, params = gpt2
    rng = np.random.default_rng(29)
    reqs = _edge_requests(rng)

    def run(**kw):
        cb = ContinuousBatcher(model, params, slots=2, t_max=64,
                               prompt_buf=10, segment=4,
                               kv_dtype="int8", **kw)
        return cb, cb.serve(_clone(reqs))

    on, got = run()
    off, want = run(decode_width_buckets=1)
    assert got == want
    assert "scale" in on._caches[0]
    assert on.width["bucket_growths"] >= 1
    # int8 blocks move fewer bytes per gathered block, and the saved
    # bytes are computed from the REAL leaf geometry (kv + scale)
    assert on._gather_block_bytes == sum(
        leaf.nbytes // leaf.shape[1] for leaf in on._caches[0].values())


def test_prewarm_widths_compiles_ladder(gpt2):
    """prewarm_widths dispatches one throwaway segment per rung (the
    compile the first long session would otherwise eat mid-traffic),
    counts serve.width.prewarmed_programs, and leaves the batcher
    state-identical to fresh — served tokens must not change."""
    model, params = gpt2
    rng = np.random.default_rng(31)
    reqs = _edge_requests(rng)
    cold = ContinuousBatcher(model, params, slots=2, t_max=64,
                             prompt_buf=10, segment=4)
    want = cold.serve(_clone(reqs))
    warm = ContinuousBatcher(model, params, slots=2, t_max=64,
                             prompt_buf=10, segment=4)
    n = warm.prewarm_widths()
    assert n == len(warm._width_ladder)
    assert warm.width["prewarmed_programs"] == n
    assert warm.serve(_clone(reqs)) == want
    # reset() rewinds the bucket to the smallest rung (post-restart
    # recovery re-admits into the smallest bucket, not the widest)
    warm.reset()
    assert warm._cur_width == warm._width_ladder[0]
    assert warm._widths_dispatched == set()


def test_width_priced_router_estimates(gpt2):
    """load_estimate/prefill_cost price decode ticks by the CURRENT
    bucket rung over the full horizon: a fresh (narrow) replica
    undercuts one stretched wide by a long session, and the
    full-horizon bucket reproduces the unweighted legacy prices."""
    model, params = gpt2
    cb = ContinuousBatcher(model, params, slots=1, t_max=64,
                           prompt_buf=8, segment=4)
    off = ContinuousBatcher(model, params, slots=1, t_max=64,
                            prompt_buf=8, segment=4,
                            decode_width_buckets=1)
    assert off.load_estimate(8) == 8              # legacy unweighted
    # fresh: smallest rung (1 of 8 blocks) -> 1/8 the price
    assert cb._cur_width == 1
    assert cb.load_estimate(8) == 1
    cb._cur_width = cb.nb                         # stretched wide
    assert cb.load_estimate(8) == 8
    cb._cur_width = cb.nb // 2
    assert cb.load_estimate(8) == 4
    # chunked prefill stalls are decode segments at the current width
    ch = ContinuousBatcher(model, params, slots=1, t_max=64,
                           prompt_buf=32, segment=4,
                           prefix_cache=True, prefill_chunk_tokens=8)
    assert ch._cur_width == 1
    full = ContinuousBatcher(model, params, slots=1, t_max=64,
                             prompt_buf=32, segment=4,
                             prefix_cache=True, prefill_chunk_tokens=8,
                             decode_width_buckets=1)
    assert ch.prefill_cost(3 * ch._chunk) < full.prefill_cost(3 * ch._chunk)
    # unchunked prefill is prefill compute — width-independent
    assert cb.prefill_cost(100) == 100


@pytest.mark.slow
def test_spec_verify_at_bucket_edge(gpt2):
    """A verify window straddling a rung boundary: the rung must cover
    row_pos + W or the sentinel would drop an in-horizon accepted
    token's K/V — spec-on bucketed must equal spec-on full-width."""
    model, params = gpt2
    rng = np.random.default_rng(37)
    reqs = _edge_requests(rng)

    def run(**kw):
        cb = ContinuousBatcher(model, params, slots=2, t_max=64,
                               prompt_buf=10, segment=4,
                               speculate=SpecConfig(k=3), **kw)
        return cb, cb.serve(_clone(reqs))

    on, got = run()
    off, want = run(decode_width_buckets=1)
    assert got == want
    assert on.spec["verify_segments"] > 0
    assert on.width["bucket_growths"] >= 1
    assert on._widths_dispatched <= set(on._width_ladder)


@pytest.mark.slow
def test_mesh_sharded_slice_parity(gpt2, devices8):
    """Under a mesh the sliced gather reshards by the same
    portable-redistribution move as the full-width one — rows stay
    sharded over data, and bucketed output equals full-width output
    on the SAME mesh."""
    del gpt2
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2", devices=devices8)
    sharded = shard_pytree(params, pick_strategy(mesh, model), mesh)
    rng = np.random.default_rng(41)
    reqs = _edge_requests(rng)

    def run(**kw):
        cb = ContinuousBatcher(model, sharded, slots=2, t_max=64,
                               prompt_buf=10, segment=4, mesh=mesh, **kw)
        return cb, cb.serve(_clone(reqs))

    on, got = run()
    _, want = run(decode_width_buckets=1)
    assert got == want
    assert on.width["bucket_growths"] >= 1
    kv = on._caches[0]["kv"]
    assert not kv.sharding.is_fully_replicated


@pytest.mark.slow
def test_tier_promotion_into_sliced_dispatch(gpt2):
    """A prefix demoted to the host tier, promoted back into DIFFERENT
    device blocks, then decoded through a SLICED table: promotion is a
    whole-pool leaf op, so the narrowed dispatch must read the promoted
    blocks exactly as a full-width one would."""
    model, params = gpt2
    # the deliberately starved device pool (the kv_tier test idiom):
    # a hot set of FOUR 40-token prefixes (5 blocks each) against 16
    # usable blocks, so caching D evicts A into the host tier and the
    # A-rehit promotes it back — into a dispatch whose rung (8 blocks
    # for a ~45-slot working set) is half the 16-block horizon
    kw = dict(slots=1, t_max=128, prompt_buf=48, segment=4,
              prefix_cache=True, pool_blocks=17, host_cache_blocks=64)
    rng = np.random.default_rng(43)
    hot = [[int(t) for t in rng.integers(1, 250, size=40)]
           for _ in range(4)]
    streams = [[Request(tokens=hot[i] + [100 + i], max_new=6)]
               for i in (0, 1, 2, 3, 0)]

    def run(**xkw):
        cb = ContinuousBatcher(model, params, **kw, **xkw)
        return cb, [cb.serve(_clone(s)) for s in streams]

    on, got = run()
    off, want = run(decode_width_buckets=1)
    assert got == want
    assert on.tier["promotions"] >= 1     # the tier actually cycled
    assert on.tier["demotions"] >= 1
    # the post-promotion decode really ran sliced
    assert on.width["bucket_blocks"] < on.nb
    assert on.last_block_leaks == 0 and on.last_host_block_leaks == 0


@pytest.mark.slow
def test_reconstruction_after_fault_across_growth(gpt2):
    """A device fault AFTER the long row grew its bucket: replay
    re-prefills at whatever rung each wave needs and the resumed
    streams must equal the fault-free serve token for token (greedy
    and sampled rows side by side)."""
    model, params = gpt2
    rng = np.random.default_rng(47)
    reqs = _edge_requests(rng)
    reqs[1].temperature = 0.8
    reqs[1].seed = 321

    def fresh(**kw):
        return ContinuousBatcher(model, params, slots=2, t_max=64,
                                 prompt_buf=10, segment=4, **kw)

    clean = fresh().serve(_clone(reqs))
    cb = fresh()
    res = cb.serve_detailed(
        _clone(reqs),
        chaos=ChaosInjector(fault_at_segment=4, fault_mode="raise"))
    assert cb.stats["faults"] == 1 and cb.stats["reconstructions"] == 1
    assert [r.tokens for r in res] == clean
    assert cb.width["bucket_growths"] >= 1
    assert cb.last_slot_leaks == 0
