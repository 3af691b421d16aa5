"""Chunked + disaggregated prefill (PR 14): `prefill_chunk_tokens`
bounds each admission wave's prefill so one long prompt can never stall
live decode rows for a whole prefill, and `ServeRouter(prefill_replicas
=K)` splits the fleet into a prefill tier and a decode tier with the
finished KV blocks HANDED OVER (export_prefix -> import_prefix, the
PR 13 position-portable CRC-checked bytes) instead of re-prefilled.

The acceptance bar everywhere is token identity: chunked-on equals
chunked-off for greedy AND sampled rows (positions are logical, so the
per-tick sampling key fold_in(key(seed), n_logical + i) cannot see the
chunking), on gpt2 and llama, over int8 weights, under a mesh, across
a mid-chunk reconstruction, and through the tier-split router with a
replica killed mid-stream. Heavy sweeps live behind `slow`.
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
from distributed_compute_pytorch_tpu.serve_router import ServeRouter

_COMMON = dict(slots=2, t_max=64, prompt_buf=24, segment=3)


@pytest.fixture(scope="module")
def gpt2():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return model, params


@pytest.fixture(scope="module")
def llama():
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    return model, params


def _reqs(rng, n, lo=3, hi=22, min_new=3, max_new=8, sampled=()):
    """Mixed-length prompts sized so several exceed the chunk budget;
    `sampled` indices decode at temperature with the index-default
    seed (chunking must be invisible to the sampling keys)."""
    out = []
    for i in range(n):
        ln = int(rng.integers(lo, hi + 1))
        out.append(Request(
            tokens=[int(t) for t in rng.integers(0, 256, size=ln)],
            max_new=int(rng.integers(min_new, max_new + 1)),
            temperature=0.8 if i in sampled else 0.0))
    return out


def _copies(reqs):
    return [dataclasses.replace(r) for r in reqs]


def _parity(model, params, reqs, chunk, **kw):
    """Chunk-off is the reference; chunk-on must match token-for-token
    and actually chunk (long prompts present by construction)."""
    kw = {**_COMMON, **kw}
    off = ContinuousBatcher(model, params, **kw)
    want = off.serve(_copies(reqs))
    on = ContinuousBatcher(model, params, **kw,
                           prefill_chunk_tokens=chunk)
    got = on.serve(_copies(reqs))
    assert got == want
    assert on.prefill["chunked_admissions"] > 0
    assert on.prefill["chunk_waves"] > 0
    assert on.prefill["chunk_tokens"] > 0
    # what bounds the stall of a decoding row: all the rows of a wave
    # share ONE budget of ``chunk`` suffix tokens
    assert on.prefill["chunk_tokens"] \
        <= chunk * on.prefill["chunk_waves"]
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0
    return on


# ------------------------------------------------- chunked-prefill parity


def test_chunked_parity_gpt2_greedy_and_sampled(gpt2):
    # 6 requests, prompts to 18: enough that several prompts span 2-3
    # chunks and both slots cycle, small enough that the module stays
    # inside the tier-1 budget (each batcher pair is a fresh compile)
    model, params = gpt2
    reqs = _reqs(np.random.default_rng(3), 6, hi=18, sampled=(1, 4))
    on = _parity(model, params, reqs, chunk=6)
    # chunk accounting is exact: chunk waves move exactly the prompt
    # tokens the admission waves deferred
    assert dict(on.prefill) == on.stats_snapshot()["prefill"]


@pytest.mark.slow
def test_chunked_parity_llama_int8(llama):
    """The quantized weight path: same chunked/unchunked identity over
    the SAME int8 params. Slow (tier-1 budget, Makefile note): the
    chunk state machine is family/dtype-independent host logic already
    pinned by the gpt2 tests above."""
    from distributed_compute_pytorch_tpu.utils.quantize import (
        quantize_params_int8)
    model, params = llama
    qp = jax.jit(quantize_params_int8)(params)
    reqs = _reqs(np.random.default_rng(5), 6, sampled=(2,))
    _parity(model, qp, reqs, chunk=5)


@pytest.mark.slow
def test_chunked_parity_mesh(llama, devices8):
    """Chunk waves ride the same constrained-scatter admission path the
    mesh uses, so the identity must survive sharding. Slow (tier-1
    budget): the scatter path itself is pinned under a mesh by
    test_serve_mesh; this adds only the chunk-window variant."""
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model, params = llama
    mesh = make_mesh("data=2,tensor=2", devices=devices8[:4])
    sharded = shard_pytree(params, pick_strategy(mesh, model), mesh)
    reqs = _reqs(np.random.default_rng(7), 6)
    _parity(model, sharded, reqs, chunk=6, mesh=mesh, slots=4)


def test_chunk_boundary_prefix_attach(gpt2):
    """Prefix cache x chunking: a chunk-admitted head only enters the
    radix once it is COMPLETE (a partial head would hand attachers
    unwritten blocks), and a follower sharing the prompt then attaches
    to the chunk-built blocks with full token parity."""
    model, params = gpt2
    rng = np.random.default_rng(9)
    head = [int(t) for t in rng.integers(0, 256, size=14)]
    # slots=2: the first wave admits the head + the decoy, so the
    # follower only admits once the chunk-built head is complete and
    # inserted — the attach crosses chunk-boundary-built blocks
    reqs = [Request(tokens=list(head), max_new=4),
            Request(tokens=[int(t) for t in rng.integers(0, 256, size=5)],
                    max_new=5),
            Request(tokens=list(head) + [7], max_new=4)]
    off = ContinuousBatcher(model, params, **_COMMON, prefix_cache=True)
    want = off.serve(_copies(reqs))
    on = ContinuousBatcher(model, params, **_COMMON, prefix_cache=True,
                           prefill_chunk_tokens=6)
    got = on.serve(_copies(reqs))
    assert got == want
    assert on.prefill["chunked_admissions"] > 0
    assert on.stats["prefix_hits"] > 0
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0


def test_reconstruction_mid_chunk(gpt2):
    """A device fault while a long prompt is still extending chunk by
    chunk: reconstruction replays the WHOLE head (the chunk cursor is
    reset, not resumed — the pool the partial chunks lived in is gone)
    and every stream still matches the fault-free unchunked run."""
    model, params = gpt2
    reqs = _reqs(np.random.default_rng(11), 6, lo=16, hi=22,
                 sampled=(3,))
    off = ContinuousBatcher(model, params, **_COMMON)
    want = [r.tokens for r in off.serve_detailed(_copies(reqs))]
    on = ContinuousBatcher(model, params, **_COMMON,
                           prefill_chunk_tokens=6, max_recoveries=1)
    res = on.serve_detailed(
        _copies(reqs),
        chaos=ChaosInjector(fault_at_segment=2, fault_mode="raise"))
    assert all(r.ok for r in res), [r.error for r in res]
    assert [r.tokens for r in res] == want
    assert on.stats["reconstructions"] == 1
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0


def test_moe_refuses_chunking():
    """Expert routing is group-dependent, so a chunked prefill would
    not be token-identical — refused at construction like prefix_cache
    and speculate."""
    from distributed_compute_pytorch_tpu.models.moe import (
        MoETransformerConfig, MoETransformerLM)
    model = MoETransformerLM(dataclasses.replace(
        MoETransformerConfig.tiny(), max_seq_len=128,
        capacity_factor=8.0))
    params, _ = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="prefill_chunk_tokens"):
        ContinuousBatcher(model, params, **_COMMON,
                          prefill_chunk_tokens=4)


def test_prefill_cost_prices_chunks():
    """The router pricing seam: unchunked cost is the raw suffix,
    chunked cost is ceil(suffix/chunk) admission waves of one segment
    each — NOT one tick per prompt token. decode_width_buckets=1 pins
    the full-horizon bucket so the segment units are unweighted (the
    width-priced form is pinned in tests/test_serve_width.py)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    flat = ContinuousBatcher(model, params, **_COMMON,
                             decode_width_buckets=1)
    assert flat.prefill_cost(0) == 0 and flat.prefill_cost(-3) == 0
    assert flat.prefill_cost(100) == 100
    cb = ContinuousBatcher(model, params, **_COMMON,
                           prefill_chunk_tokens=8,
                           decode_width_buckets=1)
    chunk, S = cb._chunk, cb.S
    assert cb.prefill_cost(1) == S
    assert cb.prefill_cost(chunk) == S
    assert cb.prefill_cost(chunk + 1) == 2 * S
    assert cb.prefill_cost(10 * chunk) == 10 * S


# ----------------------------------------------------- the handoff seam


def test_handoff_bit_exact_vs_replay_fallback(gpt2):
    """export_prefix -> import_prefix moves the finished prompt blocks
    between two independent pools and the continuation equals the
    unified single-batcher stream exactly; a corrupted payload is
    DECLINED (counter, no exception) and the same continuation still
    matches via plain replay — the fallback is invisible in tokens."""
    model, params = gpt2
    kw = dict(**_COMMON, prefix_cache=True)
    rng = np.random.default_rng(13)
    prompt = [int(t) for t in rng.integers(0, 256, size=17)]
    want = ContinuousBatcher(model, params, **kw).serve(
        [Request(tokens=list(prompt), max_new=6)])[0]

    src = ContinuousBatcher(model, params, **kw)
    first = src.serve([Request(tokens=list(prompt), max_new=1)])[0]
    payload = src.export_prefix(prompt + first)
    assert payload is not None and payload["n_tokens"] == 16
    assert src.prefill["handoff_exports"] == 1
    assert src.prefill["handoff_bytes"] > 0

    dst = ContinuousBatcher(model, params, **kw)
    assert dst.import_prefix(payload)
    assert dst.prefill["handoff_imports"] == 1
    assert dst.prefix_match_len(prompt) == 16
    cont = dst.serve([Request(tokens=prompt + first, max_new=5)])[0]
    assert first + cont == want
    assert dst.last_block_leaks == 0

    bad = dict(payload, crc=payload["crc"] ^ 1)
    fb = ContinuousBatcher(model, params, **kw)
    assert fb.import_prefix(bad) is False
    assert fb.prefill["handoff_declined"] == 1
    assert fb.prefix_match_len(prompt) == 0      # nothing half-imported
    cont = fb.serve([Request(tokens=prompt + first, max_new=5)])[0]
    assert first + cont == want                  # replay fallback
    assert fb.last_block_leaks == 0


def test_handoff_export_from_host_tier(gpt2):
    """A prefill replica under pool pressure demotes the finished entry
    D2H before the router exports it — the handoff must read the bytes
    straight out of the spill tier, not require device residency."""
    from distributed_compute_pytorch_tpu.kv_pool import TIER_HOST
    model, params = gpt2
    kw = dict(**_COMMON, prefix_cache=True)
    rng = np.random.default_rng(15)
    prompt = [int(t) for t in rng.integers(0, 256, size=17)]
    src = ContinuousBatcher(model, params, slots=1, t_max=32,
                            prompt_buf=24, segment=4, prefix_cache=True,
                            pool_blocks=8, host_cache_blocks=16)
    first = src.serve([Request(tokens=list(prompt), max_new=1)])[0]
    # force the demotion pool pressure would cause
    e = next(e for e in src._radix.entries)
    src._radix.evict_for(src._pool.num_blocks, src._tier_demote)
    assert e.tier == TIER_HOST
    payload = src.export_prefix(prompt + first)
    assert payload is not None and payload["n_tokens"] == 16
    dst = ContinuousBatcher(model, params, **kw)
    assert dst.import_prefix(payload)
    want = ContinuousBatcher(model, params, **kw).serve(
        [Request(tokens=list(prompt), max_new=6)])[0]
    cont = dst.serve([Request(tokens=prompt + first, max_new=5)])[0]
    assert first + cont == want
    assert src.last_host_block_leaks == 0


# -------------------------------------------------- the tier-split router


@pytest.fixture(scope="module")
def fleet(gpt2):
    model, params = gpt2
    return [ContinuousBatcher(model, params, slots=2, t_max=64,
                              prompt_buf=24, segment=3, prefix_cache=True,
                              prefill_chunk_tokens=6, max_recoveries=0)
            for _ in range(3)]


def _reset(fleet):
    for r in fleet:
        r.reset()


def test_router_disagg_parity_with_handoff(gpt2, fleet):
    """1 prefill + 2 decode replicas: every session prefills on the
    prefill tier, hops exactly once, and at least one hop lands as a
    block handoff (no replay) — with every stream token-identical to
    one unified batcher and no migrations charged for planned hops."""
    model, params = gpt2
    _reset(fleet)
    reqs = _reqs(np.random.default_rng(17), 8, sampled=(2, 5))
    ref = fleet[0].serve_detailed(_copies(reqs))
    _reset(fleet)
    router = ServeRouter(fleet, jitter_seed=42, prefill_replicas=1)
    res = router.route(_copies(reqs))
    assert all(r.ok for r in res), [r.error for r in res]
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert router.stats["prefill_hops"] > 0
    assert router.stats["handoffs"] > 0
    assert router.stats["migrations"] == 0      # hops are planned moves
    # every session finished on the decode tier, not the prefill tier
    assert all(r.replica in (1, 2) for r in res)
    for i, rep in enumerate(fleet):
        assert rep.last_slot_leaks == 0, i
        assert rep.last_block_leaks == 0, i


def test_router_disagg_kill_decode_replica_mid_handoff(gpt2, fleet):
    """The drill: a decode replica dies while hopped sessions decode on
    it. Its sessions migrate to the surviving decode replica and every
    stream still equals the unified reference — the handoff is an
    optimisation seam, never a correctness dependency."""
    model, params = gpt2
    _reset(fleet)
    reqs = _reqs(np.random.default_rng(19), 8, min_new=5, sampled=(3,))
    ref = fleet[0].serve_detailed(_copies(reqs))
    _reset(fleet)
    router = ServeRouter(fleet, jitter_seed=42, prefill_replicas=1)
    chaos = {1: ChaosInjector(fault_at_segment=2, fault_mode="raise")}
    res = router.route(_copies(reqs), chaos=chaos)
    assert all(r.ok for r in res), [r.error for r in res]
    assert [r.tokens for r in res] == [r.tokens for r in ref]
    assert router.stats["prefill_hops"] > 0
    assert router.stats["failovers"] >= 1
    assert router.stats["migrations"] >= 1
    for i, rep in enumerate(fleet):
        if i == 1:
            continue                            # the dead replica
        assert rep.last_slot_leaks == 0, i
        assert rep.last_block_leaks == 0, i


def test_router_validates_prefill_replicas(gpt2, fleet):
    with pytest.raises(ValueError, match="prefill_replicas"):
        ServeRouter(fleet, prefill_replicas=3)
    with pytest.raises(ValueError, match="prefill_replicas"):
        ServeRouter(fleet, prefill_replicas=-1)


# ------------------------------------------------------------ slow sweeps


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [3, 5, 8, 16])
def test_chunked_parity_sweep_gpt2(gpt2, chunk):
    model, params = gpt2
    reqs = _reqs(np.random.default_rng(100 + chunk), 10,
                 sampled=(0, 4, 7))
    _parity(model, params, reqs, chunk=chunk)


@pytest.mark.slow
@pytest.mark.parametrize("chunk", [4, 7])
def test_chunked_parity_sweep_llama_prefix(llama, chunk):
    model, params = llama
    reqs = _reqs(np.random.default_rng(200 + chunk), 8, sampled=(1, 6))
    _parity(model, params, reqs, chunk=chunk, prefix_cache=True)
