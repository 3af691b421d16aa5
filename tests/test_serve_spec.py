"""Speculative decoding through the compiled segment (serve.py
``speculate=`` + spec_decode.py): the accept/reject rule is EXACT, so
every drill here is a parity pin — spec-on serving must be
token-identical to spec-off serving (greedy AND sampled, bf16 and int8
weights, off-mesh and mesh-sharded, through faults and auto-disable) no
matter how bad the proposer is. Throughput is the benchmark's business
(``perfbench/``: no cell speculates yet); correctness lives here.

Kept CPU-cheap for tier-1 (ROADMAP budget note): tiny models, short
streams, the k/segment sweep rides behind ``slow``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.infer import generate
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.models.moe import (
    MoETransformerConfig, MoETransformerLM)
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
from distributed_compute_pytorch_tpu.spec_decode import (
    DraftModelProposer, NGramProposer, SpecConfig)


def _models():
    return [
        ("gpt2", GPT2(dataclasses.replace(GPT2Config.tiny(),
                                          max_seq_len=128))),
        ("llama", LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                              max_seq_len=128))),
    ]


def _requests(rng, n, min_new=4, max_new=9):
    reqs = []
    for _ in range(n):
        ln = int(rng.integers(2, 10))
        reqs.append(Request(
            tokens=[int(t) for t in rng.integers(0, 256, size=ln)],
            max_new=int(rng.integers(min_new, max_new + 1))))
    return reqs


def _repetitive_requests(rng, n, max_new=8):
    """Period-3 token loops: the n-gram proposer's home turf, so the
    accept path (not just reject) is genuinely exercised."""
    reqs = []
    for _ in range(n):
        period = [int(t) for t in rng.integers(0, 256, size=3)]
        reqs.append(Request(tokens=period * 3, max_new=max_new))
    return reqs


def _clone(reqs):
    return [dataclasses.replace(r) for r in reqs]


def _standalone(model, params, req):
    solo = generate(model, params, jnp.asarray([req.tokens], jnp.int32),
                    req.max_new)
    return [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]


def _assert_clean(cb):
    assert cb.last_slot_leaks == 0 and cb.last_block_leaks == 0


class _WrongProposer:
    """Deterministically proposes SOMETHING, never consults the model:
    with 256-token random streams its drafts essentially always miss,
    forcing the rejection-resample path at every verify."""

    def propose(self, context, k):
        return [(context[-1] * 31 + 7 * i + 13) % 256 for i in range(k)]


# ------------------------------------------------------- greedy parity


@pytest.mark.parametrize("name,model", _models())
def test_spec_greedy_parity_both_families(name, model):
    """The flagship pin: spec-on == spec-off == standalone generate,
    token for token, on mixed random + repetitive streams (both the
    accept and reject paths run), with real speculation happening and
    zero leaks."""
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    reqs = _requests(rng, 4) + _repetitive_requests(rng, 3)
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=12, segment=3)
    out_off = off.serve(_clone(reqs))
    on = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=12, segment=3,
                           speculate=SpecConfig(k=3))
    out_on = on.serve(_clone(reqs))
    assert out_on == out_off, name
    # one standalone anchor per family (spec-off == standalone across
    # whole streams is test_serve.py's pin; re-checking every request
    # here would just re-pay a generate compile per prompt shape)
    assert out_off[0] == _standalone(model, params, reqs[0]), name
    s = on.spec
    assert s["verify_segments"] > 0 and s["proposed"] > 0
    assert s["accepted"] > 0              # repetitive rows must accept
    assert s["emitted_tokens"] == sum(len(o) for o in out_on)
    # every verify position is either emitted or wasted, exactly once
    # (wasted covers rejected drafts AND accepted-but-beyond-budget);
    # each row-verify scores k+1 positions off k proposed drafts, and a
    # verify SEGMENT carries every live row's window at once
    assert 4 * s["proposed"] \
        == 3 * (s["emitted_tokens"] + s["wasted_verify_tokens"])
    assert s["proposed"] >= 3 * s["verify_segments"]
    assert 0 < s["accepted"] <= s["proposed"]
    assert "spec" in on.stats_snapshot()
    _assert_clean(on)


def test_spec_repetitive_stream_emits_more_than_a_token_a_window():
    """The mechanism speculation pays with, by count: a verify window
    costs one weight stream like a plain tick, so on a stream the
    n-gram proposer can draft (looped periods, one prompt in four
    random so rejects run too) each row-window must emit MORE than one
    token, and a healthy acceptance rate never trips auto-disable."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    reqs = _repetitive_requests(rng, 9, max_new=16) + _requests(rng, 3)
    k = 4
    kw = dict(slots=4, t_max=64, prompt_buf=16, segment=4)
    off = ContinuousBatcher(model, params, **kw)
    on = ContinuousBatcher(model, params, speculate=SpecConfig(k=k), **kw)
    assert on.serve(_clone(reqs)) == off.serve(_clone(reqs))
    s = on.spec
    row_windows = s["proposed"] / k          # k drafts a row-window
    assert row_windows > 0
    assert s["emitted_tokens"] / row_windows > 1.0, s
    assert s["acceptance_rate"] > 0
    assert s["autodisabled"] == 0
    _assert_clean(on)
    _assert_clean(off)


def test_spec_int_coercion_and_int8_weight_parity():
    """``speculate=2`` (the CLI's int form) coerces to SpecConfig(k=2);
    the int8 weight-quantized path stays token-identical spec-on vs
    spec-off over the SAME quantized params."""
    from distributed_compute_pytorch_tpu.utils.quantize import (
        quantize_params_int8)
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    qp = jax.jit(quantize_params_int8)(params)
    rng = np.random.default_rng(31)
    reqs = _requests(rng, 3) + _repetitive_requests(rng, 2)
    off = ContinuousBatcher(model, qp, slots=2, t_max=64, prompt_buf=12,
                            segment=3)
    out_off = off.serve(_clone(reqs))
    on = ContinuousBatcher(model, qp, slots=2, t_max=64, prompt_buf=12,
                           segment=3, speculate=2)
    assert on._spec.k == 2
    out_on = on.serve(_clone(reqs))
    assert out_on == out_off
    _assert_clean(on)


def test_spec_mesh_parity(devices8):
    """Speculation under a mesh-sharded slot pool (RoPE/GQA): the
    verify program shards like the segment program, and the stream
    stays identical to the same-mesh spec-off serve."""
    from distributed_compute_pytorch_tpu.core.mesh import make_mesh
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2", devices=devices8)
    sharded = shard_pytree(params, pick_strategy(mesh, model), mesh)
    rng = np.random.default_rng(5)
    reqs = _requests(rng, 3, min_new=3, max_new=6) \
        + _repetitive_requests(rng, 2, max_new=6)
    off = ContinuousBatcher(model, sharded, slots=2, t_max=64,
                            prompt_buf=12, segment=3, mesh=mesh)
    out_off = off.serve(_clone(reqs))
    on = ContinuousBatcher(model, sharded, slots=2, t_max=64,
                           prompt_buf=12, segment=3, mesh=mesh,
                           speculate=SpecConfig(k=3))
    out_on = on.serve(_clone(reqs))
    assert out_on == out_off
    assert on.spec["verify_segments"] > 0
    _assert_clean(on)


# -------------------------------------------------- sampled determinism


def _sampling_requests(rng, n):
    reqs = _requests(rng, n, min_new=5, max_new=8)
    for i, r in enumerate(reqs):
        r.temperature = 0.9
        r.top_k = [None, 20, None, 50][i % 4]
        r.top_p = [None, None, 0.9, 0.8][i % 4]
        r.seed = 100 + i
    return reqs


def test_spec_sampled_bit_identical():
    """Sampled rows: the verify scores position i with the SAME
    fold-in key (seed, tokens-generated) the plain tick would use, so
    spec-on streams are bit-identical to spec-off — greedy rows riding
    alongside stay pinned to standalone too."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(47)
    sampled = _sampling_requests(rng, 4)
    greedy = _requests(rng, 2, min_new=5, max_new=7)
    mixed = [r for pair in zip(sampled[:2], greedy) for r in pair] \
        + sampled[2:]
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=12, segment=3)
    out_off = off.serve(_clone(mixed))
    on = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=12, segment=3,
                           speculate=SpecConfig(k=4))
    out_on = on.serve(_clone(mixed))
    assert out_on == out_off
    # determinism across sessions on the same warm programs
    on.reset()
    assert on.serve(_clone(mixed)) == out_on
    _assert_clean(on)


def test_spec_forced_rejection_resamples_exactly():
    """A proposer that is essentially always wrong forces the rejection
    path at every verify: the emitted token at the first mismatch IS
    the deterministic resample at that position's key, so sampled and
    greedy streams alike must still equal spec-off exactly — proposer
    quality can only cost throughput, never tokens."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(53)
    reqs = _sampling_requests(rng, 3) + _requests(rng, 2)
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=12, segment=3)
    out_off = off.serve(_clone(reqs))
    spec = SpecConfig(k=3, proposer=_WrongProposer(),
                      autodisable_window=10 ** 9)   # keep speculating
    on = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=12, segment=3, speculate=spec)
    out_on = on.serve(_clone(reqs))
    assert out_on == out_off
    s = on.spec
    assert s["wasted_verify_tokens"] > 0
    assert s["acceptance_rate"] < 0.5     # the drafts really missed
    _assert_clean(on)


def test_spec_autodisable_flips_to_plain_and_keeps_parity():
    """Sustained rejection trips the auto-disable guard mid-stream: the
    batcher finishes on plain segment decode, the flip is counted and
    sticky until reset(), and the stream crossing the transition is
    still token-identical to spec-off."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(59)
    reqs = _requests(rng, 6, min_new=6, max_new=10)
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=12, segment=3)
    out_off = off.serve(_clone(reqs))
    spec = SpecConfig(k=3, proposer=_WrongProposer(),
                      autodisable_window=6, autodisable_below=0.34)
    on = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=12, segment=3, speculate=spec)
    from distributed_compute_pytorch_tpu.obs import flight
    rec = flight.FlightRecorder(capacity=256)
    prev = flight.configure_flight(rec)
    try:
        out_on = on.serve(_clone(reqs))
    finally:
        flight.configure_flight(prev)
    assert out_on == out_off
    assert on.spec["autodisabled"] >= 1
    assert not on._spec_on                # sticky for the session...
    # the flip leaves a flight-recorder instant naming the window rate
    evs = [e for e in rec.events() if e.get("kind") == "spec_autodisable"]
    assert evs and evs[0]["rate"] < 0.34
    on.reset()
    assert on._spec_on                    # ...and re-armed by reset()
    _assert_clean(on)


def test_spec_gauges_ride_the_telemetry_registry():
    """``spec`` is a MetricDict view: every counter mirrors into
    ``serve.spec.*`` registry gauges, which is what the heartbeat and
    metrics-JSONL exporters snapshot — no separate spec plumbing."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=1, t_max=64,
                           prompt_buf=12, segment=3, speculate=2)
    cb.serve([Request([1, 2, 3] * 3, 5)])
    snap = cb.obs.snapshot()
    for key in ("proposed", "accepted", "acceptance_rate",
                "wasted_verify_tokens", "verify_segments",
                "emitted_tokens", "autodisabled"):
        assert snap["serve.spec." + key] == cb.spec[key], key
    assert snap["serve.spec.emitted_tokens"] == 5


# ------------------------------------------------- faults + validation


def test_spec_reconstruction_after_fault_parity():
    """A device fault mid-stream with speculation live: reconstruction
    re-prefills from host state (which already absorbed every verify's
    emitted tokens) and re-syncs the spec mirrors, so resumed streams
    equal the clean spec-off serve — greedy and sampled."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(71)
    reqs = _requests(rng, 4, min_new=6, max_new=10) \
        + _repetitive_requests(rng, 2, max_new=8)
    reqs[1].temperature = 0.9
    reqs[1].seed = 501
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=12, segment=3)
    clean = off.serve(_clone(reqs))
    on = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=12, segment=3,
                           speculate=SpecConfig(k=3))
    res = on.serve_detailed(
        _clone(reqs),
        chaos=ChaosInjector(fault_at_segment=2, fault_mode="raise"))
    assert on.stats["reconstructions"] == 1
    assert all(r.ok for r in res), [r.status for r in res]
    assert [r.tokens for r in res] == clean
    _assert_clean(on)


def test_spec_rejects_moe_and_validates_config():
    """MoE routing is group-dependent (a verify window would route k+1
    positions as one group where plain decode routes tick-by-tick), so
    speculation refuses MoE at construction — same precedent as
    prefix_cache; bad SpecConfigs refuse too."""
    cfg = dataclasses.replace(MoETransformerConfig.tiny(), max_seq_len=128)
    model = MoETransformerLM(cfg)
    params, _ = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="speculate"):
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=10,
                          speculate=2)
    with pytest.raises(ValueError, match="k must be"):
        SpecConfig(k=0)
    with pytest.raises(ValueError, match="ngram_min"):
        SpecConfig(ngram_max=2, ngram_min=3)
    with pytest.raises(ValueError, match="draft_model"):
        ContinuousBatcher(
            GPT2(GPT2Config.tiny()),
            GPT2(GPT2Config.tiny()).init(jax.random.key(0))[0],
            slots=1, t_max=32, prompt_buf=8,
            speculate=SpecConfig(proposer="draft"))


# --------------------------------------------------- proposers (host unit)


def test_ngram_proposer_suffix_lookup():
    p = NGramProposer(ngram_max=3, ngram_min=1)
    # suffix [7, 8] recurred earlier; its continuation is proposed
    assert p.propose([7, 8, 9, 1, 7, 8], 2) == [9, 1]
    # short continuation pads by repeating the tail
    assert p.propose([5, 6, 5], 3) == [6, 5, 5]
    # nothing recurs: repeat the last token
    assert p.propose([1, 2, 3], 2) == [3, 3]
    assert p.propose([], 2) == [0, 0]


def test_draft_model_proposer_drafts_k_tokens():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=64))
    params, _ = model.init(jax.random.key(0))
    p = DraftModelProposer(model, params, window=8)
    out = p.propose([1, 2, 3], 3)
    assert len(out) == 3 and all(isinstance(t, int) for t in out)
    # deterministic (greedy draft) and window-stable
    assert p.propose([1, 2, 3], 3) == out


def test_equal_batchers_share_compiled_programs():
    """The compiled-program cache: a spec-on/off pair (and a router's N
    replicas) over one model config + geometry borrow the SAME bound
    jit objects, so the second batcher pays zero trace+compile; a
    different segment length is a different program family."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    a = ContinuousBatcher(model, params, slots=2, t_max=64,
                          prompt_buf=12, segment=3)
    b = ContinuousBatcher(model, params, slots=2, t_max=64,
                          prompt_buf=12, segment=3, speculate=2)
    assert b._segment_c is a._segment_c
    assert b._admit_c is a._admit_c
    assert b._verify_c is a._verify_c
    # an EQUAL (not identical) config shares too — cross-session reuse
    m2 = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    c = ContinuousBatcher(m2, m2.init(jax.random.key(1))[0], slots=2,
                          t_max=64, prompt_buf=12, segment=3)
    assert c._segment_c is a._segment_c
    d = ContinuousBatcher(model, params, slots=2, t_max=64,
                          prompt_buf=12, segment=4)
    assert d._segment_c is not a._segment_c


def test_spec_load_estimate_accounts_for_verify_width():
    """The router's cost probe: a live-spec batcher prices max_new in
    verify windows (cold rate=0 -> max_new verifies of k+1 ticks);
    spec-off and auto-disabled batchers price segment-rounded ticks.
    decode_width_buckets=1 pins the full-horizon bucket so the tick
    units are unweighted (the width-priced form is pinned in
    tests/test_serve_width.py)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    plain = ContinuousBatcher(model, params, slots=1, t_max=64,
                              prompt_buf=8, segment=4,
                              decode_width_buckets=1)
    assert plain.load_estimate(6) == 8            # ceil(6/4)*4
    spec = ContinuousBatcher(model, params, slots=1, t_max=64,
                             prompt_buf=8, segment=4,
                             speculate=SpecConfig(k=3),
                             decode_width_buckets=1)
    assert spec.load_estimate(6) == 6 * 4         # rate 0: 6 verifies of 4
    spec.spec["acceptance_rate"] = 1.0
    assert spec.load_estimate(6) == 2 * 4         # ceil(6/4) verifies
    spec._spec_on = False                         # auto-disabled
    assert spec.load_estimate(6) == 8


# ------------------------------------------------------------ slow sweep


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 2, 5])
@pytest.mark.parametrize("segment", [2, 4])
def test_spec_parity_sweep_k_and_segment(k, segment):
    """Window width and plain-segment size are scheduling, not
    semantics: every (k, segment) pair serves the same stream."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(97)
    reqs = _requests(rng, 5) + _repetitive_requests(rng, 3)
    reqs[2].temperature = 0.8
    reqs[2].seed = 7
    off = ContinuousBatcher(model, params, slots=2, t_max=128,
                            prompt_buf=12, segment=segment)
    out_off = off.serve(_clone(reqs))
    on = ContinuousBatcher(model, params, slots=2, t_max=128,
                           prompt_buf=12, segment=segment,
                           speculate=SpecConfig(k=k))
    assert on.serve(_clone(reqs)) == out_off
    _assert_clean(on)
