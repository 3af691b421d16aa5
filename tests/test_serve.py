"""Continuous batching (serve.py): staggered admissions through the
fixed slot pool must reproduce each prompt's STANDALONE generation
exactly — the fixed-window admission, per-row positions and slot masks,
and per-family position handling (logical embed / absolute-per-row-slot
rope) all have to line up for this to hold token-for-token — and the
per-row horizon must let streams outlive what the old lockstep design
could serve."""

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
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, HorizonError, Request)


def _models():
    # max_seq_len lifted so the serving horizon fits logical positions
    return [
        ("gpt2", GPT2(dataclasses.replace(GPT2Config.tiny(),
                                          max_seq_len=128))),
        ("llama", LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                              max_seq_len=128))),
        ("moe", MoETransformerLM(dataclasses.replace(
            MoETransformerConfig.tiny(), max_seq_len=128,
            capacity_factor=8.0))),
    ]


def _requests(rng, n, vocab=256, min_len=2, max_len=10, min_new=3,
              max_new=9):
    reqs = []
    for _ in range(n):
        ln = int(rng.integers(min_len, max_len + 1))
        reqs.append(Request(
            tokens=[int(t) for t in rng.integers(0, vocab, size=ln)],
            max_new=int(rng.integers(min_new, max_new + 1))))
    return reqs


@pytest.mark.parametrize("name,model", _models())
def test_staggered_admissions_match_standalone(name, model):
    """The gold serving test: 7 mixed-length requests through 2 slots
    with a small segment — admissions land staggered across segments
    (each rewinding its row's own position), and each request's served
    tokens must equal its standalone greedy generate()."""
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(3)
    reqs = _requests(rng, 7)
    cb = ContinuousBatcher(model, params, slots=2, t_max=128,
                           prompt_buf=10, segment=3)
    outs = cb.serve(reqs)
    assert len(outs) == len(reqs)
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        assert out == want, (name, i, out, want)


def test_eos_frees_slot_early():
    """A row that samples eos stops there (output trimmed at eos) and
    its slot takes the next request; non-eos requests are unaffected."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(5)
    reqs = _requests(rng, 5, min_new=6, max_new=6)
    # pick an eos that actually occurs early in request 0's standalone run
    solo0 = generate(model, params, jnp.asarray([reqs[0].tokens], jnp.int32),
                     6)
    eos = int(np.asarray(solo0)[0, len(reqs[0].tokens) + 1])

    cb = ContinuousBatcher(model, params, slots=2, t_max=128,
                           prompt_buf=10, segment=4, eos_id=eos)
    outs = cb.serve(reqs)
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        if eos in want:
            want = want[:want.index(eos) + 1]
        assert out == want, (i, out, want)
        assert len(out) <= req.max_new


def test_single_slot_sequential():
    """slots=1 degenerates to sequential serving and still matches."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(7)
    reqs = _requests(rng, 3, min_new=4, max_new=5)
    cb = ContinuousBatcher(model, params, slots=1, t_max=128,
                           prompt_buf=10, segment=5)
    outs = cb.serve(reqs)
    for req, out in zip(reqs, outs):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        assert out == [int(t)
                       for t in np.asarray(solo)[0, len(req.tokens):]]


def test_validation_and_horizon():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=1, t_max=32, prompt_buf=8,
                           segment=4)
    with pytest.raises(ValueError, match="prompt_buf"):
        cb.serve([Request(tokens=list(range(9)), max_new=2)])
    with pytest.raises(ValueError, match="empty"):
        cb.serve([Request(tokens=[], max_new=2)])
    with pytest.raises(ValueError, match="prompt_buf"):
        ContinuousBatcher(model, params, slots=1, t_max=8, prompt_buf=16)
    # the per-row horizon is PER REQUEST: a budget whose segment-rounded
    # need (ceil(max_new/S)*S) can never fit t_max - prompt_buf is
    # rejected with the horizon error — but only AFTER everything
    # admissible completed, and the error carries those outputs
    cb2 = ContinuousBatcher(model, params, slots=1, t_max=32, prompt_buf=8,
                            segment=4)
    fits = Request(tokens=[1, 2, 3], max_new=4)
    solo = generate(model, params, jnp.asarray([fits.tokens], jnp.int32), 4)
    want = [int(t) for t in np.asarray(solo)[0, len(fits.tokens):]]
    with pytest.raises(HorizonError, match="horizon") as ei:
        cb2.serve([Request(tokens=[1, 2, 3], max_new=32),   # need 32 > 24
                   Request(list(fits.tokens), fits.max_new)])
    assert ei.value.outputs == [[], want]


def test_long_stream_outlives_lockstep_horizon():
    """The tentpole regression: five 16-token requests through one slot
    at t_max=32 need 80 total decode ticks — far past the old design's
    shared t_max horizon (which raised RuntimeError here). Per-row
    positions recycle the row in place, so the stream completes in one
    session AND stays token-identical to standalone generation."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=1, t_max=32, prompt_buf=8,
                           segment=4)
    reqs = [Request(tokens=[1 + i, 2, 3], max_new=16) for i in range(5)]
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    assert cb.ticks >= 5 * 16 > cb.t_max   # ticks exceeded the old horizon
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        assert out == want, (i, out, want)


# the MoE long-stream case is marked slow (tier-1 budget): row
# recycling is family-independent host logic pinned by the gpt2/llama
# cases here, and MoE serving exactness keeps its own tier-1 coverage
# (test_staggered_admissions_match_standalone[moe],
# test_moe_no_drop_contract_exact_parity); `make test` runs it
@pytest.mark.parametrize("name,model", [
    pytest.param(*m, marks=pytest.mark.slow) if m[0] == "moe" else m
    for m in _models()])
def test_long_stream_all_families(name, model):
    """Mixed-length stream needing more total ticks than t_max, through
    2 slots — row recycling must stay exact for every family (learned
    positions, per-row-slot RoPE, MoE routing)."""
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(17)
    reqs = _requests(rng, 9, min_new=5, max_new=10)
    cb = ContinuousBatcher(model, params, slots=2, t_max=32,
                           prompt_buf=10, segment=3)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    assert cb.ticks * 1 > cb.t_max - cb.Tb   # outlived a lockstep session
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        assert out == want, (name, i, out, want)


def test_odd_t_max_rounds_to_window_and_matches():
    """ADVICE r5, at block granularity: an odd t_max (the longest-prompt
    parity leak from cli_serve's default sizing) must be rounded up to
    whole pool blocks — whose size is itself a Pallas cache-window
    multiple, so serving never silently falls off the window-write fast
    path — and parity must hold at the rounded shape."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=37, prompt_buf=9,
                           segment=3)
    assert cb.t_max == 40 and cb.t_max % cb.bt == 0 and cb.bt % 8 == 0
    assert cb.nb == cb.t_max // cb.bt
    # the pool's block axis holds every row's worst-case table + trash
    assert all(c["kv"].shape[1] >= cb.B * cb.nb + 1 for c in cb._caches)
    assert all(c["kv"].shape[3] == cb.bt for c in cb._caches)
    rng = np.random.default_rng(23)
    reqs = _requests(rng, 5, max_len=9)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        assert out == want, (i, out, want)


def test_eos_early_exit_reuses_slot_under_per_row_positions():
    """A row that hits eos frees mid-stream and its slot is immediately
    re-admitted AT THE SAME WINDOW (per-row positions rewind the row);
    the tight t_max forces several recycles of both slots, and every
    request must still match its standalone run."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(29)
    reqs = _requests(rng, 6, min_new=6, max_new=6)
    solo0 = generate(model, params,
                     jnp.asarray([reqs[0].tokens], jnp.int32), 6)
    eos = int(np.asarray(solo0)[0, len(reqs[0].tokens) + 1])
    # t_max 24: need = ceil(6/3)*3 = 6 <= 24 - 10; six requests need ~36
    # total ticks > t_max, so slots must recycle to finish
    cb = ContinuousBatcher(model, params, slots=2, t_max=24,
                           prompt_buf=10, segment=3, eos_id=eos)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        if eos in want:
            want = want[:want.index(eos) + 1]
        assert out == want, (i, out, want)
        assert len(out) <= req.max_new


def test_int8_weight_quantized_parity():
    """The int8 serving path (--quantize int8): served greedy outputs
    equal standalone generate over the SAME quantized params, and the
    bf16 cache dtype still rounds t_max to the 8-slot window."""
    from distributed_compute_pytorch_tpu.utils.quantize import (
        quantize_params_int8)
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    qp = jax.jit(quantize_params_int8)(params)
    rng = np.random.default_rng(31)
    reqs = _requests(rng, 5)
    cb = ContinuousBatcher(model, qp, slots=2, t_max=64, prompt_buf=10,
                           segment=3)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, qp,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        assert out == want, (i, out, want)


def test_reset_reuses_compiled_programs():
    """reset() rewinds a batcher for a fresh session on the same jitted
    pieces — outputs match a brand-new batcher's."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(13)
    reqs = _requests(rng, 4)
    cb = ContinuousBatcher(model, params, slots=2, t_max=128,
                           prompt_buf=10, segment=4)
    first = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    cb.reset()
    again = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    assert first == again


def test_cli_serve_end_to_end(tmp_path, capsys, devices8):
    """dcp-train writes a checkpoint; dcp-serve runs a mixed-length
    request file through the continuous batcher — each output line must
    equal what dcp-generate produces for that prompt alone."""
    import json

    from distributed_compute_pytorch_tpu.cli_generate import main as gen_main
    from distributed_compute_pytorch_tpu.cli_serve import main as serve_main
    from distributed_compute_pytorch_tpu.core.config import Config
    from distributed_compute_pytorch_tpu.data.datasets import synthetic_lm
    from distributed_compute_pytorch_tpu.train.trainer import Trainer

    ck = str(tmp_path / "ck.npz")
    data = synthetic_lm(64, seq_len=16, vocab=256, seed=9)
    cfg = Config(batch_size=32, lr=1e-3, epochs=1, mesh="data=8",
                 model="gpt2", model_preset="tiny",
                 dataset="synthetic-lm", optimizer="adamw", ckpt_path=ck)
    Trainer(cfg, train_data=data, eval_data=data).fit()

    reqfile = tmp_path / "reqs.txt"
    reqfile.write_text("5, 9, 12\n"
                       '{"tokens": [7], "max_new": 3}\n'
                       "1 2 3 4 5\n")
    capsys.readouterr()          # drain the trainer's log lines
    rc = serve_main(["--ckpt_path", ck, "--model", "gpt2",
                     "--model_preset", "tiny", "--max_seq_len", "16",
                     "--requests", str(reqfile), "--slots", "2",
                     "--segment", "3", "--max_new_tokens", "5"])
    assert rc == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [ln["prompt"] for ln in lines] == [[5, 9, 12], [7],
                                              [1, 2, 3, 4, 5]]
    assert len(lines[0]["new"]) == 5 and len(lines[1]["new"]) == 3

    # each request == its standalone dcp-generate output
    for ln in lines:
        gen_main(["--ckpt_path", ck, "--model", "gpt2",
                  "--model_preset", "tiny", "--max_seq_len", "16",
                  "--prompt", ",".join(map(str, ln["prompt"])),
                  "--max_new_tokens", str(len(ln["new"]))])
        solo = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert solo["new"] == ln["new"], (ln["prompt"], solo, ln)

    # malformed request files fail loudly
    bad = tmp_path / "bad.txt"
    bad.write_text("not tokens\n")
    with pytest.raises(SystemExit, match="token ids"):
        serve_main(["--ckpt_path", ck, "--model", "gpt2",
                    "--model_preset", "tiny", "--max_seq_len", "16",
                    "--requests", str(bad)])


def test_segment_size_invariance():
    """The segment knob is scheduling, not semantics: outputs are
    identical across segment sizes."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    reqs = _requests(rng, 5)
    outs = []
    for seg in (2, 5, 8):
        cb = ContinuousBatcher(model, params, slots=2, t_max=128,
                               prompt_buf=10, segment=seg)
        outs.append(cb.serve([Request(list(r.tokens), r.max_new)
                              for r in reqs]))
    assert outs[0] == outs[1] == outs[2]


# ------------------------------------------ overlap + batched admission


def test_transport_counters_overlap_and_batched_admission():
    """The scheduler's transport contract, by counter: one fetch per
    segment, every fetch with live rows behind it issued AFTER the next
    segment's dispatch, and fewer prefill dispatches than requests (a
    wave stacks the rows of one window rung into one dispatch)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(41)
    # one long request keeps the pool live across every wave boundary
    reqs = ([Request(tokens=[1, 2, 3], max_new=24)]
            + _requests(rng, 6, min_new=4, max_new=4))
    # prompt_buf 32: heads of up to 8 tokens share the ladder's lowest
    # rung, two rows a dispatch
    cb = ContinuousBatcher(model, params, slots=3, t_max=64, prompt_buf=32,
                           segment=4)
    outs = cb.serve(reqs)
    assert all(o for o in outs)
    s = cb.stats
    assert s["fetches"] == s["segments"]
    assert s["fetches_overlapped"] == s["fetches"] - 1
    assert s["prefill_rows"] == len(reqs)
    assert s["prefill_calls"] < len(reqs)     # dispatches, not per-request
    # every row-tick attributed exactly once (the waste breakdown)
    w = cb.waste
    total = cb.ticks * cb.B
    assert (w["planned_ticks"] + w["parked_admission_lag"]
            + w["parked_drain"]) == total
    assert w["planned_ticks"] >= sum(len(o) for o in outs)


@pytest.mark.parametrize("read", ["kernel", "gather"])
def test_fewer_requests_than_slots_parks_rows_and_serves_the_same(
        read, monkeypatch):
    """Two requests in four slots: two rows ride every segment under the
    all-trash table. ``stats["decode_rows_parked"]`` counts those
    slot-ticks (the waste breakdown's two parked counts together), and
    the tokens are each prompt's standalone generation whichever engine
    reads the pool: the gather, or the block-table kernel (interpreted
    here), which attends nothing for a parked row."""
    from distributed_compute_pytorch_tpu import serve
    from distributed_compute_pytorch_tpu.ops import attention as A
    from distributed_compute_pytorch_tpu.ops.pallas import decode_attention
    model = LlamaLM(dataclasses.replace(
        LlamaConfig.tiny(), d_model=256, num_heads=2, num_kv_heads=1,
        max_seq_len=128))
    assert model.config.head_dim == 128     # heads the kernel takes
    params, _ = model.init(jax.random.key(0))
    # engines of one shape family share their programs: this one's (the
    # interpreted kernel inside) stay its own
    monkeypatch.setattr(serve, "_PROGRAM_CACHE", {})
    real = decode_attention.paged_decode_attention_pallas
    called = []

    def interpreted(*a, **kw):
        called.append(1)
        return real(*a, **kw, interpret=True)
    monkeypatch.setattr(decode_attention, "paged_decode_attention_pallas",
                        interpreted)
    monkeypatch.setattr(A, "paged_read_path", lambda *a, **kw: read)
    reqs = [Request(tokens=[3, 5, 7, 11, 13, 17, 19, 23, 29, 31], max_new=9),
            Request(tokens=[2, 4], max_new=5)]
    cb = ContinuousBatcher(model, params, slots=4, t_max=64, prompt_buf=16,
                           segment=4)
    outs = cb.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    assert bool(called) == (read == "kernel")
    for r, out in zip(reqs, outs):
        alone = generate(model, params, jnp.asarray([r.tokens], jnp.int32),
                         max_new_tokens=r.max_new)
        assert out == [int(t) for t in alone[0, len(r.tokens):]]
    snap = cb.stats_snapshot()
    parked = snap["stats"]["decode_rows_parked"]
    assert parked == (snap["waste"]["parked_admission_lag"]
                      + snap["waste"]["parked_drain"])
    assert parked >= 2 * cb.ticks           # two slots never held a request
    assert parked + snap["waste"]["planned_ticks"] == cb.ticks * cb.B


def test_reset_clears_counters():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=8,
                           segment=4)
    cb.serve([Request([1, 2, 3], 4)])
    assert cb.stats["segments"] > 0
    cb.reset()
    assert cb.stats["segments"] == cb.stats["fetches"] == 0
    assert cb.waste["planned_ticks"] == 0


# ------------------------------------------------------ admission policy


def test_skip_fit_policy_matches_fifo_and_carries_outputs():
    """skip_fit: never-fitting requests are skipped in place (no
    up-front gate) and reported through the same HorizonError; the
    feasible stream is served identically to FIFO — today every row
    offers the same horizon, so the policies only differ in HOW the
    infeasible request is handled (the class docstring's contract)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(43)
    good = _requests(rng, 4, min_new=3, max_new=5)
    # the infeasible request arrives FIRST: under skip_fit it must not
    # block the queue behind it
    reqs = [Request(tokens=[1, 2], max_new=64)] + good

    fifo = ContinuousBatcher(model, params, slots=2, t_max=32,
                             prompt_buf=8, segment=4)
    with pytest.raises(HorizonError) as e_fifo:
        fifo.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    skip = ContinuousBatcher(model, params, slots=2, t_max=32,
                             prompt_buf=8, segment=4,
                             admit_policy="skip_fit")
    with pytest.raises(HorizonError) as e_skip:
        skip.serve([Request(list(r.tokens), r.max_new) for r in reqs])
    assert e_fifo.value.outputs == e_skip.value.outputs
    assert e_skip.value.outputs[0] == []          # the infeasible one
    assert all(e_skip.value.outputs[1:])

    with pytest.raises(ValueError, match="admit_policy"):
        ContinuousBatcher(model, params, slots=2, t_max=32, prompt_buf=8,
                          admit_policy="lifo")


# --------------------------------------------------- per-request sampling


def _sampling_requests(rng, n):
    reqs = _requests(rng, n, min_new=6, max_new=10)
    for i, r in enumerate(reqs):
        r.temperature = 0.9
        r.top_k = [None, 20, None, 50][i % 4]
        r.top_p = [None, None, 0.9, 0.8][i % 4]
        r.seed = 100 + i
    return reqs


def _clone(reqs):
    return [Request(list(r.tokens), r.max_new, temperature=r.temperature,
                    top_k=r.top_k, top_p=r.top_p, seed=r.seed)
            for r in reqs]


def test_sampling_deterministic_and_seed_sensitive():
    """Same seeds => identical served tokens across sessions; changing
    the seeds changes the stream (tiny models: collectively, not
    necessarily per request)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(47)
    reqs = _sampling_requests(rng, 6)
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=10,
                           segment=3)
    first = cb.serve(_clone(reqs))
    cb.reset()
    again = cb.serve(_clone(reqs))
    assert first == again
    reseeded = _clone(reqs)
    for i, r in enumerate(reseeded):
        r.seed = 900 + i
    cb.reset()
    other = cb.serve(reseeded)
    assert other != first


def test_sampling_invariant_to_scheduling():
    """A request's sampled stream is keyed on (seed, tokens-so-far), so
    it must not depend on slots/segment scheduling — the sampling
    analogue of segment-size invariance."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(53)
    reqs = _sampling_requests(rng, 5)
    outs = []
    for slots, seg in ((1, 4), (2, 3), (4, 5)):
        cb = ContinuousBatcher(model, params, slots=slots, t_max=64,
                               prompt_buf=10, segment=seg)
        outs.append(cb.serve(_clone(reqs)))
    assert outs[0] == outs[1] == outs[2]


def test_greedy_rows_keep_parity_next_to_sampling_rows():
    """A greedy request served in the same segment as sampling requests
    still equals its standalone greedy generate token for token."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(59)
    greedy = _requests(rng, 3, min_new=5, max_new=8)
    sampled = _sampling_requests(rng, 3)
    mixed = [r for pair in zip(greedy, sampled) for r in pair]
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=10,
                           segment=3)
    outs = cb.serve(_clone(mixed))
    for req, out in zip(mixed, outs):
        if req.temperature > 0:
            continue
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        assert out == [int(t)
                       for t in np.asarray(solo)[0, len(req.tokens):]]


def test_sampling_validation():
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=1, t_max=32, prompt_buf=8,
                           segment=4)
    with pytest.raises(ValueError, match="temperature"):
        cb.serve([Request([1, 2], 2, temperature=-0.5)])
    with pytest.raises(ValueError, match="top_k/top_p"):
        cb.serve([Request([1, 2], 2, top_k=5)])
    with pytest.raises(ValueError, match="top_p"):
        cb.serve([Request([1, 2], 2, temperature=0.5, top_p=1.5)])
    with pytest.raises(ValueError, match="top_k"):
        cb.serve([Request([1, 2], 2, temperature=0.5, top_k=0)])


# ----------------------------------------------- MoE admission capacity


def test_moe_admission_capacity_matches_standalone_when_binding():
    """ADVICE r5's capacity divergence, closed: admission prefills over
    the fixed ``prompt_buf`` window, but its expert queue capacity is
    the REAL prompt length's (``moe_capacity``, static per admission) —
    so with a BINDING eval capacity (ecf=1.0, far below the window's),
    the admission-written K/V equal the standalone prefill's at every
    layer (layer>0 K/V see layer-0's MoE outputs, so a routing
    difference would show). The old window-derived capacity provably
    diverges on the same input — asserted too, so this test bites."""
    cfg = dataclasses.replace(MoETransformerConfig.tiny(), max_seq_len=128,
                              capacity_factor=1.0, eval_capacity_factor=1.0,
                              top_k=1)
    model = MoETransformerLM(cfg)
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    tokens = [int(t) for t in rng.integers(0, 256, 12)]
    head = tokens[:-1]
    nn = len(head)
    Tb = 16
    cb = ContinuousBatcher(model, params, slots=2, t_max=128,
                           prompt_buf=Tb, segment=3)
    cb._cut_weights()            # the programs take the engine's tree
    # one admission wave's arrays, built the way _prefill_wave does —
    # the paged layout lands the head at logical slots 0..nn-1, mapped
    # through an explicit block table into pool blocks 1..nb
    bt, nb = cb.bt, cb.nb
    row_blocks = np.arange(1, nb + 1, dtype=np.int32)
    tables = row_blocks[None, :]
    prompt = np.zeros((1, Tb), np.int32)
    pmask = np.zeros((1, Tb), np.float32)
    prompt[0, :nn] = head
    pmask[0, :nn] = 1.0
    positions = np.tile(np.arange(Tb, dtype=np.int32), (1, 1))
    prefix_mask = np.zeros((1, 0), np.float32)
    blk_idx = np.full((1, Tb), cb._pool.num_blocks, np.int32)
    off_idx = np.zeros((1, Tb), np.int32)
    logical = np.arange(nn)
    blk_idx[0, :nn] = row_blocks[logical // bt]
    off_idx[0, :nn] = logical % bt

    def admit(cap):
        caches = jax.tree.map(jnp.zeros_like, cb._caches)
        kw = ({} if cap is None else
              {"moe_capacity": cap,
               "moe_capacity_rows": jnp.asarray([cap], jnp.int32)})
        new = cb._admit_c(cb.params, caches, jnp.asarray(tables),
                          jnp.asarray(prompt), jnp.asarray(pmask),
                          jnp.asarray(positions), jnp.asarray(prefix_mask),
                          jnp.asarray(blk_idx), jnp.asarray(off_idx), **kw)
        # row 0's logical view over its table: [2, hk, t_max, hd]
        return [np.asarray(c["kv"][:, row_blocks]).transpose(0, 2, 1, 3, 4)
                .reshape(2, c["kv"].shape[2], nb * bt, -1) for c in new]

    cap = model._block().prefill_capacity(len(tokens))
    assert cap < model._block().prefill_capacity(Tb)   # capacity binds
    new_caches = admit(cap)
    old_caches = admit(None)              # the old window-derived path

    from distributed_compute_pytorch_tpu.infer import prefill
    _, solo_caches = jax.jit(lambda p, t: prefill(model, p, t, 32))(
        params, jnp.asarray([tokens], jnp.int32))

    old_diverges = False
    for li in range(cb._n_layers):
        solo_kv = np.asarray(solo_caches[li]["kv"])[:, 0, :, :nn]
        new_kv = new_caches[li][:, :, :nn]
        old_kv = old_caches[li][:, :, :nn]
        np.testing.assert_allclose(new_kv, solo_kv, atol=1e-5)
        old_diverges |= bool(np.abs(old_kv - solo_kv).max() > 1e-3)
    assert old_diverges, ("window-derived capacity routed identically — "
                          "the scenario no longer exercises the fix")


# ------------------------------------------------- radix prefix cache


def _shared_prefix_requests(rng, n, prefix_len=19, sampled_every=3):
    """Zipf-ish workload: one hot system prompt (deliberately ending
    MID-BLOCK so copy-on-write attaches run), short per-request tails,
    sampled rows riding along."""
    shared = [int(t) for t in rng.integers(0, 256, prefix_len)]
    reqs = []
    for i in range(n):
        tail = [int(t)
                for t in rng.integers(0, 256, int(rng.integers(1, 5)))]
        r = Request(shared + tail, 6)
        if i % sampled_every == sampled_every - 1:
            r.temperature = 0.8
            r.seed = 50 + i
        reqs.append(r)
    return reqs


@pytest.mark.parametrize("name,model", _models()[:2])   # gpt2 + llama
def test_prefix_cache_token_parity_greedy_and_sampled(name, model):
    """THE paged-cache acceptance pin: prefix-cache-ON serving is
    token-identical to prefix-cache-OFF for greedy AND sampled rows
    (attachment changes where K/V come from, never a logical position,
    so the (seed, tokens-generated) key schedule is untouched); greedy
    rows additionally equal standalone generate; attaches/COW actually
    happen; nothing leaks."""
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(61)
    reqs = _shared_prefix_requests(rng, 8)
    off = ContinuousBatcher(model, params, slots=2, t_max=64,
                            prompt_buf=24, segment=3)
    out_off = off.serve(_clone(reqs))
    on = ContinuousBatcher(model, params, slots=2, t_max=64,
                           prompt_buf=24, segment=3, prefix_cache=True)
    results = on.serve_detailed(_clone(reqs))
    assert [r.tokens for r in results] == out_off, name
    for req, out in zip(reqs, out_off):
        if req.temperature > 0:
            continue
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new,
                        t_max=64)
        assert out == [int(t)
                       for t in np.asarray(solo)[0, len(req.tokens):]]
    s = on.stats
    assert s["prefix_hits"] > 0 and s["prefill_tokens_saved"] > 0
    assert s["cow_copies"] > 0             # the 19-token prefix ends
    assert s["cached_prefix_tokens"] == sum(
        r.cached_prefix_tokens for r in results)   # per-request metadata
    assert max(r.cached_prefix_tokens for r in results) >= 16
    assert on.last_slot_leaks == 0 and on.last_block_leaks == 0
    assert 0 < s["block_pool_occupancy"] <= 1


def test_prefix_cache_block_boundary_and_eviction():
    """Full-block attaches (prefix length an exact block multiple: no
    COW needed, blocks shared read-only) stay exact, and a stream too
    big for the configured pool evicts LRU entries instead of failing —
    with zero leaks either way."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(67)
    shared = [int(t) for t in rng.integers(0, 256, 16)]   # 2 full blocks
    reqs = [Request(shared + [int(t) for t in rng.integers(0, 256, 3)], 5)
            for _ in range(6)]
    # tight pool: the minimum legal size, so caching beyond the live
    # rows must evict
    cb = ContinuousBatcher(model, params, slots=2, t_max=40,
                           prompt_buf=24, segment=5, prefix_cache=True,
                           pool_blocks=2 * 5 + 1)
    outs = cb.serve(_clone(reqs))
    off = ContinuousBatcher(model, params, slots=2, t_max=40,
                            prompt_buf=24, segment=5)
    assert outs == off.serve(_clone(reqs))
    s = cb.stats
    assert s["prefix_hits"] > 0
    # shared span = 16 tokens = whole blocks: attaches never copy
    assert s["cow_copies"] == 0
    assert cb.last_block_leaks == 0 and cb.last_slot_leaks == 0


def test_prefix_cache_invariant_to_scheduling():
    """Attachment is a data-movement optimisation, not semantics: the
    cache-on stream is identical across slots/segment schedules (which
    change WHICH admissions hit the cache)."""
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(71)
    reqs = _shared_prefix_requests(rng, 6)
    outs = []
    for slots, seg in ((1, 4), (2, 3), (4, 6)):
        cb = ContinuousBatcher(model, params, slots=slots, t_max=64,
                               prompt_buf=24, segment=seg,
                               prefix_cache=True)
        outs.append(cb.serve(_clone(reqs)))
    assert outs[0] == outs[1] == outs[2]


def test_prefix_cache_rejects_moe():
    cfg = dataclasses.replace(MoETransformerConfig.tiny(), max_seq_len=128)
    model = MoETransformerLM(cfg)
    params, _ = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="prefix_cache"):
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=10,
                          prefix_cache=True)


def test_moe_no_drop_contract_exact_parity():
    """The documented no-drop contract, kept as a test: with eval
    capacity sized so NO token is capacity-dropped on either path
    (generous ecf), served outputs equal standalone generation token
    for token — including the deferred last prompt token (serve routes
    it in a full-capacity decode tick; the standalone prefill keeps it
    because capacity never binds)."""
    cfg = dataclasses.replace(MoETransformerConfig.tiny(), max_seq_len=128,
                              eval_capacity_factor=4.0)
    model = MoETransformerLM(cfg)
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(13)
    reqs = _requests(rng, 4, min_new=4, max_new=6)
    cb = ContinuousBatcher(model, params, slots=2, t_max=128,
                           prompt_buf=10, segment=3)
    outs = cb.serve(reqs)
    for i, (req, out) in enumerate(zip(reqs, outs)):
        solo = generate(model, params,
                        jnp.asarray([req.tokens], jnp.int32), req.max_new)
        want = [int(t) for t in np.asarray(solo)[0, len(req.tokens):]]
        assert out == want, (i, out, want)


def test_admission_wave_holds_at_most_wave_tokens_of_window(monkeypatch):
    """The prefill window that runs between two decode segments is bounded
    (``serve._WAVE_TOKENS``, counted in the window the wave's dispatches
    really hold: the sum of rows x window): four requests due together
    for four free rows, two long (a 16-token window each) and two short
    (one two-row dispatch of the 8-token rung), are 48 tokens of window.
    Under a bound of 24 they go out a long and a short one a wave, the
    second wave after a decode segment of the first, with the tokens of a
    solo run; under the default bound (every other test's case) the same
    call is one wave of three dispatches."""
    from distributed_compute_pytorch_tpu import serve as serve_mod
    model = GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=128))
    params, _ = model.init(jax.random.key(0))
    rng = np.random.default_rng(11)
    reqs = [Request([int(t) for t in rng.integers(0, 256, n)], 4)
            for n in (14, 5, 6, 12)]
    seen, default = {}, serve_mod._WAVE_TOKENS
    for bound in (default, 24):
        monkeypatch.setattr(serve_mod, "_WAVE_TOKENS", bound)
        cb = ContinuousBatcher(model, params, slots=4, t_max=128,
                               prompt_buf=32, segment=2)
        assert cb._admit_ladder == ((32, 1), (16, 1), (8, 2))
        outs = cb.serve(reqs)
        seen[bound] = (cb.stats["prefill_calls"], cb.stats["prefill_rows"],
                       cb.stats["prefill_window_tokens"],
                       cb.waste["parked_admission_lag"])
        for req, out in zip(reqs, outs):
            solo = generate(model, params,
                            jnp.asarray([req.tokens], jnp.int32),
                            req.max_new)
            assert out == [int(t)
                           for t in np.asarray(solo)[0, len(req.tokens):]]
    # one wave: (1, 16) + (1, 16) + (2, 8); nobody waits a segment
    assert seen[default] == (3, 4, 48, 0)
    # two waves of (1, 16) + (1, 8): the second pair sat parked while the
    # first wave's rows decoded
    assert seen[24][:3] == (4, 4, 48) and seen[24][3] > 0
