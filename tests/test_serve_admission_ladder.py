"""The admission ladder (``serve.admission_ladder``, ``wave_dispatches``,
``ContinuousBatcher._prefill_wave``): a wave costs what its prompts hold.
Its rows go out at the smallest window rung that covers each head, in
dispatches of at most half of ``prompt_buf`` in window (the one-row,
full-window case apart); the shapes are a
small fixed set, all built before the first request is served; pad rows
write nothing; and the tokens served are those of a solo run."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.infer import generate
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request, admission_ladder, ladder_shapes,
    wave_dispatches)


# ---------------------------------------------- the ladder and the grouping


def test_the_cells_ladder_is_exactly_seven_shapes():
    ladder = admission_ladder(2048, 8)
    assert ladder == ((2048, 1), (1024, 1), (512, 2), (256, 4))
    assert sorted(ladder_shapes(ladder)) == sorted(
        [(1, 2048), (1, 1024), (1, 512), (2, 512),
         (1, 256), (2, 256), (4, 256)])
    assert all(r * w <= 1024 or (r, w) == (1, 2048)
               for r, w in ladder_shapes(ladder))
    # under a mesh R is the batch axes' product times a power of two
    assert ladder_shapes(ladder, 2) == [
        (2, 2048), (2, 1024), (2, 512), (2, 256), (4, 256)]


@pytest.mark.parametrize("prompt_buf,block,want", [
    (10, 8, ((16, 1),)),                       # the tests' usual size
    (16, 8, ((16, 1), (8, 1))),
    (32, 8, ((32, 1), (16, 1), (8, 2))),
    (64, 8, ((64, 1), (32, 1), (16, 2), (8, 4))),
    (40, 32, ((64, 1),)),                      # an int8 pool's block
    (100, 8, ((104, 1), (56, 1), (32, 2), (16, 4))),
    (4096, 16, ((4096, 1), (2048, 1), (1024, 2), (512, 4))),
])
def test_ladder_derives_from_prompt_buf_and_the_block(prompt_buf, block,
                                                      want):
    ladder = admission_ladder(prompt_buf, block)
    assert ladder == want
    shapes = ladder_shapes(ladder)
    assert len(shapes) == len(set(shapes)) <= 12
    for r, w in shapes:
        # whole blocks, and but for the one-row, full-window case never
        # more window than half of it (a block's rounding a row apart)
        assert w % block == 0 and w >= block
        assert (r, w) == (1, ladder[0][0]) or (
            2 * r * w <= ladder[0][0] + 2 * r * (block - 1))
    assert ladder[0][0] >= prompt_buf


@pytest.mark.parametrize("dp", [1, 2, 4])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_row_goes_out_once_at_a_rung_that_covers_it(seed, dp):
    ladder = admission_ladder(2048, 8)
    rng = np.random.default_rng(seed)
    heads = [int(min(2048, max(0, h))) for h in
             rng.lognormal(np.log(256), 1.0, 40)] + [0, 2048, 256, 257]
    out = wave_dispatches(heads, ladder, dp)
    seen = sorted(j for _, _, take in out for j in take)
    assert seen == list(range(len(heads)))
    shapes = set(ladder_shapes(ladder, dp))
    rows_of = dict(ladder)
    for w, r, take in out:
        assert (r, w) in shapes and len(take) <= r and r % dp == 0
        assert len(take) <= max(rows_of[w], dp)
        for j in take:
            assert heads[j] <= w
            # the smallest rung that covers it
            assert all(heads[j] > w2 for w2, _ in ladder if w2 < w)
        if dp == 1:
            assert (r * w <= 1024 or r == 1) and r < 2 * len(take)


def test_the_chat_mix_needs_a_third_of_the_window():
    """What the change is for, as counts: rounded up to its rungs the
    log-normal chat mix (median 256, sigma 1.0, clipped 16-2048) needs
    under a third of the 2048 tokens a row that one window costs."""
    rng = np.random.default_rng(20260927)
    heads = np.clip(rng.lognormal(np.log(256), 1.0, 4000), 16,
                    2048).astype(int)
    ladder = admission_ladder(2048, 8)
    rung = [min(w for w, _ in ladder if w >= h) for h in heads]
    assert 560 < np.mean(rung) < 640


# ------------------------------------------------- through the batcher


def _gpt2(max_seq_len=128):
    model = GPT2(dataclasses.replace(GPT2Config.tiny(),
                                     max_seq_len=max_seq_len))
    return model, model.init(jax.random.key(0))[0]


def _llama(max_seq_len=128):
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                        max_seq_len=max_seq_len))
    return model, model.init(jax.random.key(0))[0]


def _hybrid(max_seq_len=128):
    from test_hybrid_exaone import CFG
    from distributed_compute_pytorch_tpu.models.registry import build_model
    from perfbench import weights
    from perfbench.family import exaone_moe as fam
    from perfbench.reference import exaone_moe_ref as ref
    model = build_model(fam.BUILD_MODEL, **fam.model_kwargs(
        CFG, {"max_seq_len": max_seq_len, "param_dtype": "float32"}))
    return model, weights.make_params(
        ref.param_spec(CFG), 7, ref.param_dtypes(CFG, "float32"))


def _solo(model, params, req):
    out = generate(model, params, jnp.asarray([req.tokens], jnp.int32),
                   req.max_new)
    return [int(t) for t in np.asarray(out)[0, len(req.tokens):]]


def _greedy_by_full_forward(model, params, req, served):
    """Teacher-forced on what was served: every served token is the full
    forward's greedy choice after the tokens before it (a model of layer
    kinds has no ``generate``)."""
    seq = list(req.tokens) + list(served)
    lg, _ = model.apply(params, {}, jnp.asarray([seq[:-1]], jnp.int32))
    return [int(t) for t in jnp.argmax(lg[0, len(req.tokens) - 1:], -1)]


@pytest.mark.parametrize("family", ["gpt2", "llama", "hybrid"])
def test_two_waves_over_every_rung_serve_the_solo_tokens(family):
    model, params = {"gpt2": _gpt2, "llama": _llama,
                     "hybrid": _hybrid}[family]()
    cb = ContinuousBatcher(model, params, slots=6, t_max=128,
                           prompt_buf=64, segment=4)
    assert cb._admit_ladder == ((64, 1), (32, 1), (16, 2), (8, 4))
    rng = np.random.default_rng(5)
    # two waves of six, the heads of each spanning all four rungs
    lens = [3, 64, 12, 30, 9, 2, 20, 5, 50, 8, 17, 33]
    reqs = [Request([int(t) for t in rng.integers(1, 256, n)],
                    int(rng.integers(3, 7))) for n in lens]
    outs = cb.serve(reqs)
    for req, out in zip(reqs, outs):
        want = (_greedy_by_full_forward(model, params, req, out)
                if family == "hybrid" else _solo(model, params, req))
        assert out == want, (family, len(req.tokens), out, want)
    s = cb.stats
    assert s["prefill_rows"] == len(reqs)
    assert s["prefill_calls"] < len(reqs)          # rungs share dispatches
    assert s["prefill_tokens"] == sum(n - 1 for n in lens)
    assert s["prefill_tokens"] < s["prefill_window_tokens"] < 64 * len(reqs)
    assert cb.last_slot_leaks == cb.last_block_leaks == 0


@pytest.mark.parametrize("family", ["gpt2", "hybrid"])
def test_a_null_dispatch_writes_nothing(family):
    """Pad rows are all masked and their targets out of range: a dispatch
    of pad rows only (what builds the ladder) leaves every cache, pool
    and ring alike, bit for bit as it was."""
    model, params = {"gpt2": _gpt2, "hybrid": _hybrid}[family]()
    cb = ContinuousBatcher(model, params, slots=3, t_max=64, prompt_buf=32)
    cb._caches = jax.tree.map(
        lambda a: jnp.full(a.shape, 3, a.dtype), cb._caches)
    cb._cut_weights()            # what a serve call does before it warms
    cb._warm_ladder()
    assert cb._admit_c._cache_size() >= len(ladder_shapes(cb._admit_ladder))
    for leaf in jax.tree.leaves(cb._caches):
        assert bool(jnp.all(leaf == 3))
    assert cb.stats["prefill_calls"] == cb.stats["prefill_window_tokens"] == 0


@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_every_admission_shape_is_built_before_the_first_request(family):
    """After the first served call ``jit``'s cache holds the ladder and
    nothing else, and a second call with other lengths and row counts
    adds no program: no wave traces, compiles or fetches one later."""
    # a sequence length no other test uses: no donor's programs to borrow
    model, params = {"gpt2": _gpt2, "llama": _llama}[family](
        max_seq_len=136)
    cb = ContinuousBatcher(model, params, slots=8, t_max=128,
                           prompt_buf=64, segment=4)
    n_shapes = len(ladder_shapes(cb._admit_ladder))
    assert n_shapes == 7
    cb.serve([Request([1, 2, 3], 2)])
    assert cb._admit_c._cache_size() == n_shapes
    rng = np.random.default_rng(9)
    for lens in ([64, 5, 5, 5], [9, 9, 9, 9, 9, 9, 9, 40], [17, 33, 2]):
        cb.serve([Request([int(t) for t in rng.integers(1, 256, n)], 3)
                  for n in lens])
    assert cb._admit_c._cache_size() == n_shapes


def test_thirty_two_requests_due_together_go_in_as_one_wave():
    """The burst that a whole-window wave could not hold: 32 rows due
    together are admitted at once, in dispatches of the ladder's shapes
    (none over ``prompt_buf`` tokens of window, none of several rows over
    half of it), with no program beyond the ladder."""
    model, params = _gpt2(max_seq_len=144)
    cb = ContinuousBatcher(model, params, slots=32, t_max=96,
                           prompt_buf=64, segment=4)
    rng = np.random.default_rng(13)
    lens = np.clip(rng.lognormal(np.log(8), 1.0, 32), 2, 64).astype(int)
    reqs = [Request([int(t) for t in rng.integers(1, 256, n)], 4)
            for n in lens]
    windows = []
    real = cb._dispatch_prefill
    cb._dispatch_prefill = lambda e, r, w, lp: (
        windows.append((len(e), r, w)), real(e, r, w, lp))[1]
    outs = cb.serve(reqs)
    assert all(len(o) == 4 for o in outs)
    served = [x for x in windows if x[0]]
    assert sum(k for k, _, _ in served) == 32
    assert all(r * w <= 32 or (r, w) == (1, 64) for _, r, w in windows)
    assert cb.waste["parked_admission_lag"] == 0     # nobody waited a segment
    assert cb._admit_c._cache_size() == len(ladder_shapes(cb._admit_ladder))
    assert cb.stats["prefill_window_tokens"] == sum(
        r * w for _, r, w in served)
