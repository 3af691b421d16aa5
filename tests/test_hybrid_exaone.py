"""The decoder of layer kinds (``models/hybrid.py``) and what it brought:
the held-experts layer (``models/moe.py::HeldExperts``), the banded flash
forward, the ring cache of window layers and their place in
``ContinuousBatcher``, each against the plain reference
``perfbench/reference/exaone_moe_ref.py`` at a small size on the CPU, on
seeded weights. Every tolerance says why it has its value."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.hybrid import HybridLM
from distributed_compute_pytorch_tpu.models.moe import HeldExperts
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from perfbench import weights
from perfbench.family import exaone_moe as fam
from perfbench.reference import exaone_moe_ref as ref

# The tiny configuration in the PUBLISHED keys: every kind of layer (a
# leading dense layer, then sliding, sliding, full, sliding over experts),
# 8 experts of which this chip holds 4, 2 a token, window 8.
CFG = {
    "family": "exaone_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 5,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                "sliding_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4, "sliding_window": 8,
    "router_num_experts": 8, "num_experts": 4, "experts_held": [0, 4],
    "num_experts_per_tok": 2, "num_shared_experts": 1,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "rms_norm_eps": 1e-5, "vocab_size": 512,
    "serving": {"slots": 4, "prefill_window": 32},
}

# float32 on both sides, the same weights: what is left is the order of
# summation (the program's fused matmuls, the reference at HIGHEST), a few
# 1e-6 a layer on logits of size ~1; 2e-4 leaves a decade of room and is a
# hundred times under what bfloat16 does (the control below).
TOL_F32 = 2e-4


def build(dtype="float32", seed=7, cfg=CFG):
    model = build_model(fam.BUILD_MODEL, **fam.model_kwargs(
        cfg, {"max_seq_len": 64, "param_dtype": dtype}))
    params = weights.make_params(ref.param_spec(cfg), seed,
                                 ref.param_dtypes(cfg, dtype))
    return model, params


def test_parameter_tree_is_the_references():
    model, params = build()
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    assert have == want


def test_full_forward_matches_the_reference_on_logits():
    model, params = build()
    toks = np.random.default_rng(0).integers(1, 512, 40)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL_F32


@pytest.mark.parametrize("prefill", [0, 21, 29])
def test_prefill_then_decode_through_the_batcher_matches_on_logits(prefill):
    """40 tokens: five windows of 8 (the ring wraps, and a prefill of 21
    or 29 leaves it mid-ring) and five blocks of 8 (the pool pages)."""
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=4, t_max=64, prompt_buf=32)
    assert cb.stats_snapshot()["cache_kinds"] == [
        "ring", "ring", "ring", "paged", "ring"]
    toks = np.random.default_rng(1).integers(1, 512, 40)
    got = cb.logit_probe(toks, prefill=prefill)
    want = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG)[prefill:]
    assert got.shape == want.shape
    assert float(np.max(np.abs(got - np.asarray(want)))) < TOL_F32


def test_a_lower_precision_control_fails_the_tolerance():
    """The same program with bfloat16 weights and activations, against
    the float32 reference on the float32 values of those weights: rounding
    to 8 bits of mantissa moves logits by ~1e-2, fifty times the
    tolerance."""
    model, params = build("bfloat16")
    toks = np.random.default_rng(0).integers(1, 512, 40)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    err = float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - want)))
    assert err > 10 * TOL_F32
    # and so does the reference's own int8 control form
    low = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG, "int8")
    assert float(jnp.max(jnp.abs(low - want))) > 10 * TOL_F32


def test_serving_is_greedy_equal_to_the_full_forward_and_counts_experts():
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=4, t_max=64, prompt_buf=32)
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=m) for n, m in ((20, 16), (5, 12), (31, 9))]
    for rq, res in zip(reqs, cb.serve_detailed(reqs)):
        assert res.status == "ok" and len(res.tokens) == rq.max_new
        # teacher-forced on what was served: every served token is the
        # full forward's greedy choice after the tokens before it
        seq = list(rq.tokens) + list(res.tokens)
        lg, _ = model.apply(params, {}, jnp.asarray([seq[:-1]], jnp.int32))
        want = jnp.argmax(lg[0, len(rq.tokens) - 1:], -1)
        assert list(res.tokens) == [int(t) for t in want]
    snap = cb.stats_snapshot()
    st = snap["stats"]
    # 4 sparse layers x 2 experts a token x the ticks of the rows planned
    assert st["expert_assignments"] == 4 * 2 * snap["waste"]["planned_ticks"]
    assert 0 < st["expert_assignments_held"] < st["expert_assignments"]
    assert sum(st[f"expert_load_{e}"] for e in range(4)) == st[
        "expert_assignments_held"]
    assert snap["expert_load_max_over_mean"] >= 1.0
    assert snap["slot_leaks"] == snap["block_leaks"] == 0
    # a fresh session on the same programs: both kinds of cache re-zeroed
    cb.reset()
    again = cb.serve_detailed(reqs[:1])[0]
    assert again.status == "ok" and cb.stats["expert_assignments"] > 0


@pytest.mark.parametrize("what,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("speculate", {"speculate": 2}),
    ("host_cache", {"prefix_cache": True, "host_cache_blocks": 4}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 8}),
])
def test_what_layer_kinds_cannot_be_served_with_is_refused(what, kw):
    model, params = build()
    with pytest.raises(ValueError, match="window layers and held experts"):
        ContinuousBatcher(model, params, slots=2, t_max=32, prompt_buf=16,
                          **kw)


def test_the_training_path_refuses():
    model, params = build()
    with pytest.raises(NotImplementedError):
        model.apply(params, {}, jnp.zeros((1, 4), jnp.int32), train=True)
    with pytest.raises(NotImplementedError):
        model.loss_fn(None, None)


# ---- the banded flash forward ------------------------------------------

def _dense_band(q, k, v, window, kv_mask=None):
    rep = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, rep, 1), jnp.repeat(v, rep, 1)
    t = q.shape[2]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    see = ((j <= i) & (j > i - window))[None, None]
    if kv_mask is not None:
        see = see & (kv_mask[:, None, None, :] > 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / q.shape[-1] ** 0.5
    return jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(see, s, -1e30), -1), v)


@pytest.mark.parametrize("t,window,block,masked", [
    (300, 128, None, False),     # 256-blocks, padded length, two bands
    (256, 8, 128, True),         # a narrow band inside one block, pad keys
    (384, 200, 128, False),      # a band over three 128-blocks
])
def test_banded_flash_forward_matches_a_dense_masked_softmax(t, window, block,
                                                             masked):
    """Interpret mode, float32: the kernel's online softmax against the
    dense one differs by summation order only, ~1e-6 on outputs of size
    ~1; 2e-5 leaves room."""
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention_band)
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, 4, t, 32))
    k = jax.random.normal(ks[1], (2, 2, t, 32))
    v = jax.random.normal(ks[2], (2, 2, t, 32))
    kv_mask, real = None, t
    if masked:      # the second row's last 57 keys are pads; rows whose
        real = t - 57   # every key is a pad are garbage on both sides
        kv_mask = (jnp.arange(t)[None, :] < jnp.asarray([[t], [real]])
                   ).astype(jnp.float32)
    want = _dense_band(q, k, v, window, kv_mask)[:, :, :real]
    got = flash_attention_band(q, k, v, window=window, kv_mask=kv_mask,
                               block=block)[:, :, :real]
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    # the dispatcher's dense path masks the same band
    xla = A.attention(q, k, v, causal=True, window=window, kv_mask=kv_mask,
                      impl="xla")[:, :, :real]
    assert float(jnp.max(jnp.abs(xla - want))) < 2e-5


def test_banded_flash_forward_refuses_a_backward():
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention_band)
    q = jnp.ones((1, 2, 128, 32))
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(lambda q: flash_attention_band(
            q, q[:, :1], q[:, :1], window=16).sum())(q)


# ---- the ring ------------------------------------------------------------

def test_ring_prefill_then_ticks_equal_window_attention():
    """A window layer's cache life: a prefill of n tokens leaves the ring,
    ticks write at pos % R and read under the position mask; every tick's
    output equals dense window attention over the whole sequence (float32,
    summation order only: 1e-5)."""
    window, R, hk, H, hd, T = 8, 16, 2, 4, 16, 45
    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (2, H, T, hd))
    k = jax.random.normal(ks[1], (2, hk, T, hd))
    v = jax.random.normal(ks[2], (2, hk, T, hd))
    want = _dense_band(q, k, v, window)
    n0 = jnp.asarray([3, 19])              # heads shorter / longer than R
    ring = A.ring_from_prefill(k, v, n0, R)
    # what an earlier occupant left in the slots must never be attended
    ring = jnp.where(A.ring_positions(n0 - 1, R)[None, :, None, :, None] >= 0,
                     ring, 1e3)
    cache = {"kv": ring}
    for step in range(20):
        pos = n0 + step
        at = lambda a: jnp.take_along_axis(
            a, pos[:, None, None, None], axis=2)
        o, cache = A.ring_write_and_attend(at(q), at(k), at(v), cache, pos,
                                           window)
        assert float(jnp.max(jnp.abs(o - at(want)))) < 1e-5, step


# ---- the held experts ----------------------------------------------------

def _layer_and_weights(held, shared=True, seed=5, dense_max=512):
    first, count = held
    cfg = dict(CFG, experts_held=list(held), num_experts=count)
    spec = ref.layer_spec(cfg, 1)["moe"]
    full = weights.make_params(
        ref.layer_spec(dict(CFG, experts_held=[0, 8], num_experts=8),
                       1)["moe"], seed, "float32")
    p = dict(full, experts={k: a[first:first + count]
                            for k, a in full["experts"].items()})
    assert jax.tree.map(lambda a: a.shape, p) == jax.tree.map(
        lambda s: s[0], spec, is_leaf=weights._is_leaf)
    layer = HeldExperts(64, 32, 8, 2, experts_held=held,
                        shared_d_ff=32 if shared else 0, routed_scale=2.5,
                        dense_max_tokens=dense_max)
    return layer, p, full, cfg


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_every_share_adds_up_to_the_uncut_layer(form):
    """The share test: the partial results of both shares of the 8 experts
    (4 held each), the shared expert counted once, add up to the uncut
    reference's layer output. float32: 1e-5 of outputs of size ~0.1."""
    x = jax.random.normal(jax.random.key(6), (3, 50, 64))
    total = 0.0
    for held, shared in (((0, 4), True), ((4, 4), False)):
        layer, p, full, cfg = _layer_and_weights(
            held, shared, dense_max=512 if form == "dense" else 0)
        total = total + layer.apply(p, x)
    uncut = ref.moe_partial(x.reshape(-1, 64), full, cfg, held=None)
    assert float(jnp.max(jnp.abs(total.reshape(-1, 64) - uncut))) < 1e-5


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_a_skewed_router_drops_nothing(form):
    """One expert takes every token, one takes none, and the sorted form's
    windows (sized for an even load) overflow several times: the layer
    still equals the reference's held part for every token, pads route
    nowhere and the counts say where the tokens went."""
    layer, p, _, cfg = _layer_and_weights(
        (0, 4), dense_max=512 if form == "dense" else 0)
    bias = jnp.asarray([50.0, -50.0, 0.3, 0, 0, 0, 0, 0], jnp.float32)
    p = dict(p, router_bias=bias)
    x = jax.random.normal(jax.random.key(8), (600, 64))
    mask = (jnp.arange(600) % 7 != 0).astype(jnp.float32)
    sink: list = []
    got = layer.apply(p, x, token_mask=mask, counts_sink=sink)
    want = ref.moe_partial(x, p, cfg, held=(0, 4))
    shared_only = ref.moe_partial(x, dict(p, experts={
        k: a[:0] for k, a in p["experts"].items()}), cfg, held=(0, 0))
    live = mask[:, None] > 0.5
    assert float(jnp.max(jnp.abs(jnp.where(live, got - want, 0.0)))) < 1e-5
    # a pad token gets the shared expert and no routed expert
    assert float(jnp.max(jnp.abs(jnp.where(live, 0.0,
                                           got - shared_only)))) < 1e-5
    counts = np.asarray(sink[0])
    n_live = int(mask.sum())
    # the head: assignments, held, held experts chosen, held experts
    assert counts[0] == 2 * n_live and counts[4] == n_live   # expert 0: all
    assert counts[5] == 0                                    # expert 1: none
    assert counts[1] == counts[4:].sum() > layer.window_rows(600)
    assert counts[2] == (counts[4:] > 0).sum() < counts[3] == 4


@pytest.mark.parametrize("held_rows", [0, 700, 1024, 1100, 1200])
def test_the_sorted_forms_windows_cover_every_held_row(held_rows):
    """600 tokens x 2 assignments: a first window of 1024 sorted rows, then
    windows of 512 for what it leaves. No held row, a first window partly
    and exactly full, one tail window partly full and every assignment
    held: the sorted form equals the dense form over the same
    assignments. float32: 1e-5 of outputs of size ~0.1."""
    layer, p, _, _ = _layer_and_weights((0, 4), shared=False, dense_max=0)
    assert (layer.window_rows(600), layer.tail_rows(600)) == (1024, 512)
    rng = np.random.default_rng(held_rows)
    local = np.full(1200, 4)                       # 4 = not held
    local[rng.permutation(1200)[:held_rows]] = rng.integers(0, 4, held_rows)
    local = jnp.asarray(local.reshape(600, 2), jnp.int32)
    x = jax.random.normal(jax.random.key(9), (600, 64))
    w = jax.random.uniform(jax.random.key(10), (600, 2))
    got = layer._sorted(p["experts"], x, local, w)
    want = layer._dense(p["experts"], x, local, w)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert (held_rows > 0) == bool(jnp.any(got != 0))


def test_the_window_sizes_at_the_published_widths():
    """The first window is an eighth over an even router's held rows and
    a tail window an eighth of it, both in whole 512s: K-EXAONE's 2048-token
    dispatch (16 of 128 held) and JoyAI's 16,384-token one (32 of 256)."""
    kex = HeldExperts(6144, 2048, 128, 8, experts_held=(0, 16))
    joy = HeldExperts(2048, 768, 256, 8, experts_held=(0, 32))
    assert (kex.window_rows(2048), kex.tail_rows(2048)) == (2560, 512)
    assert (joy.window_rows(16384), joy.tail_rows(16384)) == (18432, 2048)
    assert (joy.window_rows(2048), joy.tail_rows(2048)) == (2560, 512)
