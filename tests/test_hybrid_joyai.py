"""Latent attention in the decoder of layer kinds (``models/hybrid.py``,
mixer ``latent_attention``) and what it brought: the compressed paged
cache (cache kind ``latent``) with its decode kernel, the absorbed decode
form beside the expanded prefill form, flash attention at unequal q/k and
v head widths, interleaved rotation, norms on a sublayer's input; each
against the plain reference ``perfbench/reference/joyai_llm_flash_ref.py``
(expanded form only) at a small size on the CPU, on seeded weights. Every
tolerance says why it has its value."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.hybrid import (
    HybridConfig, HybridLM)
from distributed_compute_pytorch_tpu.models.moe import HeldExperts
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.rotary import apply_rope_interleaved
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from perfbench import weights
from perfbench.family import joyai_llm_flash as fam
from perfbench.reference import joyai_llm_flash_ref as ref

# The tiny configuration in the PUBLISHED keys: every part of the family
# (low-rank queries, the joint compressed K/V with a shared rotary key, a
# leading dense layer, then experts of which this chip holds 4 of 16).
CFG = {
    "family": "joyai_llm_flash", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "num_hidden_layers": 5,
    "first_k_dense_replace": 1, "router_num_experts": 16,
    "n_routed_experts": 4, "experts_held": [0, 4], "num_experts_per_tok": 2,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "rope_theta": 32000000, "rms_norm_eps": 1e-6,
    "vocab_size": 512, "serving": {"slots": 4},
}

# float32 on both sides, the same weights: what is left is the order of
# summation (the program's fused matmuls, the reference at HIGHEST) and, in
# a decode tick, the ABSORBED form's other order of products (W_uk folded
# into the query instead of expanding the key): a few 1e-6 a layer on
# logits of size ~1. 2e-4 leaves a decade of room and is a hundred times
# under what bfloat16 does (the control below).
TOL_F32 = 2e-4


def build(dtype="float32", seed=7, cfg=CFG, t_max=128):
    model = build_model(fam.BUILD_MODEL, **fam.model_kwargs(
        cfg, {"max_seq_len": t_max, "param_dtype": dtype}))
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


def test_defaults_build_the_window_and_full_family_unchanged():
    """A ``HybridConfig`` that names none of the new keys is what it was:
    norms after the sublayers, no latent width, and the parameter tree of
    the K-EXAONE family's tiny configuration equals that family's own
    reference spec, leaf for leaf (no key of the latent mixer or of the
    input norms in it)."""
    from perfbench.family import exaone_moe
    from perfbench.reference import exaone_moe_ref
    from tests.test_hybrid_exaone import CFG as EXAONE
    c = HybridConfig()
    assert c.norm_placement == "post" and c.latent_width == 0
    assert (c.q_lora_rank, c.kv_lora_rank, c.qk_nope_head_dim,
            c.qk_rope_head_dim, c.v_head_dim) == (0,) * 5
    model = build_model("hybrid", **exaone_moe.model_kwargs(
        EXAONE, {"max_seq_len": 64, "param_dtype": "float32"}))
    assert model.cache_block_tokens is None
    have = jax.tree.map(lambda a: tuple(a.shape), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.key(0)))
    want = jax.tree.map(lambda s: s[0], exaone_moe_ref.param_spec(EXAONE),
                        is_leaf=weights._is_leaf)
    assert have == want
    assert set(have["layers"][1]) == {
        "q", "k", "v", "o", "q_norm", "k_norm", "post_attn_norm",
        "post_mlp_norm", "moe"}


def test_full_forward_matches_the_reference_on_logits():
    model, params = build()
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    assert float(jnp.max(jnp.abs(got[0] - want))) < TOL_F32


@pytest.mark.parametrize("prefill", [0, 21, 45])
def test_prefill_then_decode_through_the_batcher_matches_on_logits(prefill):
    """100 tokens over four pool blocks of 32; a prefill of 21 goes out at
    an admission window of 32 and one of 45 at 64 (two rungs), the rest
    through decode ticks in the absorbed form; every logit against the
    reference's expanded full forward."""
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=4, t_max=128, prompt_buf=64)
    assert cb.bt == 32 and cb.nb == 4
    snap = cb.stats_snapshot()
    assert snap["cache_kinds"] == ["latent"] * 5
    # 40 channels of float32 a token, in one 128-lane tile
    assert snap["cache_bytes_per_token"] == {"latent": 128 * 4}
    toks = np.random.default_rng(1).integers(1, 512, 100)
    got = cb.logit_probe(toks, prefill=prefill)
    want = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG)[prefill:]
    assert got.shape == want.shape
    assert float(np.max(np.abs(got - np.asarray(want)))) < TOL_F32


def test_a_lower_precision_control_fails_the_tolerance():
    """The same program with bfloat16 weights and activations, against the
    float32 reference on the float32 values of those weights: rounding to
    8 bits of mantissa moves logits by ~1e-2, fifty times the tolerance;
    and so does the reference's own int8 control form."""
    model, params = build("bfloat16")
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG)
    err = float(jnp.max(jnp.abs(got[0].astype(jnp.float32) - want)))
    assert err > 10 * TOL_F32
    low = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG, "int8")
    assert float(jnp.max(jnp.abs(low - want))) > 10 * TOL_F32


def test_the_absorbed_decode_form_equals_the_expanded_form():
    """ONE layer: its whole-sequence ``apply`` (expanded: every head's
    k_nope and v from the compressed channels) against ``decode_step``
    tick by tick (absorbed: W_uk in the query, W_uv in the output, the
    heads attending the cached vectors) over a cache of several blocks,
    and the vectors the ticks cached against those the prefill captures
    and the reference computes. float32, summation order only: 2e-5 on
    activations of size ~1."""
    model, params = build()
    block, p = model.layer_block(1), params["layers"][1]
    T, bt = 70, 8
    x = jax.random.normal(jax.random.key(3), (1, T, 64))
    sink: list = []
    want = block.apply(p, x, kv_sink=sink)
    W = model.config.latent_width
    nb = -(-T // bt)
    Wp = A.latent_pool_width(W)
    # handed over as the planes of the pool's blocks, in whole lane tiles
    assert list(sink[0]) == ["kv"] and sink[0]["kv"].shape == (1, 1, 1, T, Wp)
    assert not sink[0]["kv"][..., W:].any()
    captured = sink[0]["kv"][0, :, 0, :, :W]
    cache = {"kv": jnp.zeros((1, nb + 1, 1, bt, Wp)),
             "table": (jnp.arange(nb, dtype=jnp.int32) + 1)[None]}
    step = jax.jit(block.decode_step)
    for t in range(T):
        y, cache = step(p, x[:, t:t + 1], cache, jnp.asarray([t]))
        assert float(jnp.max(jnp.abs(y - want[:, t:t + 1]))) < 2e-5, t
    held = cache["kv"][0, 1:, 0].reshape(nb * bt, Wp)[:T]
    assert float(jnp.max(jnp.abs(held[:, :W] - captured[0]))) < 2e-5
    assert not held[:, W:].any()                       # the lane padding
    normed = ref._rms(x[0], p["pre_attn_norm"]["scale"], 1e-6)
    assert float(jnp.max(jnp.abs(
        captured[0] - ref.latent_of(normed, p, CFG)))) < 2e-5


@pytest.mark.parametrize("bt,chunk", [(8, 512), (32, 64), (16, 16)])
def test_latent_decode_kernel_matches_the_gather(bt, chunk, monkeypatch):
    """``dcp_paged_latent_decode_attn`` in interpret mode against the
    gather + dense fallback it replaces on the chip: rows of one token, of
    a chunk's edge and of many chunks, through a scattered table; chunks
    of many blocks, of two and of one. float32, online against dense
    softmax: 2e-5."""
    from distributed_compute_pytorch_tpu.ops.pallas import decode_attention
    monkeypatch.setattr(decode_attention, "_LATENT_CHUNK_TOKENS", chunk)
    rng = np.random.default_rng(0)
    B, H, W, V, T = 4, 4, 256, 128, 640
    nb = T // bt
    P = B * nb + 1
    pool = jnp.asarray(rng.standard_normal((1, P, 1, bt, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, W)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(P - 1)[:B * nb].reshape(B, nb) + 1, jnp.int32)
    pos = jnp.asarray([0, chunk - 1, chunk, T - 1], jnp.int32)
    want = A.latent_attention_gathered(q, pool, table, pos, v_width=V,
                                       scale=0.07)
    got = decode_attention.paged_latent_decode_attention_pallas.__wrapped__(
        q, pool, table, pos, v_width=V, scale=0.07, interpret=True)
    assert got.shape == (B, H, V)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5


# True = the row is parked (an all-trash table); live rows sit at
# LATENT_POS: several chunks long wherever a parked run has a live side
LATENT_POS = [300, 639, 64, 200, 500, 0]
LATENT_PARKED = {
    "first_parked": [1, 1, 0, 0, 0, 0],
    "last_parked": [0, 0, 0, 0, 0, 1],
    "alternating": [1, 0, 1, 0, 1, 0],
    "one_live_row_in_the_middle": [1, 1, 1, 0, 1, 1],
    "long_rows_round_a_parked_run": [0, 0, 1, 1, 0, 1],
    "all_parked": [1, 1, 1, 1, 1, 1],
    "none_parked": [0, 0, 0, 0, 0, 0],
}


@pytest.mark.parametrize("name", list(LATENT_PARKED))
def test_latent_decode_kernel_skips_parked_rows(name, monkeypatch):
    """A row whose table is all trash is parked: the kernel attends
    nothing for it and writes zeros. Wherever the parked rows are, a live
    row's output is BIT FOR BIT the kernel's own with every row live (the
    stream's hand-over past a parked run changes nothing a row can see)
    and matches the gather (float32: 2e-5). Chunks of 64 tokens: the live
    rows hold up to ten."""
    from distributed_compute_pytorch_tpu.ops.pallas import decode_attention
    monkeypatch.setattr(decode_attention, "_LATENT_CHUNK_TOKENS", 64)
    rng = np.random.default_rng(1)
    B, H, W, V, T, bt = len(LATENT_POS), 4, 256, 128, 640, 16
    nb = T // bt
    P = B * nb + 1
    pool = jnp.asarray(rng.standard_normal((1, P, 1, bt, W)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, H, W)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(P - 1)[:B * nb].reshape(B, nb) + 1, jnp.int32)
    pos = jnp.asarray(LATENT_POS, jnp.int32)
    parked = np.asarray(LATENT_PARKED[name], bool)

    def run(table):
        return np.asarray(
            decode_attention.paged_latent_decode_attention_pallas.__wrapped__(
                q, pool, table, pos, v_width=V, scale=0.07, interpret=True))
    every_row_live = run(table)
    got = run(jnp.where(parked[:, None], 0, table))
    want = np.asarray(A.latent_attention_gathered(
        q, pool, table, pos, v_width=V, scale=0.07))
    assert not got[parked].any()
    np.testing.assert_array_equal(got[~parked], every_row_live[~parked])
    assert np.abs(got - want)[~parked].max(initial=0.0) < 2e-5


@pytest.mark.parametrize("t,masked", [(300, False), (256, True)])
def test_flash_forward_at_unequal_widths_matches_a_dense_softmax(t, masked):
    """q and k heads of 48 channels, v heads of 32 (the shape class of 192
    against 128), causal, with and without pad keys: the kernel in
    interpret mode against a dense masked softmax. float32: 2e-5."""
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)
    ks = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(ks[0], (2, 4, t, 48))
    k = jax.random.normal(ks[1], (2, 4, t, 48))
    v = jax.random.normal(ks[2], (2, 4, t, 32))
    kv_mask, real = None, t
    if masked:
        real = t - 57
        kv_mask = (jnp.arange(t)[None, :] < jnp.asarray([[t], [real]])
                   ).astype(jnp.float32)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    see = (j <= i)[None, None]
    if masked:
        see = see & (kv_mask[:, None, None, :] > 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / 48 ** 0.5
    want = jnp.einsum("bhqk,bhkd->bhqd",
                      jax.nn.softmax(jnp.where(see, s, -1e30), -1), v)
    got = flash_attention(q, k, v, causal=True, kv_mask=kv_mask)
    assert got.shape == (2, 4, t, 32)
    assert float(jnp.max(jnp.abs(got - want)[:, :, :real])) < 2e-5
    xla = A.attention(q, k, v, causal=True, kv_mask=kv_mask, impl="xla")
    assert float(jnp.max(jnp.abs(xla - want)[:, :, :real])) < 2e-5


def test_flash_forward_at_unequal_widths_refuses_a_backward():
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)
    q = jnp.ones((1, 2, 128, 48))
    v = jnp.ones((1, 2, 128, 32))
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash_attention(q, q, v, causal=True).sum())(q)


def test_interleaved_rotation_is_a_complex_rotation_of_the_pairs():
    """Channels (2i, 2i+1) of the last 8 of a head's 24 channels as the
    complex number x[2i] + i x[2i+1], turned by exp(i pos theta^(-2i/8));
    the first 16 pass through. Per-row positions and shared ones; the
    reference's own rotation does the same. float32 against complex64:
    1e-5 on values of size ~1 (the angle reaches 1e3 radians)."""
    x = jax.random.normal(jax.random.key(0), (2, 3, 5, 24))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 100, 1000, 5, 0]])
    theta = 10000.0
    got = apply_rope_interleaved(x, pos, theta, rotary_dim=8)
    assert (got[..., :16] == x[..., :16]).all()
    z = x[..., 16::2] + 1j * x[..., 17::2]
    ang = pos[:, None, :, None] * theta ** (-jnp.arange(0, 8, 2) / 8)
    w = z * jnp.exp(1j * ang)
    want = jnp.stack([w.real, w.imag], -1).reshape(2, 3, 5, 8)
    assert float(jnp.max(jnp.abs(got[..., 16:] - want))) < 1e-5
    shared = apply_rope_interleaved(x[..., 16:], jnp.arange(5), theta)
    assert float(jnp.max(jnp.abs(
        shared[0] - ref.rope_interleaved(x[0, :, :, 16:], theta)))) < 1e-5
    # scores between vectors rotated alike depend on the distance only
    a = apply_rope_interleaved(x[:1, :1, :1, 16:], jnp.asarray([[3]]), theta)
    b = apply_rope_interleaved(x[:1, :1, 1:2, 16:], jnp.asarray([[9]]), theta)
    a2 = apply_rope_interleaved(x[:1, :1, :1, 16:], jnp.asarray([[13]]), theta)
    b2 = apply_rope_interleaved(x[:1, :1, 1:2, 16:], jnp.asarray([[19]]),
                                theta)
    assert float(jnp.abs(jnp.sum(a * b) - jnp.sum(a2 * b2))) < 1e-4


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_every_share_of_the_router_adds_up_to_the_uncut_layer(form):
    """The share test at this router's ratio: a router 16 wide cut in
    16 / 2 = 8 shares of 2 held experts (the published 256 / 32), the
    shared expert counted once, add up to the uncut reference's layer
    output. float32: 1e-5 of outputs of size ~0.1."""
    cfg = dict(CFG, experts_held=[0, 16], n_routed_experts=16)
    full = weights.make_params(ref.layer_spec(cfg, 1)["moe"], 5, "float32")
    x = jax.random.normal(jax.random.key(6), (3, 50, 64))
    total = 0.0
    for share in range(8):
        held = (2 * share, 2)
        p = dict(full, experts={k: a[held[0]:held[0] + 2]
                                for k, a in full["experts"].items()})
        layer = HeldExperts(64, 32, 16, 2, experts_held=held,
                            shared_d_ff=32 if share == 0 else 0,
                            routed_scale=2.5,
                            dense_max_tokens=512 if form == "dense" else 0)
        total = total + layer.apply(p, x)
    uncut = ref.moe_partial(x.reshape(-1, 64), full, cfg, held=None)
    assert float(jnp.max(jnp.abs(total.reshape(-1, 64) - uncut))) < 1e-5


def test_serving_is_greedy_equal_to_the_full_forward_and_counts_latents():
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=4, t_max=128, prompt_buf=64)
    assert cb.stats_snapshot()["paged_read"] == "gather"      # on the CPU
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=m) for n, m in ((40, 16), (5, 12), (61, 9))]
    for rq, res in zip(reqs, cb.serve_detailed(reqs)):
        assert res.status == "ok" and len(res.tokens) == rq.max_new
        seq = list(rq.tokens) + list(res.tokens)
        lg, _ = model.apply(params, {}, jnp.asarray([seq[:-1]], jnp.int32))
        want = jnp.argmax(lg[0, len(rq.tokens) - 1:], -1)
        assert list(res.tokens) == [int(t) for t in want]
    snap = cb.stats_snapshot()
    st = snap["stats"]
    # the heads' tokens at admission: each prompt but its last token
    assert st["prefill_tokens"] == 39 + 4 + 60
    assert st["expert_assignments"] == 4 * 2 * snap["waste"]["planned_ticks"]
    assert snap["slot_leaks"] == snap["block_leaks"] == 0
    # a fresh session on the same programs: the latent pools re-zeroed
    cb.reset()
    assert not any(c["kv"].any() for c in cb._caches)
    again = cb.serve_detailed(reqs[:1])[0]
    assert again.status == "ok" and cb.stats["prefill_rows"] > 0


@pytest.mark.parametrize("what,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("speculate", {"speculate": 2}),
    ("host_cache", {"prefix_cache": True, "host_cache_blocks": 4}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 32}),
])
def test_what_latent_layers_cannot_be_served_with_is_refused(what, kw):
    model, params = build()
    with pytest.raises(ValueError, match="latent-attention layers"):
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                          **kw)


RINGS = "a model of window layers and held experts"


@pytest.mark.parametrize("kinds,says,silent", [
    ({"latent"}, ["latent-attention layers"], "window layers"),
    ({"ring", "paged"}, [RINGS], "latent"),
    ({"paged"}, [RINGS], "latent"),
    # every kind present gives its own reason
    ({"ring", "latent", "paged"}, [RINGS, "latent-attention layers"], None),
])
def test_a_mesh_is_refused_with_the_reason_of_every_kind_present(
        kinds, says, silent):
    with pytest.raises(ValueError) as e:
        ContinuousBatcher._refuse_for_layer_kinds(
            kinds, prefix_cache=False, speculate=None, tiers=False,
            kv_dtype="bf16", mesh=object(), prefill_chunk_tokens=None)
    for what in says:
        assert f"mesh does not compose with {what} yet" in str(e.value)
    assert silent is None or silent not in str(e.value)


def test_the_training_path_and_a_half_named_latent_layer_refuse():
    model, params = build()
    with pytest.raises(NotImplementedError):
        model.apply(params, {}, jnp.zeros((1, 4), jnp.int32), train=True)
    with pytest.raises(NotImplementedError):
        model.loss_fn(None, None)
    with pytest.raises(ValueError, match="q_lora_rank"):
        HybridConfig(layer_types=("latent_attention",),
                     mlp_layer_types=("dense",), kv_lora_rank=32)
    with pytest.raises(ValueError, match="norm_placement"):
        HybridConfig(norm_placement="both")
    assert isinstance(model, HybridLM)
