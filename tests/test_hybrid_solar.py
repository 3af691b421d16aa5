"""What the Solar-Open2 family brought to the decoder of layer kinds
(``models/hybrid.py``): a full-attention layer with an output gate and no
rotation on the paged pool (``attn_gate``), beside KDA layers whose decay
gate has no floor (``kda_gate_lower_bound`` None) and whose ``beta`` runs to
2 (``kda_allow_neg_eigval``) on a per-slot state: the first model that pairs
cache kinds ``paged`` and ``state``; each against the plain reference
``perfbench/reference/solar_open2_ref.py`` (token-by-token recurrence, dense
softmax, no cache) at a small size on the CPU, on seeded weights. Every
tolerance says why it has its value."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.hybrid import (
    HybridBlock, HybridConfig)
from distributed_compute_pytorch_tpu.models.moe import HeldExperts
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from perfbench import weights
from perfbench.family import solar_open2 as fam
from perfbench.reference import solar_open2_ref as ref

# The tiny configuration in the PUBLISHED keys: one period [full, linear,
# linear, linear], every layer sparse, over experts of which this chip holds
# 4 of 16.
CFG = {
    "family": "solar_open2", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 4,
    "gqa_layers": [0], "gqa_interval": 3, "use_rope": False,
    "use_gqa_gate": True, "kda_allow_neg_eigval": True,
    "kda_use_full_proj": False,
    "linear_attn_config": {"num_heads": 4, "head_dim": 16,
                           "short_conv_kernel_size": 4, "num_kv_heads": None},
    "kda_gate_rank": 8, "router_num_experts": 16, "n_routed_experts": 4,
    "experts_held": [0, 4], "num_experts_per_tok": 2, "n_shared_experts": 1,
    "routed_scaling_factor": 1, "norm_topk_prob": True,
    "rms_norm_eps": 1e-5, "vocab_size": 512, "serving": {"slots": 4},
}

# float32 on both sides, the same weights: what is left is the order of
# summation (the chunked recurrence's triangular solve against the
# reference's steps, the paged read against the dense softmax, fused matmuls
# against HIGHEST): a few 1e-6 on logits of size ~1. 2e-4 is what
# tests/test_hybrid_glm.py allows the same forms, leaves a decade and more of
# room and is fifty times under what bfloat16 does (the control below). The
# seeds are such that no router sits on a tie: one that does flips a whole
# expert, which is no rounding.
TOL_F32 = 2e-4


def one_layer(full: bool):
    return dict(CFG, num_hidden_layers=1, gqa_layers=[0] if full else [])


def build(dtype="float32", seed=1, cfg=CFG, t_max=128):
    model = build_model(fam.BUILD_MODEL, **fam.model_kwargs(
        cfg, {"max_seq_len": t_max, "param_dtype": dtype}))
    params = weights.make_params(ref.param_spec(cfg), seed,
                                 ref.param_dtypes(cfg, dtype))
    return model, params


def ref_logits(params, toks, cfg=CFG):
    return np.asarray(ref.forward(params, jnp.asarray(toks, jnp.int32), cfg))


def engine(model, params, **kw):
    return ContinuousBatcher(model, params, slots=4, t_max=128,
                             prompt_buf=64, **kw)


def worst(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def test_parameter_tree_is_the_references_and_the_config_says_the_forms():
    model, params = build()
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    assert have == jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    full, kda = params["layers"][0], params["layers"][1]
    assert {"q", "k", "v", "o", "gate"} <= set(full)
    assert "q_norm" not in full and "conv_q" not in full
    assert full["gate"]["kernel"].shape == (64, 4 * 16)
    assert {"conv_q", "f_down", "dt_bias", "A_log", "beta"} <= set(kda)
    assert "gate" not in kda
    c = model.config
    assert c.attn_gate and c.kda_allow_neg_eigval
    assert c.kda_gate_lower_bound is None and not c.qk_norm
    assert [model.layer_block(i).gated for i in range(4)] == [
        True, False, False, False]
    # a layer's rates run from slow to fast, whatever is served; the decay's
    # bias stays float32
    _, served = build("bfloat16")
    assert served["layers"][1]["dt_bias"].dtype == jnp.float32
    assert served["layers"][0]["gate"]["kernel"].dtype == jnp.bfloat16
    rate = np.exp(np.asarray(served["layers"][1]["A_log"]))
    assert rate[0] == pytest.approx(0.25) and rate[-1] == pytest.approx(4.0)


def test_full_forward_matches_the_reference_on_logits():
    model, params = build()
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    assert worst(got[0], ref_logits(params, toks)) < TOL_F32


@pytest.mark.parametrize("full", [True, False])
def test_each_mixer_matches_the_reference(full):
    """ONE layer of each mixer over the experts: the program's whole-window
    form (causal GQA and the gate; the chunked scan in the form that needs
    no floor, 83 tokens: a whole chunk and a part) against the reference's
    logits."""
    cfg = one_layer(full)
    model, params = build(cfg=cfg, seed=3)
    toks = np.random.default_rng(2).integers(1, 512, 83)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    assert worst(got[0], ref_logits(params, toks, cfg)) < TOL_F32


def test_the_drawn_decays_pass_what_a_floor_could_hold():
    """The gate as drawn (``assumed.kda_draw``): within one layer channels
    run from nearly no decay to far past ``e^-5.5`` a token, i.e. past
    ``e^-88`` over a sub-chunk of 16, where the bounded form's division
    overflows float32; and ``beta`` passes 1."""
    _, params = build(seed=1)
    p = params["layers"][1]
    x = jax.random.normal(jax.random.key(0), (50, 64))
    g, beta = ref.kda_gates(x, p)
    assert float(g.max()) <= 0 and float(g.min()) < -5.5
    assert float(g.max()) > -0.05
    assert 1.0 < float(beta.max()) < 2.0 and float(beta.min()) > 0.0


@pytest.mark.parametrize("prefill", [0, 11, 21, 45, 64])
def test_prefill_then_decode_through_the_batcher_matches_on_logits(prefill):
    """(a) 100 tokens through the engine's own caches, the K/V pool of the
    full layer (blocks of 8 tokens) beside the three per-slot states: a
    prefill of 0 to 64 tokens through the admission program (windows of 16
    to 64: the chunked scan hands state and tails over at the last real
    token), the rest through decode ticks (the paged read and the gate, the
    one-token step); every logit against the reference's full forward."""
    model, params = build()
    cb = engine(model, params)
    snap = cb.stats_snapshot()
    assert snap["cache_kinds"] == ["paged", "state", "state", "state"]
    assert cb._paged0 == 0
    # K and V of 2 heads of 16 in float32; a state keeps no token at all
    assert snap["cache_bytes_per_token"] == {"paged": 2 * 2 * 16 * 4,
                                             "state": 0}
    assert snap["state_bytes_per_slot"] == {
        "state": 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4}
    toks = np.random.default_rng(1).integers(1, 512, 100)
    got = cb.logit_probe(toks, prefill=prefill)
    want = ref_logits(params, toks)[prefill:]
    assert got.shape == want.shape
    assert worst(got, want) < TOL_F32


def test_a_lower_precision_control_fails_the_tolerance():
    """The same program with bfloat16 weights and activations (the state
    still float32), against the float32 reference on the float32 values of
    those weights: rounding to 8 bits of mantissa moves logits by ~1e-2;
    and so does the reference's own int8 control form."""
    model, params = build("bfloat16")
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref_logits(params, toks)
    assert worst(np.asarray(got[0], np.float32), want) > 10 * TOL_F32
    low = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG, "int8")
    assert worst(low, want) > 10 * TOL_F32


def test_serving_is_greedy_equal_to_the_full_forward_and_counts():
    """Requests of different lengths through the scheduler (admission
    ladder, hand-over, ticks, a slot reused): every served token is the
    argmax of the full forward over what came before it, and the counters
    the benchmark reads are kept: ``state_rows_advanced`` counts a tick's
    rows in the plan, so with ``decode_rows_parked`` it is every slot-tick
    dispatched."""
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=2, t_max=128, prompt_buf=64,
                           segment=4)
    rng = np.random.default_rng(3)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=m) for n, m in ((40, 9), (7, 12), (23, 6))]
    out = cb.serve_detailed(reqs)
    for r, res in zip(reqs, out):
        assert res.status == "ok" and len(res.tokens) == r.max_new
        seq = list(r.tokens) + list(res.tokens)
        logits = ref_logits(params, seq[:-1])[len(r.tokens) - 1:]
        # the served token's logit is the reference's best within the float32
        # tolerance (a bit-exact argmax could differ at a near-tie)
        best = logits.max(-1)
        got = logits[np.arange(len(res.tokens)), list(res.tokens)]
        assert float(np.max(best - got)) < TOL_F32
    st = cb.stats_snapshot()["stats"]
    assert st["state_rows_advanced"] > 0
    assert (st["state_rows_advanced"] + st["decode_rows_parked"]
            == st["segments"] * cb.S * cb.B)
    assert st["expert_assignments"] > st["expert_assignments_held"] > 0


def test_with_the_step_kernel_chosen_the_engine_serves_the_same_tokens(
        monkeypatch):
    """The one-token step as the kernel (``ops/pallas/kda_step.py``,
    interpreted) through the scheduler with slots parked: the tokens are
    the portable form's and ``state_rows_advanced`` counts the same rows."""
    from tests.test_kda_step import serve_portable_then_with_the_kernel
    (want, portable), (got, kernel), traced = (
        serve_portable_then_with_the_kernel(build, monkeypatch))
    assert traced and got == want
    assert (kernel["state_rows_advanced"] == portable["state_rows_advanced"]
            > 0)
    assert kernel["decode_rows_parked"] == portable["decode_rows_parked"] > 0


def test_a_model_without_state_layers_advances_no_state_rows():
    """The counter is of models with state layers: the tiny preset (rings
    and a pool) keeps it at 0."""
    model = build_model("hybrid", preset="tiny")
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    cb.serve([Request(tokens=[5, 9, 12], max_new=4)])
    st = cb.stats_snapshot()["stats"]
    assert st["segments"] > 0 and st["state_rows_advanced"] == 0


def test_the_gated_layers_window_and_decode_forms_agree():
    """(d) A one-layer model of the gated full layer: every position
    through decode ticks (the gate on one token a row, the paged read)
    against the whole-window form and the reference."""
    cfg = one_layer(True)
    model, params = build(cfg=cfg, seed=4)
    toks = np.random.default_rng(5).integers(1, 512, 40)
    window, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    ticks = engine(model, params).logit_probe(toks, prefill=0)
    assert worst(ticks, window[0]) < TOL_F32
    assert worst(ticks, ref_logits(params, toks, cfg)) < TOL_F32


def test_a_zeroed_gate_halves_the_ungated_layers_output(monkeypatch):
    """(d) ``sigmoid(0) = 1/2`` a channel: with the gate's matrix zeroed the
    attention sublayer adds half of what the same layer adds without a
    gate, in the program (the feed-forward taken out of the block) and in
    the reference. float32: 1e-6 of outputs of size ~0.01."""
    cfg = one_layer(True)
    _, params = build(cfg=cfg, seed=4)
    p = dict(params["layers"][0])
    p["gate"] = {"kernel": jnp.zeros_like(p["gate"]["kernel"])}
    monkeypatch.setattr(HybridBlock, "_mlp",
                        lambda self, params, x, **kw: x)
    kw = fam.model_kwargs(cfg, {"max_seq_len": 64, "param_dtype": "float32"})
    x = jax.random.normal(jax.random.key(6), (2, 30, 64))
    added = {}
    for gated in (True, False):
        block = build_model("hybrid", **dict(kw, attn_gate=gated)
                            ).layer_block(0)
        assert block.gated == gated
        added[gated] = block.apply(p, x) - x
    assert float(jnp.max(jnp.abs(added[False]))) > 1e-3
    assert worst(added[True], 0.5 * added[False]) < 1e-6
    y = ref._rms(x[0], p["pre_attn_norm"]["scale"], 1e-5)
    assert worst(ref.full_mixer(y, p, cfg),
                 0.5 * ref.full_mixer(y, p, cfg, gated=False)) < 1e-6
    assert worst(added[False][0], ref.full_mixer(y, p, cfg, gated=False)
                 ) < 1e-5


STATE = "linear-attention layers, whose state is a function of the whole prefix"


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "no snapshot at block boundaries"),
    ({"speculate": 2}, "a state cannot be rolled back"),
    ({"prefix_cache": True, "host_cache_blocks": 4}, "prefix_cache"),
    ({"kv_dtype": "int8"}, "int8"),
    ({"prefill_chunk_tokens": 32}, "prefill_chunk_tokens"),
])
def test_what_a_model_with_state_layers_cannot_be_served_with_is_refused(
        kw, reason):
    """(e) The pool beside the states changes nothing of what the states
    refuse: the engine says which layers stand in the way and why."""
    model, params = build()
    with pytest.raises(ValueError) as e:
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                          **kw)
    assert STATE in str(e.value) and reason in str(e.value)


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_every_share_of_the_router_adds_up_to_the_uncut_layer(form):
    """(b) The share test at this router's ratio: a router 16 wide cut in 8
    shares of 2 held experts (the published 320 / 40), the shared expert
    counted once, add up to the uncut reference's layer output; no clamp,
    a scaling of 1. float32: 1e-5 of outputs of size ~0.1."""
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
                            routed_scale=1.0,
                            dense_max_tokens=512 if form == "dense" else 0)
        total = total + layer.apply(p, x)
    uncut = ref.moe_partial(x.reshape(-1, 64), full, cfg, held=None)
    assert float(jnp.max(jnp.abs(uncut))) > 1e-2
    assert worst(total.reshape(-1, 64), uncut) < 1e-5


def test_the_gate_forms_the_config_refuses_and_takes():
    kw = dict(layer_types=("linear_attention",), mlp_layer_types=("dense",),
              kda_heads=2, kda_head_dim=16, kda_gate_rank=4)
    with pytest.raises(ValueError, match="kda_gate_lower_bound"):
        HybridConfig(kda_gate_lower_bound=-9.0, **kw)   # 16 x 9 > 80
    assert HybridConfig(kda_gate_lower_bound=None, **kw
                        ).kda_gate_lower_bound is None
    c = HybridConfig()
    assert (c.attn_gate, c.kda_allow_neg_eigval,
            c.kda_gate_lower_bound) == (False, False, -5.0)
