"""The two mixers and the residual that the GLM-5.3-Flash family brought to
the decoder of layer kinds (``models/hybrid.py``): KDA linear attention on a
per-slot state (mixer ``linear_attention``, cache kind ``state``), sparse
latent attention behind a learned indexer with a pooled key cache of its
own (``sparse_latent_attention``, ``latent+index``), the four-stream
hyper-connection round both sublayers, the clamped SwiGLU; each against the
plain reference ``perfbench/reference/glm5_next_ref.py`` (token-by-token
recurrence, dense masked attention, no cache) at a small size on the CPU, on
seeded weights. Every tolerance says why it has its value."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.hybrid import (
    KDA_SUB, HybridConfig, HybridLM)
from distributed_compute_pytorch_tpu.models.moe import HeldExperts
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
from perfbench import weights
from perfbench.family import glm5_next as fam
from perfbench.reference import glm5_next_ref as ref

# The tiny configuration in the PUBLISHED keys: a leading dense layer, then a
# period of one sparse latent and three KDA layers over experts of which
# this chip holds 4 of 16; a selection of 16 tokens (4 groups of 4), so that
# a context of 100 tokens is six times past it.
CFG = {
    "family": "glm5_next", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "q_lora_rank": 48, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 0, "v_head_dim": 16, "num_hidden_layers": 5,
    "layer_types": ["linear_attention", "deepseek_sparse_attention",
                    "linear_attention", "linear_attention",
                    "linear_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 4,
    "linear_attn_config": {"num_heads": 4, "head_dim": 16,
                           "short_conv_kernel_size": 4,
                           "gate_lower_bound": -5},
    "kda_gate_rank": 8, "index_n_heads": 2, "index_head_dim": 16,
    "index_topk": 16, "index_kpool": 4, "index_rope_dim": 8,
    "index_rope_theta": 10000.0, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "swiglu_limit": 10, "router_num_experts": 16,
    "n_routed_experts": 4, "experts_held": [0, 4], "num_experts_per_tok": 2,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "rms_norm_eps": 1e-5, "vocab_size": 512,
    "serving": {"slots": 4},
}

# float32 on both sides, the same weights: what is left is the order of
# summation (the chunked recurrence's triangular solve against the
# reference's steps, the absorbed read against the expanded one, fused
# matmuls against HIGHEST): a few 1e-6 on logits of size ~1. 2e-4 leaves a
# decade and more of room and is fifty times under what bfloat16 does (the
# control below). The seeds are such that no router and no selection sits on
# a tie: one that does flips a whole expert or group, which is no rounding.
TOL_F32 = 2e-4


def one_layer(mixer, mlp):
    return dict(CFG, num_hidden_layers=1, layer_types=[mixer],
                mlp_layer_types=[mlp])


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


def tick_logits(cb, toks, pos, live=None):
    """One decode tick of every slot outside the scheduler: slot ``b``
    consumes ``toks[b]`` at position ``pos[b]`` against the engine's own
    caches and tables; returns the logits ``[slots, V]``."""
    model = cb.model

    def step(params, caches, tables, tok, pos, live):
        x = model.embed(params, tok[:, None], pos[:, None])
        new = []
        for li in range(cb._n_layers):
            x, c2, _ = cb._decode_layer(li, params, x, caches[li], tables,
                                        pos, live, None, pin=False)
            new.append(c2)
        return new, model.readout(params, x)[:, -1]

    live = jnp.ones((cb.B,)) if live is None else jnp.asarray(live, float)
    cb._caches, logits = jax.jit(step)(
        cb.params, cb._caches, jnp.asarray(cb._tables),
        jnp.asarray(toks, jnp.int32), jnp.asarray(pos, jnp.int32), live)
    return np.asarray(logits)


def admit(cb, rows):
    """One admission dispatch outside the scheduler: ``rows`` ``{slot:
    tokens}``; slot ``b`` owns the blocks ``1 + b * nb ..`` (block 0 is the
    trash block). Every token given is prefilled."""
    for b in rows:
        cb._tables[b] = 1 + b * cb.nb + np.arange(cb.nb)
    longest = max(len(t) for t in rows.values())
    window = next(w for w, _ in reversed(cb._admit_ladder) if w >= longest)
    cb._dispatch_prefill([(b, list(t) + [0], 0, len(t))
                          for b, t in rows.items()], 4, window, 0)


def test_parameter_tree_is_the_references():
    model, params = build()
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    assert have == want
    kda, sparse = params["layers"][0], params["layers"][1]
    assert {"conv_q", "conv_k", "conv_v", "f_down", "f_up", "dt_bias",
            "A_log", "beta", "g_down", "g_up", "o_norm"} <= set(kda)
    assert {"idx_q", "idx_k", "idx_k_norm", "idx_w", "kv_up"} <= set(sparse)
    assert {"attn_hc", "mlp_hc"} <= set(kda) & set(sparse)
    # the state, the decays and the maps' gains stay float32 whatever is
    # served
    _, served = build("bfloat16")
    assert served["layers"][0]["dt_bias"].dtype == jnp.float32
    assert served["layers"][0]["attn_hc"]["b_res"].dtype == jnp.float32
    assert served["layers"][0]["attn_hc"]["phi_res"].dtype == jnp.bfloat16


@pytest.mark.parametrize("family", ["exaone_moe", "joyai_llm_flash", "zaya"])
def test_defaults_build_the_other_families_trees_unchanged(family):
    """A ``HybridConfig`` that names none of the new keys is what it was:
    one residual vector, no state, no indexer, an unclamped SwiGLU; and the
    parameter trees of the other families' tiny configurations equal their
    own reference specs leaf for leaf."""
    import importlib
    c = HybridConfig()
    assert (c.hc_mult, c.kda_heads, c.index_topk, c.swiglu_limit) == (
        0, 0, 0, 0.0)
    fam_mod = importlib.import_module(f"perfbench.family.{family}")
    ref_mod = importlib.import_module(f"perfbench.reference.{family}_ref")
    cfg = importlib.import_module(
        f"tests.test_hybrid_{family.split('_')[0]}").CFG
    model = build_model("hybrid", **fam_mod.model_kwargs(
        cfg, {"max_seq_len": 64, "param_dtype": "float32"}))
    have = jax.tree.map(lambda a: tuple(a.shape), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.key(0)))
    want = jax.tree.map(lambda s: s[0], ref_mod.param_spec(cfg),
                        is_leaf=weights._is_leaf)
    assert have == want
    by_slot = {b.cache_kind: [n for n, l in b.cache_leaves(
        4, 9, 32, jnp.float32).items() if not l.by_block]
               for b in map(model.layer_block, range(model.num_layers))}
    assert by_slot == {"zaya": {"paged+tail": ["tail"]},
                       "joyai_llm_flash": {"latent": []},
                       "exaone_moe": {"ring": ["kv"], "paged": []}}[family]


def test_full_forward_matches_the_reference_on_logits():
    model, params = build()
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    assert float(np.max(np.abs(np.asarray(got[0]) - ref_logits(
        params, toks)))) < TOL_F32


@pytest.mark.parametrize("mixer,mlp", [
    ("linear_attention", "dense"), ("deepseek_sparse_attention", "dense"),
    ("linear_attention", "sparse"), ("deepseek_sparse_attention", "sparse")])
def test_each_mixer_under_the_wrapper_matches_the_reference(mixer, mlp):
    """ONE layer of each mixer over each feed-forward, both wrapped in
    their hyper-connections: the program's whole-window form (the chunked
    scan; dense attention under the selection's mask, 83 tokens against a
    selection of 16) against the reference's logits."""
    cfg = one_layer(mixer, mlp)
    model, params = build(cfg=cfg, seed=3)
    toks = np.random.default_rng(2).integers(1, 512, 83)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    assert float(np.max(np.abs(np.asarray(got[0]) - ref_logits(
        params, toks, cfg)))) < TOL_F32


def test_the_wrappers_maps_are_the_references_and_doubly_stochastic():
    """``H_pre``, ``H_post`` and ``H_res`` of a block against the
    reference's (float32: 1e-5), ``H_res``'s rows and columns sum to 1
    within 1e-4 after its 20 rounds, and with the maps at their draw the
    residual map is near the identity and ``H_pre`` near ``1 / n``."""
    model, params = build()
    block, p = model.layer_block(0), params["layers"][0]
    X = jax.random.normal(jax.random.key(4), (1, 50, 4, 64))
    _, (post, res) = block._enter(p, "attn", X)
    pre_r, post_r, res_r = ref.hc_maps(X[0], p["attn_hc"], CFG)
    assert float(jnp.max(jnp.abs(post[0] - post_r))) < 1e-5
    assert float(jnp.max(jnp.abs(res[0] - res_r))) < 1e-5
    assert float(jnp.max(jnp.abs(res.sum(-1) - 1))) < 1e-4
    assert float(jnp.max(jnp.abs(res.sum(-2) - 1))) < 1e-4
    assert float(jnp.min(jnp.diagonal(res, axis1=-2, axis2=-1))) > 0.75
    assert float(jnp.max(jnp.abs(pre_r - 0.25))) < 0.1
    # what a sublayer is fed is ONE vector a token, and the merge gives the
    # four streams back
    u, hc = block._enter(p, "mlp", X)
    assert u.shape == (1, 50, 64)
    assert block._leave(p, "mlp", X, u, hc).shape == X.shape
    want = ref.hyper(X[0], p["mlp_hc"], p["pre_mlp_norm"]["scale"], CFG,
                     lambda y: y)
    assert float(jnp.max(jnp.abs(
        block._leave(p, "mlp", X, u, hc)[0] - want))) < 1e-5


def kda_inputs(T, seed=0, H=2, dk=16):
    rng = np.random.default_rng(seed)
    unit = lambda t: t / np.linalg.norm(t, axis=-1, keepdims=True)
    f = lambda *s: rng.normal(size=s).astype(np.float32)
    q, k = unit(f(1, T, H, dk)) * dk ** -0.5, unit(f(1, T, H, dk))
    g = (-5 * rng.uniform(size=(1, T, H, dk))).astype(np.float32)
    beta = rng.uniform(size=(1, T, H)).astype(np.float32)
    return q, k, f(1, T, H, dk), g, beta, f(1, H, dk, dk)


@pytest.mark.parametrize("C", [KDA_SUB, 64])
def test_the_chunked_delta_rule_equals_the_recurrence(C):
    """One chunk of ``C`` tokens (one sub-chunk, four) from a state that is
    not zero, decays down to ``e^-5`` a token (``e^-320`` over the chunk:
    the plain split of the decay would overflow float32 after 18 tokens),
    against ``C`` steps of the one-token form and against the reference's
    own scan. float32, the triangular solve's other order of sums: 2e-5 on
    outputs of size ~0.1."""
    q, k, v, g, beta, S0 = kda_inputs(C)
    o, S = A.kda_chunk(jnp.asarray(S0), q, k, v, g, beta, KDA_SUB)
    Sr, steps = jnp.asarray(S0), []
    for t in range(C):
        ot, Sr = A.kda_step(Sr, q[:, t], k[:, t], v[:, t], g[:, t],
                            beta[:, t])
        steps.append(ot)
    assert float(jnp.max(jnp.abs(o - jnp.stack(steps, 1)))) < 2e-5
    assert float(jnp.max(jnp.abs(S - Sr))) < 2e-5
    assert np.isfinite(np.asarray(o)).all()
    # from the zero state: the reference's recurrence
    o0, S1 = A.kda_chunk(jnp.zeros_like(S0), q, k, v, g, beta, KDA_SUB)
    o_ref, S_ref = ref.kda_recurrence(q[0], k[0], v[0], g[0], beta[0])
    assert float(jnp.max(jnp.abs(o0[0] - o_ref))) < 2e-5
    assert float(jnp.max(jnp.abs(S1[0] - S_ref))) < 2e-5


@pytest.mark.parametrize("T,real", [(64, 64), (128, 128), (37, 37),
                                    (100, 100), (128, 71), (64, 3),
                                    (96, 0)])
@pytest.mark.parametrize("path", ["scan", "kernel"])
def test_the_kda_window_form_equals_its_token_form_and_hands_over(
        T, real, path, monkeypatch):
    """ONE KDA layer: its whole-window ``apply`` (chunks of 64 tokens;
    windows that are and are not whole chunks, and windows padded past
    their last real token) against ``decode_step`` token by token; and
    what it hands the slot (the state after the row's LAST REAL token, the
    projections of its last three real tokens) against what the ticks
    arrive at. float32: 2e-5 on activations of size ~1. Both executions of
    the recurrence: the scan of chunks, which the CPU takes, and the
    kernel a TPU takes (``ops/pallas/kda_scan.py``, interpreted here)."""
    monkeypatch.setattr(A, "_kda_kernel_ok", lambda dk: path == "kernel")
    cfg = one_layer("linear_attention", "dense")
    model, params = build(cfg=cfg)
    block, p = model.layer_block(0), params["layers"][0]
    x = jax.random.normal(jax.random.key(3), (1, T, 4, 64))
    mask = (jnp.arange(T) < real)[None].astype(jnp.float32)
    sink: list = []
    want = block.apply(p, x, kv_mask=mask, kv_sink=sink)
    state, tail = sink[0]["state"], sink[0]["tail"]
    cache = {k: jnp.zeros(l.shape, l.dtype) for k, l in block.cache_leaves(
        1, 0, 32, jnp.float32).items()}
    assert list(sink[0]) == list(cache)
    assert state.shape == cache["state"].shape == (1, 4, 16, 16)
    assert tail.shape == cache["tail"].shape == (1, 3 * 3 * 64)
    step = jax.jit(block.decode_step)
    for t in range(real):
        y, cache = step(p, x[:, t:t + 1], cache, jnp.asarray([t]))
        assert float(jnp.max(jnp.abs(y - want[:, t:t + 1]))) < 2e-5, t
    assert float(jnp.max(jnp.abs(cache["state"] - state))) < 2e-5
    # the tail is the projections themselves, from a product of another
    # row count: the last bit of float32
    assert float(jnp.max(jnp.abs(cache["tail"] - tail))) < 1e-6
    if real < 3:
        assert not tail.reshape(3, -1)[:3 - real].any()


def sparse_pair(index_topk):
    """A sparse latent layer and a dense latent layer (no rotary key) on
    the SAME weights."""
    cfg = dict(one_layer("deepseek_sparse_attention", "dense"),
               index_topk=index_topk, hc_mult=4)
    model, params = build(cfg=cfg, seed=2)
    kw = fam.model_kwargs(cfg, {"max_seq_len": 128,
                                "param_dtype": "float32"})
    dense = build_model("hybrid", **dict(
        kw, layer_types=("latent_attention",)))
    return (model.layer_block(0), dense.layer_block(0),
            params["layers"][0], model)


def test_within_its_selection_the_sparse_layer_is_the_dense_latent_layer():
    """Complete groups <= chosen groups (a selection of 128 tokens, 100
    tokens of context): every earlier token is chosen, so the layer IS
    dense NoPE latent attention on the same weights. The cached vectors
    come out of the one program both layers share (``_latent_token``) bit
    for bit; the window forms (attention under an all-true mask against
    the causal kernel's path) and the tick forms (the gathered chosen
    tokens against the whole gathered view) differ in the order of their
    sums only: float32, 2e-5 on activations of size ~1."""
    sparse, dense, p, model = sparse_pair(128)
    assert dense.cache_kind == "latent" and sparse.cache_kind == "latent+index"
    T, bt = 100, 32
    x = jax.random.normal(jax.random.key(5), (1, T, 4, 64))
    s_sink, d_sink = [], []
    ys = sparse.apply(p, x, kv_sink=s_sink)
    yd = dense.apply(p, x, kv_sink=d_sink)
    np.testing.assert_array_equal(np.asarray(s_sink[0]["kv"]),
                                  np.asarray(d_sink[0]["kv"]))
    assert float(jnp.max(jnp.abs(ys - yd))) < 2e-5
    nb = -(-T // bt)
    table = (jnp.arange(nb, dtype=jnp.int32) + 1)[None]
    pool = lambda: jnp.zeros((1, nb + 1, 1, bt, 128))
    cs = {**{k: jnp.zeros(l.shape, l.dtype) for k, l in sparse.cache_leaves(
        1, nb + 1, bt, jnp.float32).items()}, "table": table}
    assert {k: a.shape for k, a in cs.items()} == {
        "kv": (1, nb + 1, 1, bt, 128), "idx": (1, nb + 1, 1, bt // 4, 16),
        "idx_tail": (1, 3 * 16), "table": (1, nb)}
    cd = {"kv": pool(), "table": table}
    s_step, d_step = jax.jit(sparse.decode_step), jax.jit(dense.decode_step)
    counts: list = []
    for t in range(T):
        a, cs = s_step(p, x[:, t:t + 1], cs, jnp.asarray([t]))
        b, cd = d_step(p, x[:, t:t + 1], cd, jnp.asarray([t]))
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5, t
        assert float(jnp.max(jnp.abs(a - ys[:, t:t + 1]))) < 2e-5, t
    # both wrote the pool through the one write: the same bytes
    np.testing.assert_array_equal(np.asarray(cs["kv"]), np.asarray(cd["kv"]))
    sparse.decode_step(p, x[:, :1], cs, jnp.asarray([T - 1]),
                       select_sink=counts)
    assert [int(c) for c in counts[0]] == [T, T]     # all of it attended


def test_past_its_selection_the_sparse_layer_attends_what_it_chose():
    """A selection of 16 tokens, 100 of context: the window form and the
    tick form agree with each other and with the reference's masked
    attention, a tick attends ``16 + tail`` tokens and no more, and the
    dense layer on the same weights gives something else."""
    sparse, dense, p, model = sparse_pair(16)
    cfg = dict(one_layer("deepseek_sparse_attention", "dense"))
    T, bt = 100, 32
    x = jax.random.normal(jax.random.key(5), (1, T, 4, 64))
    sink: list = []
    ys = sparse.apply(p, x, kv_sink=sink)
    assert list(sink[0]) == ["kv", "idx", "idx_tail"]
    pooled, idx_tail = sink[0]["idx"][0, :, 0], sink[0]["idx_tail"]
    assert sink[0]["kv"].shape == (1, 1, 1, T, 128)  # 64 channels in a tile
    assert pooled.shape == (1, 25, 16) and idx_tail.shape == (1, 3 * 16)
    assert not idx_tail.any()                  # 100 tokens: whole groups
    assert float(jnp.max(jnp.abs(ys - dense.apply(p, x)))) > 1e-2
    # the mixer's half of the block against the reference's
    want = ref.hyper(x[0], p["attn_hc"], p["pre_attn_norm"]["scale"], cfg,
                     lambda y: ref.sparse_mixer(y, p, cfg))
    y, hc = sparse._enter(p, "attn", x)
    got = sparse._attn_out(p, x, sparse._sparse_prefill(
        p, y, jnp.arange(T), None, None), hc)
    assert float(jnp.max(jnp.abs(got[0] - want))) < 2e-5
    nb = -(-T // bt)
    cs = {"kv": jnp.zeros((1, nb + 1, 1, bt, 128)),
          "idx": jnp.zeros((1, nb + 1, 1, bt // 4, 16)),
          "idx_tail": jnp.zeros((1, 3 * 16)),
          "table": (jnp.arange(nb, dtype=jnp.int32) + 1)[None]}
    step = jax.jit(lambda p, x, c, pos: (lambda s: sparse.decode_step(
        p, x, c, pos, select_sink=s) + (s[0],))([]))
    for t in range(T):
        a, cs, (attended, context) = step(p, x[:, t:t + 1], cs,
                                          jnp.asarray([t]))
        assert float(jnp.max(jnp.abs(a - ys[:, t:t + 1]))) < 2e-5, t
        assert int(context) == t + 1
        assert int(attended) == 4 * min(4, t // 4) + t % 4 + 1
    # the pooled keys the ticks wrote are the window form's (means of four
    # keys in another order of sums)
    held = cs["idx"][0, 1:, 0].reshape(-1, 16)[:25]
    assert float(jnp.max(jnp.abs(held - pooled[0]))) < 1e-6


def test_topk_mask_is_top_k_with_ties_to_the_lower_index():
    score = jnp.asarray([[3., 1., 3., 3., 0., 1.],
                         [-jnp.inf, 2., -jnp.inf, 2., 2., -jnp.inf]])
    got = np.asarray(A.topk_mask(score, 2))
    assert got.tolist() == [[True, False, True, False, False, False],
                            [False, True, False, True, False, False]]
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.integers(0, 6, (50, 40)), jnp.float32)
    _, idx = jax.lax.top_k(s, 7)
    want = np.zeros((50, 40), bool)
    want[np.arange(50)[:, None], np.asarray(idx)] = True
    np.testing.assert_array_equal(np.asarray(A.topk_mask(s, 7)), want)
    assert np.asarray(A.topk_mask(s, 99)).all()          # k past the row


@pytest.mark.parametrize("prefill", [0, 11, 21, 45, 64])
def test_prefill_then_decode_through_the_batcher_matches_on_logits(prefill):
    """100 tokens over four pool blocks of 32, the selection 16 tokens: a
    prefill of 11 is under it, of 21 and 45 past it (admission windows of
    32 and 64), the rest through decode ticks (the one-step recurrence, the
    gathered read of the chosen tokens); every logit against the
    reference's full forward."""
    model, params = build()
    cb = engine(model, params)
    assert cb.bt == 32 and cb.nb == 4
    snap = cb.stats_snapshot()
    assert snap["cache_kinds"] == ["state", "latent+index"] + ["state"] * 3
    assert snap["paged_read"] == "selected" and cb._paged0 == 1
    # 32 channels of float32 in one 128-lane tile, and a quarter of a
    # pooled key of 16; a state keeps no token at all
    assert snap["cache_bytes_per_token"] == {
        "state": 0, "latent+index": 128 * 4 + 16 * 4 // 4}
    assert snap["state_bytes_per_slot"] == {
        "state": 4 * 16 * 16 * 4 + 3 * 3 * 64 * 4, "latent+index": 3 * 16 * 4}
    toks = np.random.default_rng(1).integers(1, 512, 100)
    got = cb.logit_probe(toks, prefill=prefill)
    want = ref_logits(params, toks)[prefill:]
    assert got.shape == want.shape
    assert float(np.max(np.abs(got - want))) < TOL_F32


def test_a_lower_precision_control_fails_the_tolerance():
    """The same program with bfloat16 weights and activations (the state
    still float32), against the float32 reference on the float32 values of
    those weights: rounding to 8 bits of mantissa moves logits by ~1e-2;
    and so does the reference's own int8 control form."""
    model, params = build("bfloat16")
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref_logits(params, toks)
    assert np.max(np.abs(np.asarray(got[0], np.float32) - want)) > 10 * TOL_F32
    low = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG, "int8")
    assert float(np.max(np.abs(np.asarray(low) - want))) > 10 * TOL_F32


@pytest.mark.parametrize("path", ["scan", "kernel"])
def test_rows_of_different_lengths_in_one_wave_hand_their_own_state_over(
        path, monkeypatch):
    """One admission dispatch of three rows (40, 9 and 1 tokens, and a pad
    row) into slots 0, 2 and 3, then ticks: each row goes on from ITS last
    real token (state, both tails, pooled keys), against the reference;
    with the KDA layers' recurrence as the scan of chunks and as the
    kernel."""
    monkeypatch.setattr(A, "_kda_kernel_ok", lambda dk: path == "kernel")
    model, params = build()
    cb = engine(model, params)
    rng = np.random.default_rng(4)
    seqs = {0: rng.integers(1, 512, 44), 2: rng.integers(1, 512, 13),
            3: rng.integers(1, 512, 5)}
    heads = {0: 40, 2: 9, 3: 1}
    admit(cb, {b: seqs[b][:heads[b]] for b in seqs})
    for t in range(4):
        toks, pos = np.zeros(4, int), np.zeros(4, int)
        for b in seqs:
            toks[b], pos[b] = seqs[b][heads[b] + t], heads[b] + t
        got = tick_logits(cb, toks, pos, live=[1, 0, 1, 1])
        for b in seqs:
            want = ref_logits(params, seqs[b])[heads[b] + t]
            assert float(np.max(np.abs(got[b] - want))) < TOL_F32, (b, t)
    # the slot no row filled holds nothing
    assert not any(leaf[1].any() for c in cb._caches
                   for name, leaf in c.items()
                   if name in ("state", "tail", "idx_tail"))


@pytest.mark.parametrize("second", [19, 1, 0])
def test_a_reused_slot_gives_what_a_fresh_engine_gives(second):
    """Slot 1 serves 25 tokens of one request, then is admitted a second
    one (of 19 tokens, of one, of none: a row that prefills nothing still
    has its state and tails written, as zero): the logits of the second
    request's next tokens are bit for bit those of an engine that never
    held the first, and the reference's."""
    model, params = build()
    rng = np.random.default_rng(5)
    first, nxt = rng.integers(1, 512, 30), rng.integers(1, 512, second + 4)

    def serve_second(cb):
        admit(cb, {1: nxt[:second]})
        out = []
        for t in range(4):
            toks, pos = np.zeros(4, int), np.zeros(4, int)
            toks[1], pos[1] = nxt[second + t], second + t
            out.append(tick_logits(cb, toks, pos, live=[0, 1, 0, 0])[1])
        return np.stack(out)

    used = engine(model, params)
    admit(used, {1: first[:20]})
    for t in range(5):
        toks, pos = np.zeros(4, int), np.zeros(4, int)
        toks[1], pos[1] = first[20 + t], 20 + t
        tick_logits(used, toks, pos, live=[0, 1, 0, 0])
    assert all(c[name][1].any() for c in used._caches
               for name in ("state", "tail", "idx_tail") if name in c)
    got, want = serve_second(used), serve_second(engine(model, params))
    assert (got == want).all()
    assert float(np.max(np.abs(
        want - ref_logits(params, nxt)[second:]))) < TOL_F32


def test_a_parked_rows_state_and_tails_do_not_advance():
    model, params = build()
    cb = engine(model, params)
    rng = np.random.default_rng(6)
    seqs = {0: rng.integers(1, 512, 10), 1: rng.integers(1, 512, 12)}
    admit(cb, {0: seqs[0][:9], 1: seqs[1][:9]})
    slot = lambda: [{n: np.asarray(c[n]) for n in c
                     if n in ("state", "tail", "idx_tail")}
                    for c in cb._caches]
    before = slot()
    tick_logits(cb, [seqs[0][9], 77, 0, 0], [9, 9, 0, 0], live=[1, 0, 0, 0])
    for now, old in zip(slot(), before):
        for name in now:
            assert (now[name][1:] == old[name][1:]).all(), name   # parked
            assert (now[name][0] != old[name][0]).any(), name     # advanced
    # and the parked row goes on from where it was, as if never ticked
    got = tick_logits(cb, [0, seqs[1][9], 0, 0], [0, 9, 0, 0],
                      live=[0, 1, 0, 0])[1]
    assert float(np.max(np.abs(
        got - ref_logits(params, seqs[1][:10])[9]))) < TOL_F32


def test_serving_is_greedy_equal_to_the_full_forward_and_counts():
    """Seven requests through four slots (so slots are reused under the
    scheduler), prompts from one token to 61: every stream is the argmax
    of the program's own whole forward; the selection's two counters add up
    to what the rows' positions say; a fresh session re-zeroes the new
    leaves."""
    model, params = build()
    cb = engine(model, params)
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=m)
            for n, m in ((40, 16), (5, 12), (61, 9), (1, 7), (33, 16),
                         (2, 5), (50, 8))]
    attended = context = 0
    for rq, res in zip(reqs, cb.serve_detailed(reqs)):
        assert res.status == "ok" and len(res.tokens) == rq.max_new
        seq = list(rq.tokens) + list(res.tokens)
        lg, _ = model.apply(params, {}, jnp.asarray([seq[:-1]], jnp.int32))
        want = jnp.argmax(lg[0, len(rq.tokens) - 1:], -1)
        assert list(res.tokens) == [int(t) for t in want]
    snap = cb.stats_snapshot()
    st = snap["stats"]
    # every tick of a row in the plan is counted, the planned ones past a
    # request's end too: the counters bound what the streams needed
    for rq in reqs:
        for t in range(len(rq.tokens) - 1, len(rq.tokens) - 1 + rq.max_new):
            attended += 4 * min(4, t // 4) + t % 4 + 1
            context += t + 1
    assert st["sparse_tokens_attended"] >= attended
    assert st["sparse_tokens_in_context"] >= context
    assert st["sparse_tokens_attended"] < 0.6 * st["sparse_tokens_in_context"]
    assert st["expert_assignments"] == 4 * 2 * snap["waste"]["planned_ticks"]
    assert snap["slot_leaks"] == snap["block_leaks"] == 0
    assert any(c[n].any() for c in cb._caches for n in c)
    cb.reset()
    assert not any(c[n].any() for c in cb._caches for n in c)
    again = cb.serve_detailed(reqs[:1])[0]
    assert again.status == "ok" and cb.stats["prefill_rows"] > 0


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


def test_with_the_experts_kernel_chosen_the_engine_serves_the_same_tokens(
        monkeypatch):
    """The held experts' decode form as the kernel over the chosen experts
    (``ops/pallas/held_experts.py``, interpreted) through the scheduler with
    slots parked (four sparse layers of top-2 of 16, four held, the clamp,
    the shared expert): the tokens are the dense form's, so are the
    experts' counts, and the chosen experts are a share of the held ones."""
    from tests.test_held_experts_kernel import serve_dense_then_chosen
    (want, dense), (got, chosen), traced = serve_dense_then_chosen(
        build, monkeypatch)
    assert traced and got == want
    keys = [k for k in dense if k.startswith("expert")]
    assert "experts_chosen" in keys and "expert_load_0" in keys
    assert {k: chosen[k] for k in keys} == {k: dense[k] for k in keys}
    assert 0 < chosen["experts_chosen"] < chosen["experts_held_ticks"]
    assert chosen["decode_rows_parked"] == dense["decode_rows_parked"] > 0


def test_a_reconstruction_rebuilds_state_tails_and_pooled_keys():
    """A device fault mid-stream: every leaf is zeroed and the rows are
    re-prefilled from their tokens (state, tails and pooled keys with
    them); the streams are what an engine without a fault serves."""
    model, params = build()
    rng = np.random.default_rng(3)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=40) for n in (30, 7)]
    clean = engine(model, params).serve_detailed(reqs)
    cb = engine(model, params)
    faulted = cb.serve_detailed(
        reqs, chaos=ChaosInjector(fault_at_segment=1, fault_mode="raise"))
    assert cb.stats["reconstructions"] == 1
    for a, b in zip(clean, faulted):
        assert b.status == "ok" and list(a.tokens) == list(b.tokens)


STATE = "linear-attention layers, whose state is a function of the whole prefix"
INDEX = "sparse latent layers behind an indexer"


@pytest.mark.parametrize("what,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("speculate", {"speculate": 2}),
    ("host_cache", {"prefix_cache": True, "host_cache_blocks": 4}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 32}),
])
def test_what_the_new_kinds_cannot_be_served_with_is_refused(what, kw):
    model, params = build()
    with pytest.raises(ValueError) as e:
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                          **kw)
    assert STATE in str(e.value) and INDEX in str(e.value)


@pytest.mark.parametrize("kinds,says,silent", [
    ({"state"}, [STATE], "indexer"),
    ({"latent+index"}, [INDEX], "linear-attention"),
    ({"state", "latent+index"}, [STATE, INDEX], "window layers"),
])
def test_a_mesh_is_refused_with_the_reason_of_every_kind_present(
        kinds, says, silent):
    with pytest.raises(ValueError) as e:
        ContinuousBatcher._refuse_for_layer_kinds(
            kinds, prefix_cache=False, speculate=None, tiers=False,
            kv_dtype="bf16", mesh=object(), prefill_chunk_tokens=None)
    for what in says:
        assert f"mesh does not compose with {what} yet" in str(e.value)
    assert silent not in str(e.value)


def test_the_refusal_matrix_is_five_kinds_by_six_features():
    table = ContinuousBatcher._LAYER_KIND_REFUSALS
    assert list(table) == ["ring", "latent", "paged+tail", "state",
                           "latent+index"]
    features = set(table["ring"][1])
    assert len(features) == 6
    assert all(set(why) == features for _, why in table.values())


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_every_share_of_the_router_adds_up_to_the_uncut_layer(form):
    """The share test at this router's ratio: a router 16 wide cut in 8
    shares of 2 held experts (the published 288 / 36), the shared expert
    counted once, add up to the uncut reference's layer output, every
    SwiGLU clamped. float32: 1e-5 of outputs of size ~0.1."""
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
                            routed_scale=2.5, swiglu_limit=10.0,
                            dense_max_tokens=512 if form == "dense" else 0)
        total = total + layer.apply(p, x)
    uncut = ref.moe_partial(x.reshape(-1, 64), full, cfg, held=None)
    assert float(jnp.max(jnp.abs(total.reshape(-1, 64) - uncut))) < 1e-5


def test_the_clamp_of_the_swiglu_binds_where_it_should():
    """Inputs large enough that gates and ups pass +-10: the clamped layer
    follows the reference's clamped SwiGLU and not the unclamped one."""
    cfg = dict(CFG, experts_held=[0, 16], n_routed_experts=16)
    p = weights.make_params(ref.layer_spec(cfg, 1)["moe"], 5, "float32")
    p = jax.tree.map(lambda a: a * 40.0 if a.ndim == 3 else a, p)
    x = 8.0 * jax.random.normal(jax.random.key(7), (40, 64))
    kw = dict(experts_held=(0, 16), shared_d_ff=32, routed_scale=2.5)
    clamped = HeldExperts(64, 32, 16, 2, swiglu_limit=10.0, **kw).apply(p, x)
    free = HeldExperts(64, 32, 16, 2, **kw).apply(p, x)
    want = ref.moe_partial(x, p, cfg, held=(0, 16))
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(clamped - want))) < 1e-4 * scale
    assert float(jnp.max(jnp.abs(free - want))) > 0.1 * scale


def test_half_named_layers_and_the_training_path_refuse():
    model, params = build()
    assert isinstance(model, HybridLM)
    with pytest.raises(NotImplementedError):
        model.apply(params, {}, jnp.zeros((1, 4), jnp.int32), train=True)
    kw = dict(mlp_layer_types=("dense",))
    with pytest.raises(ValueError, match="kda_heads"):
        HybridConfig(layer_types=("linear_attention",), **kw)
    with pytest.raises(ValueError, match="kda_gate_lower_bound"):
        HybridConfig(layer_types=("linear_attention",), kda_heads=2,
                     kda_head_dim=16, kda_gate_rank=4,
                     kda_gate_lower_bound=-9.0, **kw)
    with pytest.raises(ValueError, match="index_heads"):
        HybridConfig(layer_types=("sparse_latent_attention",),
                     q_lora_rank=8, kv_lora_rank=8, qk_nope_head_dim=8,
                     v_head_dim=8, **kw)
    with pytest.raises(ValueError, match="hc_mult"):
        HybridConfig(hc_mult=4)               # norms after the sublayers
    with pytest.raises(ValueError, match="whole groups"):
        model.layer_block(1).cache_leaves(4, 8, 6, jnp.float32)
