"""What the SDAR family brought (``models/hybrid.py``, ``models/moe.py``,
``ops/``, ``serve.py``): full-attention layers under the BLOCK mask over
experts behind a softmax router, and generation by block diffusion: a pass
of the served path takes a block of ``block_length`` positions a row and
yields none to ``block_length`` tokens (the step contract:
``serve.py::ContinuousBatcher._row_passes``). Each against the plain
reference ``perfbench/reference/sdar_moe_ref.py`` (one whole sequence under
the mask, no cache, its own generation loop) at a small size on the CPU, on
seeded weights. Every tolerance says why it has its value."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu import infer
from distributed_compute_pytorch_tpu.models.hybrid import HybridConfig
from distributed_compute_pytorch_tpu.models.moe import (
    HeldExperts, SoftmaxRouter)
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.pallas import (
    cache_update, decode_attention)
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from distributed_compute_pytorch_tpu.serve_lifecycle import ChaosInjector
from perfbench import weights
from perfbench.family import sdar_moe as fam
from perfbench.reference import sdar_moe_ref as ref

MASK_ID = 200
# The tiny configuration in the PUBLISHED keys. initializer_range 0.3: at 64
# channels the drawn model's output then hangs on its context, and a pass
# that read the wrong
# keys or unmasked the wrong position would serve other tokens (at 0.02 a
# masked position's logits are the mask token's own whatever stands round it).
CFG = {
    "family": "sdar_moe", "hidden_size": 64, "intermediate_size": 128,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "num_experts": 8, "experts_held": [0, 8], "num_experts_per_tok": 2,
    "norm_topk_prob": True, "rms_norm_eps": 1e-6, "rope_theta": 1000000,
    "vocab_size": 256, "initializer_range": 0.3,
    "generation": {"block_length": 4, "denoising_steps": 2,
                   "remasking": "sequential", "mask_token_id": MASK_ID},
}

# float32 on both sides, the same weights: what is left is the order of
# summation (the paged read against the dense softmax, fused matmuls against
# HIGHEST): a few 1e-6 on logits of size ~3. 2e-4 is what
# tests/test_hybrid_solar.py allows the same forms. The seeds are such that
# no router sits on a tie.
TOL_F32 = 2e-4


def cfg_with(**generation):
    return dict(CFG, generation=dict(CFG["generation"], **generation))


def build(cfg=CFG, dtype="float32", seed=3, t_max=64):
    model = build_model(fam.BUILD_MODEL, **fam.model_kwargs(
        cfg, {"max_seq_len": t_max, "param_dtype": dtype}))
    params = weights.make_params(ref.param_spec(cfg), seed,
                                 ref.param_dtypes(cfg, dtype))
    return model, params


def worst(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


def prompts(rng, lengths, with_mask_id_at=None):
    out = [[int(t) for t in rng.integers(0, 256, size=n)] for n in lengths]
    if with_mask_id_at is not None:
        out[with_mask_id_at][3] = MASK_ID
    return out


def test_parameter_tree_is_the_references_and_the_model_says_how_it_generates():
    model, _ = build()
    have = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    want = jax.tree.map(lambda s, d: (s[0], d), ref.param_spec(CFG),
                        ref.param_dtypes(CFG, "float32"),
                        is_leaf=weights._is_leaf)
    assert have == want
    assert "router_bias" not in have["layers"][0]["moe"]
    assert model.block_generation == (4, 2, "sequential", MASK_ID)
    assert build_model("hybrid", preset="tiny").block_generation is None
    assert isinstance(model.layer_block(0).experts()._router(), SoftmaxRouter)


def test_apply_under_the_block_mask_matches_the_reference_on_logits():
    """The whole forward, 6 blocks: a position sees every earlier block and
    all of its own. A causal forward of the same weights is far off."""
    model, params = build()
    toks = np.random.default_rng(0).integers(0, 256, size=24)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, {}, jnp.asarray(toks)[None])
        kw = fam.model_kwargs(CFG, {"max_seq_len": 64,
                                    "param_dtype": "float32"})
        plain = build_model("hybrid", **dict(kw, block_length=0))
        off, _ = plain.apply(params, {}, jnp.asarray(toks)[None])
    want = ref.forward(params, toks, CFG)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert worst(got[0], want) < TOL_F32
    assert worst(off[0], want) > 0.1


@pytest.mark.parametrize("n_prompt", [8, 9, 10, 11, 3])
def test_prefill_then_block_passes_through_the_cache_match_on_logits(
        n_prompt):
    """Admission writes the prompt's whole blocks; then passes over two
    blocks through the pool, each against the reference's forward of the
    SAME partly masked sequence (logits, not tokens): a pass whose block is
    half masked, the next over the same slots (its K/V overwritten), the
    commit pass, and a block that reads the committed one."""
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=16)
    L, bt = 4, cb.bt
    rng = np.random.default_rng(n_prompt)
    seq = [int(t) for t in rng.integers(0, 256, size=n_prompt + 8)]
    start = n_prompt // L * L
    nbp = 64 // bt
    scratch = [{name: jnp.zeros(l.shape, l.dtype)
                for name, l in leaves.items()}
               for leaves in cb._declared(1, nbp)]
    table = jnp.arange(nbp, dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        if start:
            W = -(-start // bt) * bt
            at = np.arange(W)
            real = at < start
            scratch = jax.jit(cb._admit_impl)(
                params, scratch, table,
                jnp.asarray([seq[:start] + [0] * (W - start)], jnp.int32),
                jnp.asarray(real[None], jnp.float32),
                jnp.asarray(at[None], jnp.int32),
                jnp.zeros((1, 0), jnp.float32),
                jnp.asarray(np.where(real, at // bt, nbp)[None], jnp.int32),
                jnp.asarray((at % bt)[None], jnp.int32))

        @jax.jit
        def one_pass(caches, toks, pos0):
            x = model.embed(params, toks)
            out = []
            for i in range(model.num_layers):
                x, c = model.layer_block(i).block_step(
                    model.layer_params(params, i), x,
                    {**caches[i], "table": table}, pos0)
                out.append({n: c[n] for n in caches[i]})
            return out, model.readout(params, x)[0]

        for blk_start in (start, start + L):
            known = max(n_prompt - blk_start, 0)
            for n_known in (known, min(known + 2, L), L):
                masked = np.arange(L) >= n_known
                block = np.where(masked, MASK_ID, seq[blk_start:blk_start + L])
                scratch, got = one_pass(
                    scratch, jnp.asarray(block[None], jnp.int32),
                    jnp.asarray([blk_start], jnp.int32))
                want = ref.forward(
                    params, np.concatenate([seq[:blk_start], block]),
                    CFG)[blk_start:]
                assert worst(got, want) < TOL_F32, (blk_start, n_known)


def test_a_lower_precision_control_fails_the_tolerance():
    """The same comparison with the reference in int8 operands is two
    decades over the tolerance: the comparison would catch it."""
    _, params = build()
    toks = np.random.default_rng(0).integers(0, 256, size=24)
    assert worst(ref.forward(params, toks, CFG, "int8"),
                 ref.forward(params, toks, CFG)) > 100 * TOL_F32


@pytest.mark.parametrize("steps", [1, 2, 4])
@pytest.mark.parametrize("rule", ["sequential", "low_confidence_static"])
def test_served_tokens_are_the_references_loop(rule, steps):
    """Staggered admissions into 3 slots, prompts of every remainder mod 4
    (one shorter than a block, one holding the mask token's id), ``max_new``
    not a multiple of 4: every request is served exactly ``max_new`` tokens,
    those of the reference's own loop; the counters add up."""
    cfg = cfg_with(denoising_steps=steps, remasking=rule)
    model, params = build(cfg)
    shapes = [(5, 7), (8, 9), (3, 4), (10, 6), (6, 13), (1, 5), (7, 2),
              (12, 1)]
    toks = prompts(np.random.default_rng(1), [n for n, _ in shapes], 3)
    with jax.default_matmul_precision("highest"):
        cb = ContinuousBatcher(model, params, slots=3, t_max=64,
                               prompt_buf=16, segment=4)
        res = cb.serve_detailed([Request(tokens=t, max_new=m)
                                 for t, (_, m) in zip(toks, shapes)])
    for t, (_, m), r in zip(toks, shapes, res):
        assert r.status == "ok" and len(r.tokens) == m
        assert r.tokens == ref.generate(params, t, m, cfg, pad_to=16)
    st = cb.stats
    assert (cb.last_slot_leaks, cb.last_block_leaks) == (0, 0)
    assert st["block_passes"] == 4 * st["segments"]
    assert st["block_row_passes"] == (st["denoise_row_passes"]
                                      + st["commit_row_passes"])
    blocks = sum(-(-(n + m) // 4) - n // 4 for n, m in shapes)
    assert st["commit_row_passes"] == st["blocks_committed"] == blocks == 18
    assert st["prompt_tail_tokens"] == sum(n % 4 for n, _ in shapes)
    # every generated position is delivered or cut
    assert st["block_tokens_cut"] == (
        4 * blocks - st["prompt_tail_tokens"] - sum(m for _, m in shapes))
    assert sum(r.ticks for r in res) == sum(m for _, m in shapes)
    snap = cb.stats_snapshot()
    assert snap["paged_read"] == "gather" and snap["cache_kinds"] == [
        "paged", "paged"]


def test_a_fault_rebuilds_the_rows_from_prompt_and_delivered_blocks():
    """A device fault mid-stream: the rows are re-admitted as prompt +
    delivered tokens (whole blocks) and the streams are the uninterrupted
    ones."""
    model, params = build()
    toks = prompts(np.random.default_rng(2), [5, 8, 3])
    reqs = [Request(tokens=t, max_new=m) for t, m in zip(toks, (11, 9, 14))]
    with jax.default_matmul_precision("highest"):
        cb = ContinuousBatcher(model, params, slots=3, t_max=64,
                               prompt_buf=32, segment=4)
        plain = [r.tokens for r in cb.serve_detailed(reqs)]
        cb.reset()
        res = cb.serve_detailed(reqs, chaos=ChaosInjector(
            fault_at_segment=2, fault_mode="raise"))
    assert cb.stats["reconstructions"] == 1
    assert [r.tokens for r in res] == plain
    assert all(r.status == "ok" for r in res)
    assert (cb.last_slot_leaks, cb.last_block_leaks) == (0, 0)


def test_an_eos_ends_a_request_at_its_blocks_delivery():
    model, params = build()
    t = prompts(np.random.default_rng(1), [5])[0]
    with jax.default_matmul_precision("highest"):
        full = ContinuousBatcher(model, params, slots=2, t_max=64,
                                 prompt_buf=16, segment=4).serve(
            [Request(tokens=t, max_new=12)])[0]
        eos = full[4]
        cut = ContinuousBatcher(model, params, slots=2, t_max=64,
                                prompt_buf=16, segment=4, eos_id=eos).serve(
            [Request(tokens=t, max_new=12)])[0]
    assert cut == full[:full.index(eos) + 1]


@pytest.mark.parametrize("kw,reason", [
    ({"prefix_cache": True}, "end on a block boundary"),
    ({"speculate": 2}, "a verify window scores causal drafts"),
    ({"kv_dtype": "int8"}, "no quantized form"),
    ({"prefill_chunk_tokens": 8}, "the chunk path is causal"),
])
def test_what_a_block_model_is_not_served_with_is_refused(kw, reason):
    model, params = build()
    with pytest.raises(ValueError) as e:
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=16,
                          **kw)
    assert reason in str(e.value)


def test_the_dynamic_rule_sampled_rows_a_mesh_and_the_tick_loop_are_refused():
    model, params = build(cfg_with(remasking="low_confidence_dynamic"))
    with pytest.raises(ValueError, match="yield depend on the data"):
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=16)
    model, params = build(cfg_with(block_length=16))
    with pytest.raises(ValueError, match="never straddles two pool blocks"):
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=16,
                          kv_block_tokens=8)
    model, params = build()
    with pytest.raises(ValueError, match="single-device kernels"):
        ContinuousBatcher._refuse_for_block_generation(
            "sequential", prefix_cache=False, speculate=None,
            kv_dtype="bf16", mesh=object(), prefill_chunk_tokens=None)
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=16)
    res = cb.serve_detailed([Request(tokens=[1, 2, 3], max_new=3,
                                     temperature=0.7, seed=1)])
    assert res[0].status == "failed"
    assert "sampled rows are not served" in res[0].error
    with pytest.raises(NotImplementedError, match="no such tick"):
        cb.logit_probe([1, 2, 3])
    with pytest.raises(ValueError, match="generates by block diffusion"):
        infer.generate(model, params, jnp.asarray([[1, 2, 3]]), 4)
    # a budget's estimate counts a block's passes, in whole segments
    assert cb.load_estimate(9) == 16 and cb._rounded_need(9) == 13


@pytest.mark.parametrize("bad", [
    {"block_length": 3}, {"denoising_steps": 3}, {"remasking": "random"},
    {"mask_token_id": 256}, {"layer_types": ("sliding_attention",) * 2},
    {"router": "argmax"}])
def test_the_config_refuses_what_is_no_block_model(bad):
    kw = fam.model_kwargs(CFG, {"max_seq_len": 64, "param_dtype": "float32"})
    with pytest.raises(ValueError):
        HybridConfig(**dict(kw, **bad))


def test_the_softmax_router_is_the_references():
    """Softmax over all experts in float32, the top-k, renormalised: the
    reference's ``route`` on the same matrix."""
    p = weights.make_params(ref.layer_spec(CFG)["moe"], 5, "float32")
    x = jax.random.normal(jax.random.key(6), (50, 64))
    idx, w, state = SoftmaxRouter(64, 8, 2).route(p, x)
    ridx, rw = ref.route(x, p, 2, True)
    assert state is None and np.array_equal(idx, ridx)
    assert worst(w, rw) < 1e-6
    assert worst(jnp.sum(w, -1), 1.0) < 1e-6
    _, raw, _ = SoftmaxRouter(64, 8, 2, norm_topk_prob=False).route(p, x)
    assert float(jnp.max(jnp.sum(raw, -1))) < 1.0


@pytest.mark.parametrize("router", ["sigmoid", "softmax"])
@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_every_share_of_the_router_adds_up_to_the_uncut_layer(router, form):
    """The share test, over the router: a router 16 wide cut in 8 shares of
    2 held experts adds up to the layer that holds all 16 (for the softmax
    router also to the uncut reference's). float32: 1e-5 of outputs of
    size ~0.1."""
    cfg = dict(CFG, num_experts=16, experts_held=[0, 16],
               initializer_range=0.05)
    full = weights.make_params(ref.layer_spec(cfg)["moe"], 5, "float32")
    if router == "sigmoid":
        full["router_bias"] = 0.01 * jax.random.normal(jax.random.key(2),
                                                       (16,))
    x = jax.random.normal(jax.random.key(6), (3, 50, 64))

    def layer(held):
        return HeldExperts(
            64, 32, 16, 2, experts_held=held,
            dense_max_tokens=512 if form == "dense" else 0,
            router=SoftmaxRouter(64, 16, 2) if router == "softmax" else None)

    total = 0.0
    for share in range(8):
        p = dict(full, experts={k: a[2 * share:2 * share + 2]
                                for k, a in full["experts"].items()})
        total = total + layer((2 * share, 2)).apply(p, x)
    whole = layer(None).apply(full, x)
    assert float(jnp.max(jnp.abs(whole))) > 1e-2
    assert worst(total, whole) < 1e-5
    if router == "softmax":
        uncut = ref.moe_partial(x.reshape(-1, 64), full, cfg, held=None)
        assert worst(total.reshape(-1, 64), uncut) < 1e-5


@pytest.mark.parametrize("t,masked", [(256, False), (200, True)])
def test_the_flash_forward_under_the_block_mask_is_the_dense_one(t, masked):
    """The kernel (interpreted) masks its diagonal tiles by blocks: against
    the dense path's whole mask, with pad keys and a length that is padded
    up to the tile."""
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (2, 2, t, 128)) for kk in ks)
    kv_mask = None
    if masked:
        kv_mask = (jnp.arange(t)[None, :] < jnp.asarray([[200], [120]])
                   ).astype(jnp.float32)
    got = A.attention(q, k, v, causal=True, kv_mask=kv_mask, impl="pallas",
                      block_q=128, block_k=128, mask_block=4)
    want = A.attention(q, k, v, causal=True, kv_mask=kv_mask, impl="xla",
                       mask_block=4)
    real = 120 if masked else t
    assert worst(got[1, :, :real], want[1, :, :real]) < 2e-5
    assert worst(got[0], want[0]) < 2e-5
    causal = A.attention(q, k, v, causal=True, kv_mask=kv_mask, impl="xla")
    assert worst(causal[0], want[0]) > 1e-2
    with pytest.raises(ValueError, match="mask_block"):
        A.attention(q[:, :, :64], k, v, causal=True, mask_block=4)


def test_the_block_table_kernel_takes_a_blocks_queries():
    """``dcp_paged_decode_attn`` (interpreted) with 4 query positions a row
    beside the head group, all over ONE range of slots, against the
    gathered view; a parked row reads zeros; one position is the call every
    tick makes."""
    B, H, hk, hd, bt, nb, L = 3, 4, 2, 128, 8, 4, 4
    ks = jax.random.split(jax.random.key(1), 2)
    pool = jax.random.normal(ks[0], (2, 1 + B * nb, hk, bt, hd))
    table = (1 + jnp.arange(B * nb, dtype=jnp.int32)).reshape(B, nb)
    table = table.at[1].set(0)                       # a parked row
    end = jnp.asarray([19, 0, 7], jnp.int32)
    q = jax.random.normal(ks[1], (B, H, L, hd))
    got = decode_attention.paged_decode_attention_pallas(
        q, pool, table, end, interpret=True)
    view = A._paged_view({"kv": pool}, table)
    want = A.cached_attention(q, view["k"], view["v"],
                              jnp.broadcast_to(end[:, None], (B, L)))
    assert got.shape == (B, H, L, hd)
    live = jnp.asarray([0, 2])
    assert worst(got[live], want[live]) < 2e-5
    assert float(jnp.max(jnp.abs(got[1]))) == 0.0
    one = decode_attention.paged_decode_attention_pallas(
        q[:, :, :1], pool, table, end, interpret=True)
    assert worst(one[live], got[live, :, :1]) < 2e-5


def test_the_span_write_is_the_scatter_of_its_slots():
    """``dcp_kv_pool_write_span`` (interpreted): 4 consecutive slots a row
    in one window, every other slot of the pool untouched."""
    B, hk, hd, bt, L = 3, 2, 128, 16, 4
    ks = jax.random.split(jax.random.key(2), 2)
    pool = {"kv": jax.random.normal(ks[0], (2, 7, hk, bt, hd))}
    upd = {"kv": jax.random.normal(ks[1], (2, B, hk, L, hd))}
    blocks = jnp.asarray([5, 2, 0], jnp.int32)
    offsets = jnp.asarray([4, 12, 0], jnp.int32)
    got = cache_update.kv_pool_insert_span_pallas(pool, upd, blocks, offsets,
                                                  interpret=True)["kv"]
    want = np.array(pool["kv"])
    for b in range(B):
        o = int(offsets[b])
        want[:, int(blocks[b]), :, o:o + L] = np.asarray(upd["kv"][:, b])
    assert worst(got, want) == 0.0
    assert worst(cache_update.kv_pool_insert_span_all(
        pool, upd, blocks, offsets)["kv"], want) == 0.0
