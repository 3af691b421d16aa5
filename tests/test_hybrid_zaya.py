"""Compressed convolutional attention in the decoder of layer kinds
(``models/hybrid.py``, mixer ``cca_attention``) and what it brought: the
per-slot tail beside the paged pool (cache kind ``paged+tail``) handed
from admission to decode, the carry between layers (a router that is a
network with depth-averaged state), the skip choice, scaled residual
merges, partial rotation, a tied head; each against the plain reference
``perfbench/reference/zaya_ref.py`` (whole sequences only: real
convolutions, a real shift, no cache, no tail) at a small size on the CPU,
on seeded weights. Every tolerance says why it has its value."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.hybrid import (
    HybridConfig, HybridLM)
from distributed_compute_pytorch_tpu.models.moe import HeldExperts, MLPRouter
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops.rotary import apply_rope
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from perfbench import weights
from perfbench.family import zaya as fam
from perfbench.reference import zaya_ref as ref

# The tiny configuration in the PUBLISHED keys: every part of the family
# (4 query / 2 key heads of 16, both convolutions 2 wide, half of a head
# rotated, a router 16 wide over 4 experts and the skip, tied vocabulary).
CFG = {
    "family": "zaya", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "cca_time0": 2,
    "cca_time1": 2, "partial_rotary_factor": 0.5, "rope_theta": 5000000,
    "rms_norm_eps": 1e-5, "num_experts": 4, "num_experts_per_tok": 1,
    "moe_intermediate_size": 32, "router_hidden_size": 16,
    "tie_word_embeddings": True, "vocab_size": 512, "num_hidden_layers": 3,
    "serving": {"slots": 4},
}

# float32 on both sides, the same weights: what is left is the order of
# summation (the program's fused products and shifted sums, the
# reference's convolutions at HIGHEST), read 3e-7 on logits of size ~0.7.
# 2e-5 leaves a decade and a half of room and is five hundred times under
# what bfloat16 does (the control below). A router flip at a near-tie
# would read ~1e-1: none on these seeds.
TOL_F32 = 2e-5


def build(dtype="float32", seed=7, cfg=CFG, t_max=128):
    model = build_model(fam.BUILD_MODEL, **fam.model_kwargs(
        cfg, {"max_seq_len": t_max, "param_dtype": dtype}))
    params = weights.make_params(ref.param_spec(cfg), seed,
                                 ref.param_dtypes(cfg, dtype))
    return model, params


def engine(model, params, **kw):
    """Blocks of 8 tokens (the model implies 32: a block of its short K/V
    pairs is then what 8 tokens are at 8 KV heads), so that 100 tokens
    cross a dozen blocks and ``prompt_buf`` 64 has four admission rungs."""
    return ContinuousBatcher(model, params, slots=4, t_max=128,
                             prompt_buf=64, kv_block_tokens=8, **kw)


def tick_logits(cb, toks, pos, live=None):
    """One decode tick of every slot outside the scheduler: slot ``b``
    consumes ``toks[b]`` at position ``pos[b]`` against the engine's own
    caches and tables; returns the logits ``[slots, V]``."""
    model = cb.model

    def step(params, caches, tables, tok, pos, live):
        x = model.embed(params, tok[:, None], pos[:, None])
        new, carry = [], None
        for li in range(cb._n_layers):
            x, c2, carry = cb._decode_layer(li, params, x, caches[li],
                                            tables, pos, live, None,
                                            pin=False, carry=carry)
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


def ref_logits(params, toks):
    return np.asarray(ref.forward(params, jnp.asarray(toks, jnp.int32), CFG))


def test_parameter_tree_is_the_references():
    model, params = build()
    have = jax.tree.map(lambda a: (a.shape, str(a.dtype)),
                        jax.eval_shape(lambda k: model.init(k)[0],
                                       jax.random.key(0)))
    want = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    assert have == want
    assert "lm_head" not in params          # tied: one matrix, held once


@pytest.mark.parametrize("family", ["exaone_moe", "joyai_llm_flash"])
def test_defaults_build_the_other_families_trees_unchanged(family):
    """A ``HybridConfig`` that names none of the new keys is what it was:
    plain residual adds, an untied head, whole-head rotation, no carry, no
    tail; and the parameter trees of the K-EXAONE and JoyAI families' tiny
    configurations equal their own reference specs leaf for leaf (no key
    of the CCA mixer, the merges or the MLP router in them)."""
    import importlib
    c = HybridConfig()
    assert (c.scale_residual_merge, c.tie_embeddings, c.router_hidden,
            c.partial_rotary_factor) == (False, False, 0, 1.0)
    fam_mod = importlib.import_module(f"perfbench.family.{family}")
    ref_mod = importlib.import_module(f"perfbench.reference.{family}_ref")
    cfg = importlib.import_module(
        "tests.test_hybrid_exaone" if family == "exaone_moe"
        else "tests.test_hybrid_joyai").CFG
    model = build_model("hybrid", **fam_mod.model_kwargs(
        cfg, {"max_seq_len": 64, "param_dtype": "float32"}))
    have = jax.tree.map(lambda a: tuple(a.shape), jax.eval_shape(
        lambda k: model.init(k)[0], jax.random.key(0)))
    want = jax.tree.map(lambda s: s[0], ref_mod.param_spec(cfg),
                        is_leaf=weights._is_leaf)
    assert have == want
    assert "lm_head" in have and "embed_merge" not in have
    assert not any(model.layer_block(i).carries
                   for i in range(model.num_layers))
    assert set(have["layers"][1]["moe"]["router"]) == {"kernel"}


def test_full_forward_matches_the_reference_on_logits():
    model, params = build()
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    assert float(np.max(np.abs(got[0] - ref_logits(params, toks)))) < TOL_F32


@pytest.mark.parametrize("prefill", [0, 1, 2, 21, 45])
def test_prefill_then_decode_through_the_batcher_matches_on_logits(prefill):
    """100 tokens over thirteen pool blocks of 8; no prefill at all (the
    zero tail is the empty row), windows of 1 and 2 tokens (the zero
    padding and ``a_{-1} = c0``), one of 21 at the admission rung of 32 and
    one of 45 at 64 (two rungs); the rest through decode ticks from the
    tail admission wrote; every logit against the reference's whole-
    sequence forward."""
    model, params = build()
    cb = engine(model, params)
    assert cb.bt == 8 and cb.nb == 16 and model.cache_block_tokens == 32
    snap = cb.stats_snapshot()
    assert snap["cache_kinds"] == ["paged+tail"] * 3
    # a K/V pair of 2 heads of 16 in float32; a tail of 2 x 96 + 16 floats
    assert snap["cache_bytes_per_token"] == {"paged+tail": 2 * 2 * 16 * 4}
    assert snap["state_bytes_per_slot"] == {"paged+tail": 208 * 4}
    assert model.tail_width == fam.tail_width(CFG) == 208
    toks = np.random.default_rng(1).integers(1, 512, 100)
    got = cb.logit_probe(toks, prefill=prefill)
    want = ref_logits(params, toks)[prefill:]
    assert got.shape == want.shape
    assert float(np.max(np.abs(got - want))) < TOL_F32


def test_rows_of_different_lengths_in_one_wave_hand_their_own_tails_over():
    """ONE admission dispatch of three rows of 3, 17 and 30 tokens (and a
    pad row) into slots 0, 2 and 3: each row's tail is of ITS last real
    token, so the ticks that follow (twelve: every row crosses a block
    edge) give the reference's logits for each row's own sequence."""
    model, params = build()
    cb = engine(model, params)
    rng = np.random.default_rng(3)
    seqs = {b: rng.integers(1, 512, n + 12) for b, n in
            ((0, 3), (2, 17), (3, 30))}
    heads = {0: 3, 2: 17, 3: 30}
    admit(cb, {b: seqs[b][:heads[b]] for b in seqs})
    want = {b: ref_logits(params, seqs[b]) for b in seqs}
    for t in range(12):
        toks, pos = np.zeros(4, int), np.zeros(4, int)
        for b in seqs:
            toks[b], pos[b] = seqs[b][heads[b] + t], heads[b] + t
        got = tick_logits(cb, toks, pos, live=[1, 0, 1, 1])
        for b in seqs:
            assert float(np.max(np.abs(
                got[b] - want[b][heads[b] + t]))) < TOL_F32, (b, t)


@pytest.mark.parametrize("lengths", [(1, 2, 13), (5, 0, 9)])
def test_the_decode_form_equals_the_prefill_form_token_for_token(lengths):
    """ONE layer: its whole-window ``apply`` over rows of 1, 2 and 13 real
    tokens (or none: the empty row), then ``decode_step`` tick by tick from
    the tail that ``apply`` captured at each row's last real token, against
    ``apply`` over the whole sequences; and the K/V the ticks wrote against
    the prefill's. float32, the same sums in another order: 1e-5 on
    activations of size ~1."""
    model, params = build()
    block, p = model.layer_block(1), params["layers"][1]
    B, T, more = 3, 16, 6
    x = jax.random.normal(jax.random.key(3), (B, T + more, 64))
    n = jnp.asarray(lengths)
    mask = (jnp.arange(T)[None] < n[:, None]).astype(jnp.float32)

    @jax.jit
    def prefill(p, x, mask=None):
        sink: list = []
        y, _ = block.apply(p, x, kv_mask=mask, kv_sink=sink)
        return y, sink[0]

    _, kept = prefill(p, x[:, :T], mask)
    (k, v), tail = kept["kv"], kept["tail"]
    assert list(kept) == list(block.cache_leaves(B, 4, 16, jnp.float32))
    assert tail.shape == (B, model.tail_width)
    for b, real in enumerate(lengths):
        if real <= 1:                # no token before the first: zeros
            assert not tail[b, 96:192].any()
        if real == 0:                # the empty row
            assert not tail[b].any()
    keep = (jnp.arange(T + more)[None, None, :, None] < n[:, None, None, None])
    pad = lambda a: jnp.pad(a, ((0, 0), (0, 0), (0, more), (0, 0)))
    cache = {"kv": jnp.stack([pad(k), pad(v)]) * keep, "tail": tail}
    step = jax.jit(block.decode_step)
    got = []
    for t in range(more):
        pos = n + t
        xt = jnp.take_along_axis(x, pos[:, None, None], axis=1)
        y, cache, _ = step(p, xt, cache, pos)
        got.append(y[:, 0])
    for b in range(B):
        nb = int(n[b])
        want, kept = prefill(p, x[b:b + 1, :nb + more])
        wk, wv = kept["kv"]
        for t in range(more):
            assert float(jnp.max(jnp.abs(
                got[t][b] - want[0, nb + t]))) < 1e-5, (b, t)
        assert float(jnp.max(jnp.abs(
            cache["kv"][0, b, :, :nb + more] - wk[0]))) < 1e-5
        assert float(jnp.max(jnp.abs(
            cache["kv"][1, b, :, :nb + more] - wv[0]))) < 1e-5


@pytest.mark.parametrize("second", [19, 1, 0])
def test_a_reused_slot_gives_what_a_fresh_engine_gives(second):
    """Slot 1 serves 25 tokens of one request, then is admitted a second
    one (of 19 tokens, of one, of none: a row that prefills nothing still
    has its tail written, as zero): the logits of the second request's
    next tokens are bit for bit those of an engine that never held the
    first. The former tenant's tail, had it stayed, would move them by
    ~1e-1."""
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
    assert all(c["tail"][1].any() for c in used._caches)
    got, want = serve_second(used), serve_second(engine(model, params))
    assert (got == want).all()
    assert float(np.max(np.abs(
        want - ref_logits(params, nxt)[second:]))) < TOL_F32


def test_a_parked_rows_tail_does_not_advance():
    model, params = build()
    cb = engine(model, params)
    rng = np.random.default_rng(6)
    seqs = {0: rng.integers(1, 512, 9), 1: rng.integers(1, 512, 12)}
    admit(cb, {0: seqs[0][:8], 1: seqs[1][:8]})
    before = [np.asarray(c["tail"]) for c in cb._caches]
    tick_logits(cb, [seqs[0][8], 77, 0, 0], [8, 8, 0, 0], live=[1, 0, 0, 0])
    for c, old in zip(cb._caches, before):
        now = np.asarray(c["tail"])
        assert (now[1:] == old[1:]).all()           # parked: where it was
        assert (now[0] != old[0]).any()             # in the plan: advanced
    # and the parked row goes on from where it was, as if never ticked
    got = tick_logits(cb, [0, seqs[1][8], 0, 0], [0, 8, 0, 0],
                      live=[0, 1, 0, 0])[1]
    assert float(np.max(np.abs(
        got - ref_logits(params, seqs[1][:9])[8]))) < TOL_F32


def test_the_carry_between_layers_enters_the_next_layers_router():
    """The router state of layer ``l`` enters layer ``l + 1``'s router
    (``gamma``): with ``gamma`` zeroed (its stored offset at -0.5) the
    logits differ from the seeded model's by far more than the tolerance,
    and both match the reference, in the whole forward and through
    admission and ticks."""
    model, params = build()
    zeroed = jax.tree.map(lambda a: a, params)
    for layer in zeroed["layers"]:
        layer["moe"]["router"]["gamma"] = jnp.full_like(
            layer["moe"]["router"]["gamma"], -0.5)
    toks = np.random.default_rng(8).integers(1, 512, 60)
    outs = []
    for p in (params, zeroed):
        got, _ = model.apply(p, {}, jnp.asarray(toks[None], jnp.int32))
        want = ref_logits(p, toks)
        assert float(np.max(np.abs(got[0] - want))) < TOL_F32
        served = engine(model, p).logit_probe(toks, prefill=30)
        assert float(np.max(np.abs(served - want[30:]))) < TOL_F32
        outs.append(want)
    assert float(np.max(np.abs(outs[0] - outs[1]))) > 100 * TOL_F32


def test_a_token_sent_to_skip_gets_the_merge_of_a_zero_output():
    """With the selection bias of the skip choice raised above every
    probability, every token takes index 4, which no chip holds: the
    expert sublayer's result is EXACTLY ``sr (h + br) + sy (0 + by)``, the
    counts say so, and the reference agrees on the whole forward."""
    model, params = build()
    block, p = model.layer_block(0), jax.tree.map(lambda a: a,
                                                  params["layers"][0])
    p["moe"]["router_bias"] = p["moe"]["router_bias"].at[4].set(2.0)
    x = jax.random.normal(jax.random.key(2), (2, 20, 64))
    counts: list = []
    got, state = block._mlp(p, x, counts_sink=counts)
    m = p["mlp_merge"]
    want = ((1 + m["res_scale"]) * (x + m["res_bias"])
            + (1 + m["out_scale"]) * (0.0 + m["out_bias"]))
    assert (got == want).all()
    assert state.shape == (2, 20, 16)
    assert [int(c) for c in counts[0][:3]] == [40, 0, 40]
    skipping = dict(params, layers=[p] + params["layers"][1:])
    toks = np.random.default_rng(9).integers(1, 512, 40)
    out, _ = model.apply(skipping, {}, jnp.asarray(toks[None], jnp.int32))
    assert float(np.max(np.abs(
        out[0] - ref_logits(skipping, toks)))) < TOL_F32


@pytest.mark.parametrize("form", ["dense", "sorted"])
def test_with_every_expert_held_the_layer_is_the_uncut_references(form):
    """The share test in the form this cut has (held = all 16 of 16; the
    seventeenth choice is held by nobody): the program's expert layer, in
    the decode form and in the sorted form of an admission window, equals
    the uncut reference's ``moe_layer``, output and router state, with a
    state handed up from below. float32: 1e-5 of outputs of size ~0.1."""
    layer_p = weights.make_params(ref.layer_spec(CFG)["moe"], 5, "float32")
    x = jax.random.normal(jax.random.key(6), (3, 50, 64))
    below = jax.random.normal(jax.random.key(7), (3, 50, 16))
    layer = HeldExperts(64, 32, 5, 1, experts_held=(0, 4),
                        router=MLPRouter(64, 16, 5, 1e-5), skip_index=4,
                        dense_max_tokens=512 if form == "dense" else 0)
    counts: list = []
    got, state = layer.apply_with_state(layer_p, x, below,
                                        counts_sink=counts)
    want, want_state = ref.moe_layer(x.reshape(-1, 64),
                                     below.reshape(-1, 16), layer_p, CFG)
    assert float(jnp.max(jnp.abs(got.reshape(-1, 64) - want))) < 1e-5
    assert float(jnp.max(jnp.abs(
        state.reshape(-1, 16) - want_state))) < 1e-5
    total, held, skipped = (int(c) for c in counts[0][:3])
    assert total == 150 and held + skipped == total and skipped > 0
    # after the head: the held experts chosen, the held experts, the loads
    chosen, of = (int(c) for c in counts[0][3:5])
    assert 1 <= chosen <= of == 4
    assert int(counts[0][5:].sum()) == held
    assert int((counts[0][5:] > 0).sum()) == chosen


def test_partial_rotation_turns_the_first_channels_only():
    """``apply_rope(rotary_dim=8)`` on heads of 16: the first 8 channels
    are the whole-head rotation of those 8 alone, the last 8 pass through,
    the reference's own rotation agrees, and without the argument the
    function traces to what it traced to (its callers' programs do not
    move). float32: 1e-5 (the angle reaches 1e3 radians)."""
    x = jax.random.normal(jax.random.key(0), (2, 3, 5, 16))
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 100, 1000, 5, 0]])
    got = apply_rope(x, pos, 5e6, rotary_dim=8)
    assert (got[..., 8:] == x[..., 8:]).all()
    assert float(jnp.max(jnp.abs(
        got[..., :8] - apply_rope(x[..., :8], pos, 5e6)))) == 0.0
    shared = apply_rope(x, jnp.arange(5), 5e6, rotary_dim=8)
    assert float(jnp.max(jnp.abs(
        shared[0] - ref.rope_partial(x[0], 5e6, 8)))) < 1e-5
    whole = lambda x: apply_rope(x, pos, 1e4)
    same = lambda x: apply_rope(x, pos, 1e4, rotary_dim=16)
    assert str(jax.make_jaxpr(whole)(x)) == str(jax.make_jaxpr(same)(x))


def test_a_lower_precision_control_fails_the_tolerance():
    """The same program with bfloat16 weights and activations, against the
    float32 reference on the float32 values of those weights: rounding to
    8 bits of mantissa moves logits by ~1e-2; and so does the reference's
    own int8 control form."""
    model, params = build("bfloat16")
    toks = np.random.default_rng(0).integers(1, 512, 100)
    got, _ = model.apply(params, {}, jnp.asarray(toks[None], jnp.int32))
    want = ref_logits(params, toks)
    err = float(np.max(np.abs(np.asarray(got[0], np.float32) - want)))
    assert err > 10 * TOL_F32
    low = ref.forward(params, jnp.asarray(toks, jnp.int32), CFG, "int8")
    assert float(np.max(np.abs(np.asarray(low) - want))) > 10 * TOL_F32


def test_serving_is_greedy_equal_to_the_reference_and_counts_tails():
    """Staggered requests through the scheduler (one of a single token:
    nothing to prefill; one reusing a slot another has left), each token
    the reference's argmax; the counters of the tail and of the skip."""
    model, params = build()
    cb = engine(model, params)
    assert cb.stats_snapshot()["paged_read"] == "gather"      # on the CPU
    rng = np.random.default_rng(2)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=m)
            for n, m in ((40, 16), (5, 12), (61, 9), (1, 5), (2, 6), (33, 7))]
    for rq, res in zip(reqs, cb.serve_detailed(reqs)):
        assert res.status == "ok" and len(res.tokens) == rq.max_new
        seq = list(rq.tokens) + list(res.tokens)
        want = np.argmax(ref_logits(params, seq[:-1])[len(rq.tokens) - 1:],
                         -1)
        assert list(res.tokens) == [int(t) for t in want]
    snap = cb.stats_snapshot()
    st = snap["stats"]
    assert st["prefill_tokens"] == 39 + 4 + 60 + 0 + 1 + 32
    assert st["prefill_rows"] == 6
    assert st["expert_assignments"] == 3 * snap["waste"]["planned_ticks"]
    assert (st["expert_assignments_held"] + st["expert_assignments_skipped"]
            == st["expert_assignments"])
    assert snap["slot_leaks"] == snap["block_leaks"] == 0
    # a fresh session on the same programs: pools and tails re-zeroed
    cb.reset()
    assert not any(leaf.any() for c in cb._caches for leaf in c.values())
    again = cb.serve_detailed(reqs[:1])[0]
    assert again.status == "ok" and cb.stats["prefill_rows"] > 0


TAILS = "layers that keep a per-slot tail beside the pool"


@pytest.mark.parametrize("what,kw", [
    ("prefix_cache", {"prefix_cache": True}),
    ("speculate", {"speculate": 2}),
    ("host_cache", {"prefix_cache": True, "host_cache_blocks": 4}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("prefill_chunk_tokens", {"prefill_chunk_tokens": 32}),
])
def test_what_a_tail_cannot_be_served_with_is_refused(what, kw):
    model, params = build()
    with pytest.raises(ValueError, match=TAILS):
        ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                          **kw)


def test_a_mesh_is_refused_with_the_tails_reason():
    with pytest.raises(ValueError) as e:
        ContinuousBatcher._refuse_for_layer_kinds(
            {"paged+tail"}, prefix_cache=False, speculate=None, tiers=False,
            kv_dtype="bf16", mesh=object(), prefill_chunk_tokens=None)
    assert f"mesh does not compose with {TAILS} yet" in str(e.value)
    assert "window layers" not in str(e.value)


def test_the_training_path_and_half_named_kinds_refuse():
    model, params = build()
    with pytest.raises(NotImplementedError):
        model.apply(params, {}, jnp.zeros((1, 4), jnp.int32), train=True)
    with pytest.raises(NotImplementedError):
        model.loss_fn(None, None)
    with pytest.raises(ValueError, match="cca_time0"):
        HybridConfig(layer_types=("cca_attention",),
                     mlp_layer_types=("dense",), cca_time0=3)
    with pytest.raises(ValueError, match="router_hidden"):
        HybridConfig(layer_types=("full_attention",),
                     mlp_layer_types=("sparse_top1",), top_k=1)
    with pytest.raises(ValueError, match="norm_placement"):
        HybridConfig(scale_residual_merge=True)
    assert isinstance(model, HybridLM)


def test_with_the_experts_kernel_chosen_the_engine_serves_the_same_tokens(
        monkeypatch):
    """The held experts' decode form as the kernel over the chosen experts
    (``ops/pallas/held_experts.py``, interpreted) through the scheduler with
    slots parked (top-1 with the skip choice, the router's state carried
    from layer to layer): the tokens are the dense form's, so are the
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
