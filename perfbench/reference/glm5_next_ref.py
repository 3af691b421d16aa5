"""Plain ``glm5_next_text`` decoder (GLM-5.3-Flash) in float32
``jax.numpy``: the full forward pass over one whole sequence, no cache, no
chunks, no kernels, no batching. Imports nothing of the program.

What a token carries between layers is ``X`` in ``R^{n x d}``: ``n =
hc_mult`` residual STREAMS of ``d = hidden_size`` channels. ``embed``
copies the token's embedding into every stream; the readout is ``W_head
RMSNorm_f(sum of streams)``, over the vocabulary slice held here.

1. HYPER-CONNECTION round a sublayer ``F`` (mixer or feed-forward, two a
   layer; manifold-constrained hyper-connections, arXiv:2512.24880):
   ``x~ = RMSNorm(vec(X))`` over all ``n d`` channels (no learned scale);
   ``H_pre = sigmoid(a_pre (x~ phi_pre) + b_pre)`` in ``R^n``; ``H_post = 2
   sigmoid(a_post (x~ phi_post) + b_post)`` in ``R^n``; ``H_res =
   Sinkhorn(exp(a_res mat(x~ phi_res) + b_res))`` in ``R^{n x n}``:
   ``hc_sinkhorn_iters`` rounds of row then column normalisation, ``hc_eps``
   in each denominator. ``u = H_pre X`` (one vector); ``y =
   F(RMSNorm_in(u))``; ``X' = H_res X + H_post^T y``.
2. KDA mixer (Kimi Delta Attention, arXiv:2510.26692; ``layer_types[l] ==
   "linear_attention"``; ``H`` heads of ``d_k = d_v = head_dim`` from
   ``linear_attn_config``): ``q^, k^, v^ = W_q x, W_k x, W_v x``, each
   through a depth-wise causal convolution over ``short_conv_kernel_size``
   tokens (zero left padding) and SiLU; ``q``, ``k`` scaled to unit length
   a head, ``q`` times ``d_k ** -0.5``; log-decay a CHANNEL ``g_t =
   gate_lower_bound * sigmoid(exp(A_h) (W_f2 W_f1 x + b_dt))`` in
   ``[gate_lower_bound, 0)``, ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(W_b
   x)`` a head; ``S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} +
   beta_t k_t v_t^T`` (``S`` in ``R^{d_k x d_v}`` a head, zero before the
   first token), ``o_t = S_t^T q_t``; ``y = W_o (RMSNorm_head(o_t) *
   sigmoid(W_g2 W_g1 x))``. The recurrence runs token by token
   (``lax.scan``).
3. SPARSE LATENT mixer (``"deepseek_sparse_attention"``: DeepSeek-V3.2-Exp's
   indexer over NoPE latent attention): ``c^q = RMSNorm(W_dq x)``, ``q_h =
   W_uq,h c^q`` (``qk_nope_head_dim``, no rotation: ``qk_rope_head_dim``
   0); ``c = RMSNorm(W_dkv x)`` (``kv_lora_rank``) is all a cache would keep
   of a token; ``[k_h, v_h] = W_ukv,h c``. Indexer: ``q^I_{t,j} =
   RoPE(W_qI,j c^q_t)`` (``index_n_heads`` heads of ``index_head_dim``),
   ``k^I_s = RoPE(LayerNorm(W_kI x_s))`` (one head), ``w_t = index_n_heads
   ** -0.5 * index_head_dim ** -0.5 * W_w x_t``; RoPE turns the FIRST
   ``index_rope_dim`` channels as interleaved pairs at
   ``index_rope_theta``. Pooled key of group ``g`` (tokens ``P g .. P g + P
   - 1``, ``P = index_kpool``): the mean of its ``P`` index keys. Score of
   every group BEFORE the token's own (``g < floor(t / P)``, all complete):
   ``I_{t,g} = sum_j w_{t,j} ReLU(q^I_{t,j} . K^I_g)``. The token attends
   the tokens of the ``min(index_topk / P, floor(t / P))`` groups of largest
   score (ties to the lower index) and ALWAYS its own group as far as it
   has come (``P floor(t / P) .. t``); ``o_{t,h} = softmax over those of
   (q_{t,h} . k_{s,h} / sqrt(qk_nope_head_dim)) v_{s,h}``; ``y = W_o o``.
4. Feed-forward: ``mlp_layer_types[l] == "dense"``: SwiGLU of
   ``intermediate_size``; ``"sparse"``: ``s = sigmoid(W_r y)`` in float32
   over ``router_num_experts``, the ``num_experts_per_tok`` with the
   largest ``s_e + b_e`` (``noaux_tc``; ``b`` for selection only), ``w_e =
   routed_scaling_factor * s_e / sum s``, the HELD experts' part of ``sum
   w_e E_e(y)`` plus the shared expert. Every SwiGLU is clamped: ``silu(
   min(gate, swiglu_limit)) * clip(up, -swiglu_limit, swiglu_limit)``.

What the published config does not settle is under ``assumed`` in the
configuration's file (the bounded form of the decay gate and its rank, the
pooling and the tail of the selection, the indexer's rotary width and base,
the clamp, the fan-out and fold of the streams, the draws).

The cut (the configuration file states it): ONE chip of the
``deployment_chips`` that share each layer holds ``experts_held = [first,
count]`` of the router's experts and an equal slice of the vocabulary; what
the absent experts would add is left out here as in the program.
``moe_partial`` with ``held=None`` and all experts' weights is the uncut
layer: the share test adds the shares up to it.

Departures: weights are drawn from the seed IN THE SERVED TYPE and handed to
the program; the reference multiplies their exact float32 values at
``Precision.HIGHEST``. One sublayer is walked at a time, an expert's weights
are cast one expert at a time, and both mixers run ``HEAD_GROUP`` heads at a
time (attention one block of ``Q_BLOCK`` queries at a time), so that 9.4 GB
of served weights and a 17k-token float32 forward fit one chip together.

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS for
a bfloat16 cell: both operands of every projection of the mixers (the
indexer's too), of every SwiGLU and of the head rounded to symmetric int8 or
float8 e4m3, per row of the activations and per column of the weights. The
router, the hyper-connection maps, the decay and beta gates, the recurrence
and the selection itself stay in float32 (a deployment at a lower precision
keeps them so).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
EMBED_STD = 1.0          # the embedding: the stream keeps its token
BIAS_STD = 0.01          # the router's selection bias (JoyAI's reading)
CONV_STD = 0.5           # a convolution tap (short_conv_kernel_size of them)
DT_BIAS_STD = 3.0        # the decay gate's bias: decays from fast to slow
HC_GAIN = 0.1            # a_pre, a_post, a_res
HC_RES_DIAG = 2.5        # b_res = HC_RES_DIAG * I: H_res starts near I
UNIT_EPS = 1e-6
Q_BLOCK = 256
HEAD_GROUP = 16

KDA = "linear_attention"     # every other layer: deepseek_sparse_attention


def _held(cfg: dict) -> tuple:
    first, count = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return int(first), int(count)


def _router_width(cfg: dict) -> int:
    return cfg.get("router_num_experts") or cfg["n_routed_experts"]


def _out_std(cfg: dict) -> float:
    """A mixer's output projection ``o`` is drawn at ``initializer_range /
    sqrt(2 x published depth)``, the scaled init of output projections
    (``assumed.stream_draw`` says why)."""
    depth = cfg.get("published", {}).get("num_hidden_layers",
                                         cfg["num_hidden_layers"])
    return cfg.get("initializer_range", 0.02) / math.sqrt(2 * depth)


def _kda(cfg: dict) -> tuple:
    """(heads, head width, taps, gate rank, lower bound) of the KDA layers."""
    la = cfg["linear_attn_config"]
    return (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"],
            cfg.get("kda_gate_rank", 128), float(la["gate_lower_bound"]))


def hc_spec(cfg: dict) -> dict:
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    std = cfg.get("initializer_range", 0.02)
    const = lambda v: ("const", v)
    # (an array as a constant's value: weights.py fills the leaf with it)
    eye = HC_RES_DIAG * np.eye(n, dtype=np.float32)
    return {"phi_pre": ((n * d, n), std), "phi_post": ((n * d, n), std),
            "phi_res": ((n * d, n * n), std),
            "a_pre": ((), const(HC_GAIN)), "a_post": ((), const(HC_GAIN)),
            "a_res": ((), const(HC_GAIN)),
            # H_pre starts near 1/n, H_post at 1, H_res near the identity
            "b_pre": ((n,), const(-math.log(n - 1.0))),
            "b_post": ((n,), const(0.0)),
            "b_res": ((n, n), const(eye))}


def layer_spec(cfg: dict, l: int) -> dict:
    d = cfg["hidden_size"]
    std, out = cfg.get("initializer_range", 0.02), _out_std(cfg)
    one = ("const", 1.0)
    lin = lambda i, o, s=std: {"kernel": ((i, o), s)}
    if cfg["layer_types"][l] == KDA:
        H, dk, taps, R, _ = _kda(cfg)
        C = H * dk
        conv = lambda: {"kernel": ((taps, C), CONV_STD)}
        p = {"q": lin(d, C), "k": lin(d, C), "v": lin(d, C),
             "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
             "f_down": lin(d, R), "f_up": lin(R, C),
             "dt_bias": ((C,), DT_BIAS_STD),
             "A_log": ((H,), ("const", 0.0)),
             "beta": lin(d, H),
             "g_down": lin(d, R), "g_up": lin(R, C),
             "o_norm": {"scale": ((dk,), one)}, "o": lin(C, d, out)}
    else:
        H, ql, kvl = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                      cfg["kv_lora_rank"])
        n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                   cfg["v_head_dim"])
        Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
        p = {"q_down": lin(d, ql), "q_norm": {"scale": ((ql,), one)},
             "q_up": lin(ql, H * (n + r)),
             "kv_down": lin(d, kvl + r), "kv_norm": {"scale": ((kvl,), one)},
             "kv_up": lin(kvl, H * (n + v)), "o": lin(H * v, d, out),
             "idx_q": lin(ql, Hi * di), "idx_k": lin(d, di),
             "idx_k_norm": {"scale": ((di,), one),
                            "bias": ((di,), ("const", 0.0))},
             "idx_w": lin(d, Hi)}
    p.update(attn_hc=hc_spec(cfg), mlp_hc=hc_spec(cfg),
             pre_attn_norm={"scale": ((d,), one)},
             pre_mlp_norm={"scale": ((d,), one)})
    if cfg["mlp_layer_types"][l] == "dense":
        ff = cfg["intermediate_size"]
        p.update(gate=lin(d, ff), up=lin(d, ff), down=lin(ff, d))
    else:
        f = cfg["moe_intermediate_size"]
        sf = f * cfg["n_shared_experts"]
        cnt = _held(cfg)[1]
        p["moe"] = {
            "router": lin(d, _router_width(cfg)),
            "router_bias": ((_router_width(cfg),), BIAS_STD),
            "experts": {"gate": ((cnt, d, f), std), "up": ((cnt, d, f), std),
                        "down": ((cnt, f, d), std)},
            "shared": {"gate": lin(d, sf), "up": lin(d, sf),
                       "down": lin(sf, d)}}
    return p


def param_spec(cfg: dict) -> dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    return {
        "wte": {"embedding": ((V, d), EMBED_STD)},
        "layers": [layer_spec(cfg, l)
                   for l in range(cfg["num_hidden_layers"])],
        "norm_f": {"scale": ((d,), ("const", 1.0))},
        "lm_head": {"kernel": ((d, V), std)},
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


FLOAT32_LEAVES = ("router_bias", "dt_bias")


def param_dtypes(cfg: dict, served: str) -> dict:
    """Constants (norm scales and biases, the hyper-connections' gains and
    biases, ``A_log``), the router's selection bias and the decay gate's
    bias are float32 whatever the served type (the program keeps them so);
    every matrix is served."""
    def walk(node, name=""):
        if _is_leaf(node):
            keep32 = isinstance(node[1], tuple) or name in FLOAT32_LEAVES
            return "float32" if keep32 else served
        if isinstance(node, list):
            return [walk(x) for x in node]
        return {k: walk(v, k) for k, v in node.items()}
    return walk(param_spec(cfg))


def _fq(x, axis, kind):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    top = 127.0 if kind == "int8" else 448.0
    scale = jnp.where(amax > 0, amax / top, 1.0)
    if kind == "int8":
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _linear(x, w, precision="f32"):
    w = w.astype(jnp.float32)
    if precision != "f32":
        x, w = _fq(x, -1, precision), _fq(w, 0, precision)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    y = x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y if scale is None else y * scale.astype(jnp.float32)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


# ---- 1. the hyper-connection ----------------------------------------------

def sinkhorn(m, iters, eps):
    """``m [..., n, n]`` positive -> near doubly stochastic: ``iters``
    rounds of row then column normalisation."""
    def body(_, m):
        m = m / (jnp.sum(m, -1, keepdims=True) + eps)
        return m / (jnp.sum(m, -2, keepdims=True) + eps)
    return lax.fori_loop(0, iters, body, m)


def hc_maps(X, hp, cfg):
    """``X [T, n, d]`` -> ``H_pre [T, n]``, ``H_post [T, n]``, ``H_res [T,
    n, n]``."""
    T, n, d = X.shape
    hp = _f32(hp)
    xt = _rms(X.reshape(T, n * d), None, float(cfg["rms_norm_eps"]))
    mm = lambda w: jnp.matmul(xt, w, precision=HIGHEST)
    pre = jax.nn.sigmoid(hp["a_pre"] * mm(hp["phi_pre"]) + hp["b_pre"])
    post = 2.0 * jax.nn.sigmoid(hp["a_post"] * mm(hp["phi_post"])
                                + hp["b_post"])
    res = sinkhorn(jnp.exp(hp["a_res"] * mm(hp["phi_res"]).reshape(T, n, n)
                           + hp["b_res"]),
                   int(cfg["hc_sinkhorn_iters"]), float(cfg["hc_eps"]))
    return pre, post, res


def hyper(X, hp, norm_scale, cfg, F):
    """``X' = H_res X + H_post^T F(RMSNorm_in(H_pre X))``."""
    pre, post, res = hc_maps(X, hp, cfg)
    u = jnp.einsum("tn,tnd->td", pre, X, precision=HIGHEST)
    y = F(_rms(u, norm_scale, float(cfg["rms_norm_eps"])))
    return (jnp.einsum("tnm,tmd->tnd", res, X, precision=HIGHEST)
            + post[:, :, None] * y[:, None, :])


# ---- 2. the KDA mixer -------------------------------------------------------

def _causal_conv(x, w):
    """``x [T, C]``, taps ``w [K, C]`` (the earliest token's first):
    ``y_t = sum_i w_i x_{t - (K - 1) + i}``, zeros before the sequence."""
    K = w.shape[0]
    xp = jnp.pad(x, ((K - 1, 0), (0, 0)))
    T = x.shape[0]
    return sum(w[i].astype(jnp.float32) * xp[i:i + T] for i in range(K))


def _unit(x):
    return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + UNIT_EPS)


def kda_recurrence(q, k, v, g, beta):
    """The delta rule with a per-channel decay, token by token: ``q, k, g
    [T, H, dk]``, ``v [T, H, dv]``, ``beta [T, H]`` -> ``o [T, H, dv]`` and
    the final state ``[H, dk, dv]``."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, :, None]
        w = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = S + k_t[:, :, None] * (b_t[:, None] * (v_t - w))[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    S, o = lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                    (q, k, v, g, beta))
    return o, S


def kda_mixer(x, p, cfg, precision="f32"):
    """``y`` of one KDA layer for the normed mixer input ``x [T, d]``,
    ``HEAD_GROUP`` heads at a time."""
    T = x.shape[0]
    H, dk, _, _, lower = _kda(cfg)
    f_low = jnp.matmul(x, p["f_down"]["kernel"].astype(jnp.float32),
                       precision=HIGHEST)
    g_low = _linear(x, p["g_down"]["kernel"], precision)
    beta = jax.nn.sigmoid(jnp.matmul(
        x, p["beta"]["kernel"].astype(jnp.float32), precision=HIGHEST))
    y = jnp.zeros((T, cfg["hidden_size"]), jnp.float32)
    hg = min(HEAD_GROUP, H)
    for h0 in range(0, H, hg):
        cols = slice(h0 * dk, (h0 + hg) * dk)
        heads = lambda t: t.reshape(T, hg, dk)
        proj = lambda name: heads(jax.nn.silu(_causal_conv(
            _linear(x, p[name]["kernel"][:, cols], precision),
            p["conv_" + name]["kernel"][:, cols])))
        q = _unit(proj("q")) * dk ** -0.5
        k = _unit(proj("k"))
        v = proj("v")
        f = jnp.matmul(f_low, p["f_up"]["kernel"][:, cols].astype(
            jnp.float32), precision=HIGHEST) + p["dt_bias"][cols]
        g = lower * jax.nn.sigmoid(
            jnp.exp(p["A_log"][h0:h0 + hg].astype(jnp.float32))[None, :, None]
            * heads(f))
        o, _ = kda_recurrence(q, k, v, g, beta[:, h0:h0 + hg])
        o = _rms(o, p["o_norm"]["scale"], float(cfg["rms_norm_eps"]))
        gate = jax.nn.sigmoid(heads(_linear(
            g_low, p["g_up"]["kernel"][:, cols], precision)))
        y = y + _linear((o * gate).reshape(T, hg * dk),
                        p["o"]["kernel"][cols], precision)
    return y


# ---- 3. the sparse latent mixer ---------------------------------------------

def rope_first(x, rd, theta):
    """``x [T, ..., hd]`` rotated at positions 0..T-1 over the FIRST ``rd``
    channels as interleaved pairs: ``(x[2i], x[2i+1])`` by the angle ``pos *
    theta ** (-2i / rd)``; the rest pass through."""
    T = x.shape[0]
    inv = theta ** (-jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv       # [T, rd/2]
    ang = ang.reshape((T,) + (1,) * (x.ndim - 2) + (rd // 2,))
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0:rd:2], x[..., 1:rd:2]
    rot = jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape[:-1] + (rd,))
    return jnp.concatenate([rot, x[..., rd:]], -1)


def _layernorm(x, scale, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale.astype(jnp.float32) \
        + bias.astype(jnp.float32)


def index_parts(x, cq, p, cfg, precision="f32"):
    """The indexer's queries ``[T, Hi, di]``, keys ``[T, di]`` (both
    rotated) and head weights ``[T, Hi]``."""
    T = x.shape[0]
    Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    rd = cfg.get("index_rope_dim", di // 2)
    theta = float(cfg.get("index_rope_theta", 10000.0))
    qi = rope_first(_linear(cq, p["idx_q"]["kernel"], precision).reshape(
        T, Hi, di), rd, theta)
    ki = rope_first(_layernorm(
        _linear(x, p["idx_k"]["kernel"], precision),
        p["idx_k_norm"]["scale"], p["idx_k_norm"]["bias"],
        float(cfg["rms_norm_eps"])), rd, theta)
    w = _linear(x, p["idx_w"]["kernel"], precision) * (Hi * di) ** -0.5
    return qi, ki, w


def selection_mask(qi, ki, w, cfg):
    """``[T, T]`` bool: may token ``t`` (row) attend token ``s`` (column)?
    The tokens of its chosen groups and its own group up to itself."""
    T = qi.shape[0]
    P = int(cfg["index_kpool"])
    top = int(cfg["index_topk"]) // P
    G = T // P
    t = jnp.arange(T)
    own = (t[None, :] // P == t[:, None] // P) & (t[None, :] <= t[:, None])
    if G == 0:
        return own
    pooled = ki[:G * P].reshape(G, P, -1).mean(1)               # [G, di]
    kk = min(top, G)
    bq = min(Q_BLOCK, T)
    pad = (-T) % bq

    def block(args):
        qb, wb, t0 = args                       # [bq, Hi, di], [bq, Hi]
        s = jnp.einsum("qjd,gd->qjg", qb, pooled, precision=HIGHEST)
        score = jnp.einsum("qj,qjg->qg", wb, jax.nn.relu(s),
                           precision=HIGHEST)
        seen = jnp.arange(G)[None, :] < ((t0 + jnp.arange(bq)) // P)[:, None]
        _, idx = lax.top_k(jnp.where(seen, score, -jnp.inf), kk)
        chosen = jnp.zeros((bq, G), bool).at[
            jnp.arange(bq)[:, None], idx].set(True)
        return chosen & seen

    nblk = (T + pad) // bq
    padq = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
    groups = lax.map(block, (
        padq(qi).reshape((nblk, bq) + qi.shape[1:]),
        padq(w).reshape(nblk, bq, -1), jnp.arange(nblk) * bq))
    groups = groups.reshape(nblk * bq, G)[:T]
    tokens = jnp.repeat(groups, P, axis=1)                      # [T, G P]
    tokens = jnp.pad(tokens, ((0, 0), (0, T - G * P)))
    return tokens | own


def _masked_attention(q, k, v, mask):
    """``q``/``k [H, T, dq]``, ``v [H, T, dv]``, ``mask [T, T]``: softmax
    attention over the allowed tokens, one block of queries at a time."""
    H, T, dq = q.shape
    bq = min(Q_BLOCK, T)
    pad = (-T) % bq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    # a pad query sees token 0 (its row is cut off below)
    mp = jnp.pad(mask, ((0, pad), (0, 0))).at[T:, 0].set(True)

    def block(args):
        qb, mb = args                          # [H, bq, dq], [bq, T]
        s = jnp.einsum("hqd,hkd->hqk", qb, k,
                       precision=HIGHEST) / math.sqrt(dq)
        s = jnp.where(mb[None], s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    nblk = (T + pad) // bq
    o = lax.map(block, (qp.reshape(H, nblk, bq, dq).transpose(1, 0, 2, 3),
                        mp.reshape(nblk, bq, T)))
    return o.transpose(1, 0, 2, 3).reshape(H, T + pad, -1)[:, :T]


def sparse_mixer(x, p, cfg, precision="f32", dense=False):
    """``y`` of one sparse latent layer for the normed mixer input ``x [T,
    d]``. ``dense``: plain causal attention over every token (what the
    layer is while no token's context is past the selection's size)."""
    T = x.shape[0]
    H, kvl = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, vd = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    eps = float(cfg["rms_norm_eps"])
    cq = _rms(_linear(x, p["q_down"]["kernel"], precision),
              p["q_norm"]["scale"], eps)
    c = _rms(_linear(x, p["kv_down"]["kernel"], precision),
             p["kv_norm"]["scale"], eps)
    if dense:
        t = jnp.arange(T)
        mask = t[None, :] <= t[:, None]
    else:
        mask = selection_mask(*index_parts(x, cq, p, cfg, precision), cfg)
    y = jnp.zeros((T, cfg["hidden_size"]), jnp.float32)
    hg = min(HEAD_GROUP, H)
    up = p["kv_up"]["kernel"].reshape(kvl, H, n + vd)
    for h0 in range(0, H, hg):
        q = _linear(cq, p["q_up"]["kernel"][:, h0 * n:(h0 + hg) * n],
                    precision).reshape(T, hg, n).transpose(1, 0, 2)
        kv = _linear(c, up[:, h0:h0 + hg].reshape(kvl, hg * (n + vd)),
                     precision).reshape(T, hg, n + vd).transpose(1, 0, 2)
        o = _masked_attention(q, kv[..., :n], kv[..., n:], mask)
        y = y + _linear(o.transpose(1, 0, 2).reshape(T, hg * vd),
                        p["o"]["kernel"][h0 * vd:(h0 + hg) * vd], precision)
    return y


# ---- 4. the feed-forwards ---------------------------------------------------

def _swiglu(x, gate, up, down, precision, limit):
    g, u = _linear(x, gate, precision), _linear(x, up, precision)
    if limit:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return _linear(jax.nn.silu(g) * u, down, precision)


def route(h, p, top_k, scale, norm_topk):
    """``h [T, d]`` -> (experts ``[T, k]``, weights ``[T, k]``) over the
    whole router, in float32."""
    s = jax.nn.sigmoid(jnp.matmul(
        h, p["router"]["kernel"].astype(jnp.float32), precision=HIGHEST))
    _, idx = lax.top_k(s + p["router_bias"].astype(jnp.float32), top_k)
    w = jnp.take_along_axis(s, idx, -1)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w * scale


def moe_partial(h, p, cfg, held=None, shared=True, precision="f32"):
    """The sparse layer's ``m`` for ``h [T, d]``: the sum over the HELD
    experts (``held = (first, count)``; ``p["experts"]`` stacks exactly
    those, in order; None = every expert of the router) of ``w_e E_e(h)``,
    plus the shared expert when ``shared``."""
    first, count = held or (0, _router_width(cfg))
    limit = float(cfg.get("swiglu_limit") or 0.0)
    idx, w = route(h, p, cfg["num_experts_per_tok"],
                   float(cfg["routed_scaling_factor"]),
                   bool(cfg["norm_topk_prob"]))
    ex = p["experts"]

    def add_expert(m, e_w):
        e, gate, up, down = e_w
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)      # [T]
        return m + we[:, None] * _swiglu(h, gate, up, down, precision,
                                         limit), None

    # one expert at a time (a scan, so that one expert's float32 copy is
    # live and the program is compiled once for all of them)
    m, _ = lax.scan(add_expert, jnp.zeros_like(h),
                    (jnp.arange(count), ex["gate"], ex["up"], ex["down"]))
    if shared:
        sp = p["shared"]
        m = m + _swiglu(h, sp["gate"]["kernel"], sp["up"]["kernel"],
                        sp["down"]["kernel"], precision, limit)
    return m


def feed_forward(y, p, cfg, dense, precision="f32"):
    if dense:
        return _swiglu(y, p["gate"]["kernel"], p["up"]["kernel"],
                       p["down"]["kernel"], precision,
                       float(cfg.get("swiglu_limit") or 0.0))
    return moe_partial(y, p["moe"], cfg, _held(cfg), True, precision)


# ---- the forward --------------------------------------------------------------

def _static(cfg: dict) -> tuple:
    """The keys a layer reads, hashable (a static argument of the jit)."""
    keys = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "index_kpool", "index_rope_dim", "index_rope_theta",
            "kda_gate_rank", "hc_mult", "hc_sinkhorn_iters", "hc_eps",
            "rms_norm_eps", "n_routed_experts", "num_experts_per_tok",
            "routed_scaling_factor", "norm_topk_prob", "router_num_experts",
            "swiglu_limit")
    out = {k: cfg[k] for k in keys if k in cfg}
    out["experts_held"] = _held(cfg)
    out["linear_attn_config"] = tuple(sorted(
        (k, v) for k, v in cfg["linear_attn_config"].items()
        if not isinstance(v, list)))
    return tuple(sorted(out.items()))


def _unstatic(cfg_t: tuple) -> dict:
    cfg = dict(cfg_t)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"])
    return cfg


@partial(jax.jit, static_argnames=("cfg_t", "kind", "precision"),
         donate_argnums=(0,))
def _attn_half(X, p, cfg_t, kind, precision):
    cfg = _unstatic(cfg_t)
    mixer = kda_mixer if kind == KDA else sparse_mixer
    return hyper(X, p["attn_hc"], p["pre_attn_norm"]["scale"], cfg,
                 lambda x: mixer(x, p, cfg, precision))


@partial(jax.jit, static_argnames=("cfg_t", "dense", "precision"),
         donate_argnums=(0,))
def _mlp_half(X, p, cfg_t, dense, precision):
    cfg = _unstatic(cfg_t)
    return hyper(X, p["mlp_hc"], p["pre_mlp_norm"]["scale"], cfg,
                 lambda y: feed_forward(y, p, cfg, dense, precision))


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(X, scale, head, eps, precision):
    return _linear(_rms(jnp.sum(X, 1), scale, eps), head, precision)


def embed(params, tokens, cfg):
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    return jnp.repeat(x[:, None, :], cfg["hc_mult"], axis=1)


def forward(params, tokens, cfg, precision="f32", rows=None):
    """``tokens [T]`` -> logits ``[T, V]`` (float32), one sequence; ``rows =
    (first, count)``: the logits of those positions only."""
    X = embed(params, tokens, cfg)
    cfg_t = _static(cfg)
    for l in range(cfg["num_hidden_layers"]):
        p = params["layers"][l]
        X = _attn_half(X, p, cfg_t, cfg["layer_types"][l], precision)
        X = _mlp_half(X, p, cfg_t, cfg["mlp_layer_types"][l] == "dense",
                      precision)
    if rows is not None:
        X = X[rows[0]:rows[0] + rows[1]]
    return _readout(X, params["norm_f"]["scale"],
                    params["lm_head"]["kernel"], float(cfg["rms_norm_eps"]),
                    precision)


@jax.jit
def _gaps(logits, served):
    """Per position: the reference's best logit minus the reference's
    logit of the token that was served there."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


def raw_token_gaps(params, prompt, served, cfg, pad_to=256, control=()):
    """Teacher-forced over ``prompt + served``: for every served token the
    reference's best logit at its position minus the reference's logit of
    the token itself (``"served"``) and, per precision named in
    ``control``, the same for the token that forward pass puts first."""
    seq = list(prompt) + list(served)
    n, m = len(prompt), len(served)
    T = -(-(len(seq) - 1) // pad_to) * pad_to
    toks = jnp.asarray(seq[:-1] + [0] * (T - (len(seq) - 1)), jnp.int32)
    logits = forward(params, toks, cfg, "f32", rows=(n - 1, m))
    out = {"served": _gaps(logits, jnp.asarray(served, jnp.int32))}
    for prec in control:
        low = forward(params, toks, cfg, prec, rows=(n - 1, m))
        out[prec] = _gaps(logits, jnp.argmax(low, -1).astype(jnp.int32))
    return jax.device_get(out)


def served_token_gaps(params, prompt, served, cfg, pad_to=256,
                      control=()):
    """What the serve runner compares: for each served token THE MEAN GAP
    OF ITS REQUEST's served tokens (:func:`raw_token_gaps` has each token's
    own), so that the worst the runner takes is the worst request's mean;
    the same for each control precision.

    Why a request's mean and not a token's own gap: as in every routed
    model here, the worst token is a flip of the router at a near-tie (and
    here of the SELECTION at the edge of its chosen groups) and not
    rounding, so the sound program's worst token reaches into the int8
    control's range, while lower precision makes a gap more frequent, which
    a mean shows (the readings at the published widths are in PERF.md
    section 6, PR 41). Each request's raw worst gap and count are printed
    beside what is reported."""
    raw = raw_token_gaps(params, prompt, served, cfg, pad_to, control)
    worst = {k: float(v.max()) for k, v in raw.items()}
    print(f"INFO glm5_next_ref: request of {len(prompt)} + "
          f"{len(served)} tokens: a token's own gap at worst {worst}, "
          f"tokens with a gap "
          f"{ {k: int((v > 0).sum()) for k, v in raw.items()} }; reported: "
          f"the request's mean", flush=True)
    return {k: [float(v.mean())] * len(v) for k, v in raw.items()}
