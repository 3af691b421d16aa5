"""Plain ``solar_open2`` decoder (Solar-Open2-250B) in float32
``jax.numpy``: the full forward pass over one whole sequence, no cache, no
chunks, no kernels, no batching. Imports nothing of the program.

Pre-norm residual, one vector a token: ``x <- x + Mixer(RMSNorm(x))``, ``x
<- x + MoE(RMSNorm(x))``, a final RMSNorm, an untied head over the
vocabulary slice held here. Layer ``l`` is a FULL layer if ``l`` is in
``gqa_layers``, else a KDA layer (three of them follow each full one).

1. FULL mixer (``use_rope`` false, ``use_gqa_gate``): ``q = W_q x``
   (``num_attention_heads`` heads of ``head_dim``), ``k, v = W_k x, W_v x``
   (``num_key_value_heads`` heads; query head ``h`` reads key-value head
   ``h // (heads / kv heads)``), no rotation, no QK-norm, causal softmax of
   ``q . k / sqrt(head_dim)``, ``y = W_o (attn * sigmoid(W_g x))``: one gate
   a channel of the heads laid side by side (arXiv:2505.06708).
2. KDA mixer (Kimi Delta Attention, arXiv:2510.26692; ``H`` heads of ``d_k
   = d_v = head_dim`` from ``linear_attn_config``): ``q^, k^, v^ = W_q x,
   W_k x, W_v x``, each through a depth-wise causal convolution over
   ``short_conv_kernel_size`` tokens (zero left padding) and SiLU; ``q``,
   ``k`` scaled to unit length a head, ``q`` times ``d_k ** -0.5``;
   log-decay a CHANNEL ``g_t = -exp(A_h) softplus(W_f2 W_f1 x + b_dt)``,
   NO floor, ``alpha_t = exp(g_t)`` in (0, 1); ``beta_t = 2 sigmoid(W_b x)``
   a head (``kda_allow_neg_eigval``, arXiv:2411.12537); ``S_t = (I - beta_t
   k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T`` (``S`` in ``R^{d_k
   x d_v}`` a head, zero before the first token), ``o_t = S_t^T q_t``; ``y =
   W_o (RMSNorm_head(o_t) * sigmoid(W_g2 W_g1 x))``. The recurrence runs
   token by token (``lax.scan``).
3. Feed-forward, every layer sparse: ``s = sigmoid(W_r y)`` in float32 over
   ``router_num_experts``, the ``num_experts_per_tok`` with the largest
   ``s_e + b_e`` (``noaux_tc``; ``b`` for selection only), ``w_e =
   routed_scaling_factor * s_e / sum s``, the HELD experts' part of ``sum
   w_e E_e(y)`` plus the shared expert; every expert a SwiGLU, unclamped.

What the published config does not settle is under ``assumed`` in the
configuration's file (the gate's element-wise form, the decay gate without
a floor and its rank, the shared expert's width, the router, the draws).

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
time (attention one block of ``Q_BLOCK`` queries at a time), so that 6.6 GB
of served weights and a 5k-token float32 forward fit one chip together.

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS for
a bfloat16 cell: both operands of every projection of the mixers, of every
SwiGLU and of the head rounded to symmetric int8 or float8 e4m3, per row of
the activations and per column of the weights. The router, the decay and
beta gates and the recurrence stay in float32 (a deployment at a lower
precision keeps them so).
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
DT_BIAS_STD = 3.0        # the decay gate's bias: a head's channels differ
RATE_LO, RATE_HI = 0.25, 4.0   # exp(A_log) of a layer's first and last head
UNIT_EPS = 1e-6
Q_BLOCK = 256
HEAD_GROUP = 16


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
    """(heads, head width, taps, gate rank) of the KDA layers."""
    la = cfg["linear_attn_config"]
    return (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"],
            cfg.get("kda_gate_rank", 128))


def is_full(cfg: dict, l: int) -> bool:
    return l in cfg["gqa_layers"]


def a_log(heads: int) -> np.ndarray:
    """``A_log`` of a layer's heads: rates ``exp(A_log)`` spread evenly in
    the logarithm from ``RATE_LO`` to ``RATE_HI`` (``assumed.kda_draw``)."""
    return np.linspace(math.log(RATE_LO), math.log(RATE_HI), heads,
                       dtype=np.float32)


def layer_spec(cfg: dict, l: int) -> dict:
    d = cfg["hidden_size"]
    std, out = cfg.get("initializer_range", 0.02), _out_std(cfg)
    one = ("const", 1.0)
    lin = lambda i, o, s=std: {"kernel": ((i, o), s)}
    if is_full(cfg, l):
        hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
        p = {"q": lin(d, hq * hd), "k": lin(d, hk * hd), "v": lin(d, hk * hd),
             "gate": lin(d, hq * hd), "o": lin(hq * hd, d, out)}
    else:
        H, dk, taps, R = _kda(cfg)
        C = H * dk
        conv = lambda: {"kernel": ((taps, C), CONV_STD)}
        p = {"q": lin(d, C), "k": lin(d, C), "v": lin(d, C),
             "conv_q": conv(), "conv_k": conv(), "conv_v": conv(),
             "f_down": lin(d, R), "f_up": lin(R, C),
             "dt_bias": ((C,), DT_BIAS_STD),
             "A_log": ((H,), ("const", a_log(H))),
             "beta": lin(d, H),
             "g_down": lin(d, R), "g_up": lin(R, C),
             "o_norm": {"scale": ((dk,), one)}, "o": lin(C, d, out)}
    p.update(pre_attn_norm={"scale": ((d,), one)},
             pre_mlp_norm={"scale": ((d,), one)})
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
    """Constants (norm scales, ``A_log``), the router's selection bias and
    the decay gate's bias are float32 whatever the served type (the program
    keeps them so); every matrix is served."""
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


def _exact(x, w):
    """A product that every precision keeps in float32 (the gates)."""
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _rms(x, scale, eps):
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


# ---- 1. the full mixer ------------------------------------------------------

def _causal_attention(q, k, v):
    """``q [H, T, hd]``, ``k``/``v [Hk, T, hd]`` (``H`` a multiple of
    ``Hk``): causal softmax attention, one block of queries at a time."""
    H, T, hd = q.shape
    group = H // k.shape[0]
    bq = min(Q_BLOCK, T)
    pad = (-T) % bq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    nblk = (T + pad) // bq
    qb = qp.reshape(k.shape[0], group, nblk, bq, hd).transpose(2, 0, 1, 3, 4)

    def block(args):
        qi, t0 = args                               # [Hk, group, bq, hd]
        s = jnp.einsum("kgqd,ktd->kgqt", qi, k,
                       precision=HIGHEST) / math.sqrt(hd)
        see = jnp.arange(T)[None, :] <= (t0 + jnp.arange(bq))[:, None]
        s = jnp.where(see[None, None], s, -jnp.inf)
        return jnp.einsum("kgqt,ktd->kgqd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    o = lax.map(block, (qb, jnp.arange(nblk) * bq))
    return o.transpose(1, 2, 0, 3, 4).reshape(H, T + pad, hd)[:, :T]


def full_mixer(x, p, cfg, precision="f32", gated=True):
    """``y`` of one full layer for the normed mixer input ``x [T, d]``
    (``gated`` False: the layer without its output gate)."""
    T = x.shape[0]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    heads = lambda name, n: _linear(x, p[name]["kernel"], precision).reshape(
        T, n, hd).transpose(1, 0, 2)
    o = _causal_attention(heads("q", hq), heads("k", hk), heads("v", hk))
    o = o.transpose(1, 0, 2).reshape(T, hq * hd)
    if gated:
        o = o * jax.nn.sigmoid(_linear(x, p["gate"]["kernel"], precision))
    return _linear(o, p["o"]["kernel"], precision)


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
    the final state ``[H, dk, dv]``. In the order the equation is written:
    decay the state, take what it holds along ``k`` away from ``v``, write
    the difference back along ``k``, read along ``q``."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = jnp.exp(g_t)[:, :, None] * S
        held = jnp.einsum("hk,hkv->hv", k_t, S, precision=HIGHEST)
        S = S + b_t[:, None, None] * k_t[:, :, None] * (v_t - held)[:, None]
        return S, jnp.einsum("hk,hkv->hv", q_t, S, precision=HIGHEST)

    S, o = lax.scan(step, jnp.zeros((H, dk, dv), jnp.float32),
                    (q, k, v, g, beta))
    return o, S


def kda_gates(x, p):
    """The log-decay ``g [T, C]`` (no floor) and ``beta [T, H]`` in (0, 2),
    both float32 whatever the precision."""
    C, H = p["dt_bias"].shape[0], p["A_log"].shape[0]
    f = _exact(_exact(x, p["f_down"]["kernel"]),
               p["f_up"]["kernel"]) + p["dt_bias"]
    rate = jnp.repeat(jnp.exp(p["A_log"].astype(jnp.float32)), C // H)
    return (-rate * jax.nn.softplus(f),
            2.0 * jax.nn.sigmoid(_exact(x, p["beta"]["kernel"])))


def kda_mixer(x, p, cfg, precision="f32"):
    """``y`` of one KDA layer for the normed mixer input ``x [T, d]``,
    ``HEAD_GROUP`` heads at a time."""
    T = x.shape[0]
    H, dk, _, _ = _kda(cfg)
    g, beta = kda_gates(x, p)
    g_low = _linear(x, p["g_down"]["kernel"], precision)
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
        o, _ = kda_recurrence(q, k, v, heads(g[:, cols]),
                              beta[:, h0:h0 + hg])
        o = _rms(o, p["o_norm"]["scale"], float(cfg["rms_norm_eps"]))
        gate = jax.nn.sigmoid(heads(_linear(
            g_low, p["g_up"]["kernel"][:, cols], precision)))
        y = y + _linear((o * gate).reshape(T, hg * dk),
                        p["o"]["kernel"][cols], precision)
    return y


# ---- 3. the feed-forward ----------------------------------------------------

def _swiglu(x, gate, up, down, precision):
    return _linear(jax.nn.silu(_linear(x, gate, precision))
                   * _linear(x, up, precision), down, precision)


def route(h, p, top_k, scale, norm_topk):
    """``h [T, d]`` -> (experts ``[T, k]``, weights ``[T, k]``) over the
    whole router, in float32."""
    s = jax.nn.sigmoid(_exact(h, p["router"]["kernel"]))
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
    idx, w = route(h, p, cfg["num_experts_per_tok"],
                   float(cfg["routed_scaling_factor"]),
                   bool(cfg["norm_topk_prob"]))
    ex = p["experts"]

    def add_expert(m, e_w):
        e, gate, up, down = e_w
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)      # [T]
        return m + we[:, None] * _swiglu(h, gate, up, down, precision), None

    # one expert at a time (a scan, so that one expert's float32 copy is
    # live and the program is compiled once for all of them)
    m, _ = lax.scan(add_expert, jnp.zeros_like(h),
                    (jnp.arange(count), ex["gate"], ex["up"], ex["down"]))
    if shared:
        sp = p["shared"]
        m = m + _swiglu(h, sp["gate"]["kernel"], sp["up"]["kernel"],
                        sp["down"]["kernel"], precision)
    return m


# ---- the forward --------------------------------------------------------------

def _static(cfg: dict) -> tuple:
    """The keys a layer reads, hashable (a static argument of the jit)."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "kda_gate_rank", "rms_norm_eps", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "router_num_experts", "moe_intermediate_size",
            "n_shared_experts")
    out = {k: cfg[k] for k in keys if k in cfg}
    out["experts_held"] = _held(cfg)
    out["linear_attn_config"] = tuple(sorted(
        (k, v) for k, v in cfg["linear_attn_config"].items()))
    return tuple(sorted(out.items()))


def _unstatic(cfg_t: tuple) -> dict:
    cfg = dict(cfg_t)
    cfg["linear_attn_config"] = dict(cfg["linear_attn_config"])
    return cfg


@partial(jax.jit, static_argnames=("cfg_t", "full", "precision"),
         donate_argnums=(0,))
def _attn_half(x, p, cfg_t, full, precision):
    cfg = _unstatic(cfg_t)
    mixer = full_mixer if full else kda_mixer
    return x + mixer(_rms(x, p["pre_attn_norm"]["scale"],
                          float(cfg["rms_norm_eps"])), p, cfg, precision)


@partial(jax.jit, static_argnames=("cfg_t", "precision"),
         donate_argnums=(0,))
def _mlp_half(x, p, cfg_t, precision):
    cfg = _unstatic(cfg_t)
    return x + moe_partial(
        _rms(x, p["pre_mlp_norm"]["scale"], float(cfg["rms_norm_eps"])),
        p["moe"], cfg, _held(cfg), True, precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, scale, head, eps, precision):
    return _linear(_rms(x, scale, eps), head, precision)


def forward(params, tokens, cfg, precision="f32", rows=None):
    """``tokens [T]`` -> logits ``[T, V]`` (float32), one sequence; ``rows =
    (first, count)``: the logits of those positions only."""
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    cfg_t = _static(cfg)
    for l in range(cfg["num_hidden_layers"]):
        p = params["layers"][l]
        x = _attn_half(x, p, cfg_t, is_full(cfg, l), precision)
        x = _mlp_half(x, p, cfg_t, precision)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    return _readout(x, params["norm_f"]["scale"],
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
    model here, the worst token is a flip of the router at a near-tie and
    not rounding, so the sound program's worst token reaches into the int8
    control's range, while lower precision makes a gap more frequent, which
    a mean shows (the readings at the published widths are in PERF.md
    section 6, PR 44). Each request's raw worst gap and count are printed
    beside what is reported."""
    raw = raw_token_gaps(params, prompt, served, cfg, pad_to, control)
    worst = {k: float(v.max()) for k, v in raw.items()}
    print(f"INFO solar_open2_ref: request of {len(prompt)} + "
          f"{len(served)} tokens: a token's own gap at worst {worst}, "
          f"tokens with a gap "
          f"{ {k: int((v > 0).sum()) for k, v in raw.items()} }; reported: "
          f"the request's mean", flush=True)
    return {k: [float(v.mean())] * len(v) for k, v in raw.items()}
