"""Plain ``zaya`` decoder (ZAYA1-8B) in float32 ``jax.numpy``: the full
forward pass over ONE WHOLE SEQUENCE, real convolutions over the sequence,
a real shift, no cache, no tail, no batching, no kernels. Imports nothing
of the program.

The layer, from the published ``config.json`` (``model_type: zaya``), the
switches its sibling ``ZAYA1-base`` publishes (``cca``, ``zaya_use_eda``,
``zaya_use_mod``, ``scale_residual_merge``) and the two public
descriptions (Compressed Convolutional Attention, arXiv:2510.04476; the
ZAYA1 technical report, arXiv:2511.17127). Sizes at the published widths:
``d`` 2048, ``Hq`` 8 query and ``Hk`` 2 key/value heads of ``hd`` 128,
group ``G = Hq / Hk`` 4, ``C = (Hq + Hk) hd`` 1280. No projection has a
bias except where said.

The residual stream is a sequence of MERGES: ``h_0 = s_0 (Emb(t) + b_0)``;
for sublayer ``j = 1..2L`` (odd: attention, even: experts), ``x =
RMSNorm_j(h_{j-1})``, ``h_j = sr_j (h_{j-1} + br_j) + sy_j (f_j(x) +
by_j)``, all element-wise over ``d``; ``logits = Emb^T RMSNorm_f(h_2L)``
(``tie_word_embeddings``). assumed (``residual_merge``): the affine form
``scale (value + bias)``, each merge's four vectors stored with its own
sublayer. assumed (``scale_parameterisation``): every learned scale is
stored as its OFFSET from the neutral value (``s = 1 + w`` for merges and
``tau``, ``gamma = 0.5 + w``), so that a stored zero is the identity and
the seeded draws (normal, zero mean) give ``1 + N(0, 0.02)``, ``1 + N(0,
0.1)``, ``N(0.5, 0.1)``. assumed (``merge_draw``): the merges' BIASES are
drawn at std ``MERGE_BIAS_STD`` = 0.002, a tenth of the embedding's 0.02 a
channel: at 0.02 (ISSUE 36's draw) half of ``h_0`` is one constant vector,
attention over a long context averages the tokens' own part away and keeps
the constant, and every row serves the same token whatever its prompt
(measured on the chip, PR 36: not one served token with a gap, every tick
routed alike), so that the comparison compares nothing. assumed
(``stream_draw``): a seeded model is drawn so that it behaves like a
trained one where the measurement reads it. ``W_o`` is drawn at
``initializer_range / sqrt(2 x 40)`` (the scaled init of output
projections in the Megatron-style code the family was trained with): at
0.02 the attention sublayer passes whatever is CONSTANT in its normed
input at a gain of 0.58 while a long context averages the tokens' own part
away, so the stream collapses onto one vector within three layers and
every token of every row takes the same expert. The experts'
down-projections are drawn at three times ``initializer_range``: what a
token's own expert adds then outgrows its embedding (0.37 against 0.02 a
channel after ten layers), as in a trained model, so that the TIED head
does not read the input token back with a margin no rounding can cross (at
0.02 it does for 97% of positions and the comparison compares nothing). On
the CPU at the published widths (2048 tokens, ten layers, vocabulary 8192,
experts 256 wide; PR 36): skip 4-9% a layer after the first, no expert over
15%, 1770 distinct next tokens of 2048, the int8 control's tokens with a
gap 45%.

Attention sublayer, CCA (token ``t`` of the sequence; an index below 0 is
zero):

- ``qr_t = W_q x_t`` (``Hq hd``), ``kr_t = W_k x_t`` (``Hk hd``), ``u_t =
  [qr_t; kr_t]`` (``C``).
- two causal convolutions over the sequence (``cca_time0`` 2,
  ``cca_time1`` 2). Depth-wise: ``a_t[c] = w0[c, 0] u_{t-1}[c] + w0[c, 1]
  u_t[c] + c0[c]``. Grouped by head (``Hq + Hk`` groups of ``hd``
  channels): ``m_t[g] = W1_g[0] a_{t-1}[g] + W1_g[1] a_t[g] + c1[g]``.
  assumed (``conv_padding``): the sequence is padded ONCE, on the left, by
  ``(cca_time0 - 1) + (cca_time1 - 1)`` zero vectors and neither
  convolution pads again, so ``a_{-1} = c0`` and not 0. Both have a bias.
- query-key mean from the PRE-convolution heads: ``mq_t[i] = (qr_t[i] +
  kr_t[i // G]) / 2``; ``mk_t[g] = (mean_{i in g} qr_t[i] + kr_t[g]) / 2``;
  ``q_t = m_t[:Hq hd] + mq_t``, ``k_t = m_t[Hq hd:] + mk_t``.
- every head of ``q`` and ``k`` scaled to length ``sqrt(hd)`` (``sqrt(hd) q
  / sqrt(|q|^2 + eps)``, ``eps`` 1e-5), ``k`` times ``tau_g``, one learned
  number a key head. assumed (``key_temperature``): ``tau`` multiplies as
  it is; no learned vector scale beside it.
- rotation of the first ``partial_rotary_factor hd`` channels of every
  head of ``q`` and ``k``, half-split pairs ``(i, i + r/2)``, angle ``pos
  theta^(-2i/r)`` (``rope_parameters.hybrid``: theta 5e6); the rest pass.
- values, shifted: ``v_t = [W_v1 x_t; W_v2 x_{t-1}]``. assumed
  (``value_shift``): the split is by HEAD (the first ``Hk / 2`` heads
  current, the rest of the token before), not by channel inside a head.
- causal softmax of ``q_i . k_{i // G} / sqrt(hd)`` over tokens ``0..t``,
  ``f = W_o merge(o)``.

Expert sublayer, ``y = RMSNorm(h)``, ``rs_prev`` the router state handed up
by the layer below (zero into layer 0): ``rs = W_d y + c_d + gamma
rs_prev`` (``router_hidden_size`` wide; ``zaya_use_eda``); ``z =
RMSNorm_r(rs)``; ``l = W_3 gelu(W_2 gelu(W_1 z + c_1) + c_2)``; ``p =
softmax(l)`` over ``num_experts + 1`` choices, the last one SKIP
(``zaya_use_mod``); ``e = argmax(p + beta)``; the output is ``p_e E_e(y)``
for an expert and 0 for skip, ``E`` a SwiGLU of ``moe_intermediate_size``.
assumed (``router``): ``gamma`` per channel, the RMSNorm before the MLP,
GELU in its tanh form, ``beta`` a balancing bias for selection only
(drawn at std ``BIAS_STD``), the weight ``p_e`` not renormalised, no token
dropped. assumed (``router_draw``): nothing is loaded, and a trained
router's loads are even, so the MLP is drawn to choose near uniformly
over the 17: ``W_1`` at an eighth of unit gain (a GELU is then close to
its linear part, whose output has no mean; at unit gain the mean of the
activations gives every token the same few favourites and the skip
choice's share swings from seed to seed, and the admission's work with
it), ``W_2`` at unit gain, ``W_3`` at 32 times (logits of spread ~1), the
two biases at std ``FC_BIAS_STD`` (a larger constant is a favourite too).

The cut (the configuration file states it): one stage of a four-stage
pipeline, ``num_hidden_layers`` of the 40, every expert held, the whole
tied vocabulary. ``moe_layer`` is the uncut layer (held = all).

Departures: weights are drawn from the seed IN THE SERVED TYPE and handed
to the program; the reference multiplies their exact float32 values at
``Precision.HIGHEST``. One layer is walked at a time, an expert's weights
are cast one expert at a time, attention runs one block of queries at a
time so that a request of 32k + 384 tokens fits, and the read-out is taken
at the positions asked for only (262,272 logits a position).

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS
for a bfloat16 cell: both operands of every linear layer (``W_q``, ``W_k``,
``W_v1``, ``W_v2``, ``W_o``, the experts' SwiGLUs, the read-out) rounded to
symmetric int8 or float8 e4m3, scaled per row of the activations and per
column of the weights. The convolutions and the router stay in float32 in
the controls too.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BIAS_STD = 0.01          # beta, the router's selection bias
MERGE_STD = 0.02         # merge scales' offsets from 1
MERGE_BIAS_STD = 0.002   # merge biases (merge_draw)
TAU_STD = 0.1            # key temperature's offset
GAMMA_STD = 0.1          # depth-averaging coefficient's offset from 0.5
FC_BIAS_STD = 0.002      # the router MLP's two biases (router_draw)
O_GAIN = 80 ** -0.5      # W_o against initializer_range (stream_draw)
DOWN_GAIN = 3.0          # the experts' down-projections (stream_draw)
Q_BLOCK = 512


def _sizes(cfg: dict) -> tuple:
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return hq, hk, hd, (hq + hk) * hd


def _choices(cfg: dict) -> int:
    """The router's width: every expert and the skip choice."""
    return cfg["num_experts"] + 1


def _merge_spec(d: int) -> dict:
    return {"res_scale": ((d,), MERGE_STD),
            "res_bias": ((d,), MERGE_BIAS_STD),
            "out_scale": ((d,), MERGE_STD),
            "out_bias": ((d,), MERGE_BIAS_STD)}


def layer_spec(cfg: dict) -> dict:
    d, f, R = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["router_hidden_size"])
    hq, hk, hd, C = _sizes(cfg)
    E = cfg["num_experts"]
    std = cfg.get("initializer_range", 0.02)
    one = ("const", 1.0)
    lin = lambda i, o: {"kernel": ((i, o), std)}
    linb = lambda i, o: {"kernel": ((i, o), std), "bias": ((o,), std)}
    return {
        "pre_attn_norm": {"scale": ((d,), one)},
        "attn_merge": _merge_spec(d),
        "q": lin(d, hq * hd), "k": lin(d, hk * hd),
        "v_cur": lin(d, hk * hd // 2), "v_prev": lin(d, hk * hd // 2),
        # taps of width cca_time0 / cca_time1, the earlier token first
        "conv0": {"kernel": ((cfg["cca_time0"], C), 0.5),
                  "bias": ((C,), std)},
        "conv1": {"kernel": ((cfg["cca_time1"], hq + hk, hd, hd),
                             (2 * hd) ** -0.5),
                  "bias": ((C,), std)},
        "k_temp": ((hk,), TAU_STD),
        "o": {"kernel": ((hq * hd, d), std * O_GAIN)},
        "pre_mlp_norm": {"scale": ((d,), one)},
        "mlp_merge": _merge_spec(d),
        "moe": {
            "router": {"down": linb(d, R), "gamma": ((R,), GAMMA_STD),
                       "norm": {"scale": ((R,), one)},
                       "fc1": {"kernel": ((R, R), 0.125 * R ** -0.5),
                               "bias": ((R,), FC_BIAS_STD)},
                       "fc2": {"kernel": ((R, R), R ** -0.5),
                               "bias": ((R,), FC_BIAS_STD)},
                       "out": {"kernel": ((R, _choices(cfg)),
                                          32 * R ** -0.5)}},
            "router_bias": ((_choices(cfg),), BIAS_STD),
            "experts": {"gate": ((E, d, f), std), "up": ((E, d, f), std),
                        "down": ((E, f, d), std * DOWN_GAIN)}},
    }


def param_spec(cfg: dict) -> dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    return {
        "wte": {"embedding": ((V, d), std)},
        "embed_merge": {"scale": ((d,), MERGE_STD),
                        "bias": ((d,), MERGE_BIAS_STD)},
        "layers": [layer_spec(cfg) for _ in range(cfg["num_hidden_layers"])],
        "norm_f": {"scale": ((d,), ("const", 1.0))},
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


FLOAT32_LEAVES = ("router_bias", "k_temp")


def param_dtypes(cfg: dict, served: str) -> dict:
    """Norm scales, the router's selection bias and the key temperature
    are float32 whatever the served type (the program keeps them so); the
    rest is served."""
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


def _linear(x, w, precision):
    w = w.astype(jnp.float32)
    if precision != "f32":
        x, w = _fq(x, -1, precision), _fq(w, 0, precision)
    return jnp.matmul(x, w, precision=HIGHEST)


def _f32(a):
    return a.astype(jnp.float32)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        _f32(scale)


def merge(h, f, p):
    """``sr (h + br) + sy (f + by)``, the scales stored as offsets from 1."""
    return ((1.0 + _f32(p["res_scale"])) * (h + _f32(p["res_bias"]))
            + (1.0 + _f32(p["out_scale"])) * (f + _f32(p["out_bias"])))


def rope_partial(x, theta, rotary_dim):
    """``x [..., T, hd]`` at positions 0..T-1: the first ``rotary_dim``
    channels rotated as half-split pairs ``(i, i + rotary_dim / 2)`` by
    ``pos * theta ** (-2i / rotary_dim)``; the rest pass through."""
    T = x.shape[-2]
    half = rotary_dim // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:rotary_dim]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin,
                            x[..., rotary_dim:]], -1)


def cca_convolved(u, p, cfg):
    """``m [T, C]`` of ``u [T, C]``: the sequence padded once on the left,
    then the depth-wise and the grouped convolution, each ``VALID``."""
    hq, hk, hd, C = _sizes(cfg)
    k0, k1 = cfg["cca_time0"], cfg["cca_time1"]
    x = jnp.pad(u, ((k0 - 1 + k1 - 1, 0), (0, 0))).T[None]     # [1, C, T+2]
    w0 = _f32(p["conv0"]["kernel"]).T[:, None, :]              # [C, 1, k0]
    a = lax.conv_general_dilated(
        x, w0, (1,), "VALID", feature_group_count=C,
        precision=HIGHEST) + _f32(p["conv0"]["bias"])[None, :, None]
    # [k1, G, in, out] -> [G * out, in, k1]
    w1 = _f32(p["conv1"]["kernel"]).transpose(1, 3, 2, 0).reshape(C, hd, k1)
    m = lax.conv_general_dilated(
        a, w1, (1,), "VALID", feature_group_count=hq + hk,
        precision=HIGHEST) + _f32(p["conv1"]["bias"])[None, :, None]
    return m[0].T


def _unit(x, eps):
    """Every vector of the last axis scaled to length sqrt(width)."""
    return x * math.sqrt(x.shape[-1]) * lax.rsqrt(
        jnp.sum(x * x, -1, keepdims=True) + eps)


def cca_qkv(x, p, cfg, precision="f32"):
    """``q [Hq, T, hd]``, ``k``/``v [Hk, T, hd]`` of the normed mixer input
    ``x [T, d]``: what attention multiplies, and what a cache would keep."""
    T = x.shape[0]
    hq, hk, hd, _ = _sizes(cfg)
    G = hq // hk
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    rd = int(hd * cfg["partial_rotary_factor"])
    qr = _linear(x, p["q"]["kernel"], precision)
    kr = _linear(x, p["k"]["kernel"], precision)
    m = cca_convolved(jnp.concatenate([qr, kr], -1), p, cfg)
    qh, kh = qr.reshape(T, hq, hd), kr.reshape(T, hk, hd)
    mq = (qh + jnp.repeat(kh, G, axis=1)) / 2
    mk = (qh.reshape(T, hk, G, hd).mean(2) + kh) / 2
    q = m[:, :hq * hd].reshape(T, hq, hd) + mq
    k = m[:, hq * hd:].reshape(T, hk, hd) + mk
    tau = 1.0 + _f32(p["k_temp"])
    q = _unit(q, eps).transpose(1, 0, 2)
    k = (_unit(k, eps) * tau[None, :, None]).transpose(1, 0, 2)
    q, k = rope_partial(q, theta, rd), rope_partial(k, theta, rd)
    x_prev = jnp.pad(x, ((1, 0), (0, 0)))[:-1]                 # the shift
    v = jnp.concatenate([_linear(x, p["v_cur"]["kernel"], precision),
                         _linear(x_prev, p["v_prev"]["kernel"], precision)],
                        -1)
    return q, k, v.reshape(T, hk, hd).transpose(1, 0, 2)


def _attention(q, k, v):
    """``q [Hq, T, hd]``, ``k``/``v [Hk, T, hd]``: causal softmax
    attention, query head ``i`` on key head ``i // G``, one block of
    ``Q_BLOCK`` queries (all heads) at a time."""
    Hq, T, hd = q.shape
    G = Hq // k.shape[0]
    k, v = jnp.repeat(k, G, axis=0), jnp.repeat(v, G, axis=0)
    bq = min(Q_BLOCK, T)
    pad = (-T) % bq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    j = jnp.arange(T)[None, :]

    def block(args):
        qb, i0 = args
        s = jnp.einsum("hqd,hkd->hqk", qb, k,
                       precision=HIGHEST) / math.sqrt(hd)
        see = j <= (i0 + jnp.arange(bq))[:, None]
        s = jnp.where(see[None], s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    nblk = (T + pad) // bq
    o = lax.map(block, (qp.reshape(Hq, nblk, bq, hd).transpose(1, 0, 2, 3),
                        jnp.arange(nblk) * bq))
    return o.transpose(1, 0, 2, 3).reshape(Hq, T + pad, hd)[:, :T]


def cca_attention(x, p, cfg, precision="f32"):
    """``f`` of one attention sublayer for the normed input ``x [T, d]``."""
    q, k, v = cca_qkv(x, p, cfg, precision)
    o = _attention(q, k, v)
    return _linear(o.transpose(1, 0, 2).reshape(x.shape[0], -1),
                   p["o"]["kernel"], precision)


def _swiglu(x, gate, up, down, precision):
    g = jax.nn.silu(_linear(x, gate, precision))
    return _linear(g * _linear(x, up, precision), down, precision)


def router_probs(y, rs_prev, p, cfg):
    """``y [T, d]``, ``rs_prev [T, R]`` -> (``p [T, E + 1]`` float32, the
    state ``rs [T, R]`` this layer hands up)."""
    r = p["router"]
    mm = lambda a, w: jnp.matmul(a, _f32(w), precision=HIGHEST)
    rs = (mm(y, r["down"]["kernel"]) + _f32(r["down"]["bias"])
          + (0.5 + _f32(r["gamma"])) * rs_prev)
    z = _rms(rs, r["norm"]["scale"], float(cfg["rms_norm_eps"]))
    g = lambda a: jax.nn.gelu(a, approximate=True)
    z = g(mm(z, r["fc1"]["kernel"]) + _f32(r["fc1"]["bias"]))
    z = g(mm(z, r["fc2"]["kernel"]) + _f32(r["fc2"]["bias"]))
    return jax.nn.softmax(mm(z, r["out"]["kernel"]), -1), rs


def moe_layer(y, rs_prev, p, cfg, precision="f32"):
    """The expert sublayer's output for ``y [T, d]`` with EVERY expert
    (the uncut layer), and the router state handed up: ``p_e E_e(y)`` of
    the one choice ``e = argmax(p + beta)``, 0 where it is skip."""
    probs, rs = router_probs(y, rs_prev, p, cfg)
    e = jnp.argmax(probs + _f32(p["router_bias"]), -1)
    w = jnp.take_along_axis(probs, e[:, None], -1)[:, 0]
    ex = p["experts"]

    def add_expert(m, e_w):
        i, gate, up, down = e_w
        we = jnp.where(e == i, w, 0.0)
        return m + we[:, None] * _swiglu(y, gate, up, down, precision), None

    # one expert at a time (a scan: one expert's float32 copy is live)
    m, _ = lax.scan(add_expert, jnp.zeros_like(y),
                    (jnp.arange(cfg["num_experts"]), ex["gate"], ex["up"],
                     ex["down"]))
    return m, rs


@partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _attn_half(h, p, cfg_t, precision):
    cfg = dict(cfg_t)
    x = _rms(h, p["pre_attn_norm"]["scale"], float(cfg["rms_norm_eps"]))
    return merge(h, cca_attention(x, p, cfg, precision), p["attn_merge"])


@partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _mlp_half(h, rs_prev, p, cfg_t, precision):
    cfg = dict(cfg_t)
    y = _rms(h, p["pre_mlp_norm"]["scale"], float(cfg["rms_norm_eps"]))
    m, rs = moe_layer(y, rs_prev, p["moe"], cfg, precision)
    return merge(h, m, p["mlp_merge"]), rs


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, scale, emb, eps, precision):
    return _linear(_rms(x, scale, eps), emb.T, precision)


def _static(cfg: dict) -> tuple:
    """The keys the layer reads, hashable (a static argument of the jit)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "cca_time0", "cca_time1", "partial_rotary_factor", "rope_theta",
            "rms_norm_eps", "num_experts", "router_hidden_size")
    return tuple(sorted((k, cfg[k]) for k in keys))


def embed(params, tokens):
    e = params["embed_merge"]
    return (1.0 + _f32(e["scale"])) * (
        _f32(params["wte"]["embedding"][tokens]) + _f32(e["bias"]))


def forward(params, tokens, cfg, precision="f32", rows=None):
    """``tokens [T]`` -> logits (float32) of every position, or of the
    ``count`` positions from ``start`` on with ``rows = (start, count)``."""
    x = embed(params, tokens)
    rs = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), jnp.float32)
    cfg_t = _static(cfg)
    for p in params["layers"]:
        x = _attn_half(x, p, cfg_t, precision)
        x, rs = _mlp_half(x, rs, p, cfg_t, precision)
    if rows is not None:
        x = x[rows[0]:rows[0] + rows[1]]
    return _readout(x, params["norm_f"]["scale"],
                    params["wte"]["embedding"], float(cfg["rms_norm_eps"]),
                    precision)


def near_tie_share(params, tokens, cfg, margin=1e-3) -> dict:
    """Shares of ``tokens [T]`` with a layer whose first and second choice
    (of ``p + beta``, in the float32 forward) lie within ``margin``
    (``"any"``) and with such a layer where one of the two is skip
    (``"skip"``: a flip there adds or removes a whole expert's output)."""
    cfg_t = _static(cfg)
    eps = float(cfg["rms_norm_eps"])
    x = embed(params, tokens)
    rs = jnp.zeros((x.shape[0], cfg["router_hidden_size"]), jnp.float32)
    near = skip = jnp.zeros(x.shape[:1], bool)
    for p in params["layers"]:
        x = _attn_half(x, p, cfg_t, "f32")
        y = _rms(x, p["pre_mlp_norm"]["scale"], eps)
        probs, _ = router_probs(y, rs, p["moe"], cfg)
        top, idx = lax.top_k(probs + _f32(p["moe"]["router_bias"]), 2)
        tie = (top[:, 0] - top[:, 1]) < margin
        near = near | tie
        skip = skip | (tie & jnp.any(idx == cfg["num_experts"], -1))
        x, rs = _mlp_half(x, rs, p, cfg_t, "f32")
    return {"any": float(jnp.mean(near)), "skip": float(jnp.mean(skip))}


@jax.jit
def _gaps(logits, served):
    """Per position: the reference's best logit minus the reference's
    logit of the token that was served there."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


def raw_token_gaps(params, prompt, served, cfg, pad_to=256, control=()):
    """Teacher-forced over ``prompt + served``: for every served token
    (the request's FIRST among them: it is the one a stale, shifted or
    foreign tail would move) the reference's best logit at its position
    minus the reference's logit of the token itself (``"served"``) and, per
    precision named in ``control``, the same for the token that forward
    pass puts first."""
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

    Why a request's mean: the argmax over 17 softmax values has near-ties
    of its own, and a token that flips to or from SKIP gains or loses a
    whole expert's output, so the sound program's worst token reaches into
    the int8 control's range while lower precision makes a gap more
    frequent, which a mean shows (the readings at the published widths are
    in PERF.md section 2, PR 36). Each request's raw worst gap, its count
    and its FIRST token's gap are printed beside what is reported."""
    raw = raw_token_gaps(params, prompt, served, cfg, pad_to, control)
    worst = {k: float(v.max()) for k, v in raw.items()}
    print(f"INFO zaya_ref: request of {len(prompt)} + {len(served)} "
          f"tokens: a token's own gap at worst {worst}, the first served "
          f"token's {float(raw['served'][0]):.5f}, tokens with a gap "
          f"{ {k: int((v > 0).sum()) for k, v in raw.items()} }; reported: "
          f"the request's mean", flush=True)
    return {k: [float(v.mean())] * len(v) for k, v in raw.items()}
