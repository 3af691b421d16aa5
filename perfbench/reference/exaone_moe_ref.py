"""Plain ``exaone_moe`` decoder (K-EXAONE) in float32 ``jax.numpy``: the
full forward pass over one whole sequence, no cache, no batching, no
kernels. Imports nothing of the program.

The layer, from the published ``config.json`` (``model_type:
exaone_moe``) and, where it is silent, the EXAONE 4.0 family's published
convention (each such item is ``assumed`` and is in the configuration
file's ``assumed`` too). Residual stream ``h``, layer ``l``:

- ``q = W_q h``, ``k = W_k h``, ``v = W_v h`` split into ``H`` query and
  ``Hk`` KV heads of ``head_dim`` channels; no bias (assumed: the config
  has no bias key, the family has none).
- assumed (EXAONE 4.0): ``q`` and ``k`` each pass an RMSNorm over the
  head's channels (one learned scale of ``head_dim`` for q, one for k)
  before any rotation; rotation (half-split ``rotate_half`` form,
  ``rope_theta``) on SLIDING layers only, full layers rotate nothing.
- scores ``q.k / sqrt(head_dim)``, causal; on a sliding layer position
  ``i`` sees ``j`` with ``i - window < j <= i`` (assumed: the window counts
  the token itself, as the family's published modelling code does).
  Query head ``h`` reads KV head ``h // (H / Hk)``.
  ``a = W_o merge(softmax(scores) v)``.
- assumed (EXAONE 4.0): no norm on a sublayer's input;
  ``h = h + RMSNorm_attn(a)``, then ``h = h + RMSNorm_mlp(m)``.
- dense layer (``mlp_layer_types[l] == "dense"``):
  ``m = W_down(silu(W_gate h) * W_up h)``.
- sparse layer: ``s = sigmoid(W_r h)`` in float32 over all
  ``router_num_experts``; ``T`` = the ``num_experts_per_tok`` experts with
  the largest ``s_e + b_e`` (``b`` a per-expert selection bias: assumed
  from the ``n_group`` / ``topk_group`` keys of the aux-loss-free router,
  drawn from the seed so that selection and weighting orders differ);
  ``w_e = routed_scaling_factor * s_e / sum_{e' in T} s_e'``
  (``norm_topk_prob``); ``m = sum_{e in T, e held} w_e E_e(h) +
  E_shared(h)``, every ``E`` a SwiGLU. No token is dropped, no capacity.
  ``n_group = topk_group = 1``: no group limit.
- ``logits = W_head RMSNorm_f(h)``, over the vocabulary slice held here.

The cut (the configuration file states it): this is ONE chip of the
``deployment_chips`` that share each layer. It holds ``experts_held =
[first, count]`` of the router's experts and an equal slice of the
vocabulary; what the absent experts would have added is left out here as
in the program, and that partial result goes on to the next layer. The
multi-token-prediction layer is left out (it only drafts).
``moe_partial`` with ``held=None`` and all experts' weights is the uncut
layer: the share test adds the shares up to it.

Departures: weights are drawn here from the seed (normal, std 0.02; norm
scales 1; the selection bias normal std 0.1, float32) IN THE SERVED TYPE
and handed to the program; the reference multiplies their exact float32
values at ``Precision.HIGHEST``. One layer is walked at a time and an
expert's weights are cast one expert at a time, so that one layer's
float32 copy at most is live beside the served weights; attention runs
one KV head's group at a time so that the scores of a 5k-token sequence
fit.

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS
for a bfloat16 cell: both operands of every linear layer (projections,
dense and expert SwiGLUs, the shared expert, the head) rounded to
symmetric int8 or float8 e4m3, scaled per row of the activations and per
column of the weights. The router stays in float32 in the controls too
(a deployment at a lower precision keeps it so): the control measures
the arithmetic of the layers, not a second routing.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BIAS_STD = 0.1


def _held(cfg: dict) -> tuple:
    first, count = cfg.get("experts_held") or (0, cfg["num_experts"])
    return int(first), int(count)


def _router_width(cfg: dict) -> int:
    return cfg.get("router_num_experts") or cfg["num_experts"]


def layer_spec(cfg: dict, l: int) -> dict:
    d = cfg["hidden_size"]
    H, Hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    std = cfg.get("initializer_range", 0.02)
    one = ("const", 1.0)
    lin = lambda i, o: {"kernel": ((i, o), std)}
    p = {"q": lin(d, H * hd), "k": lin(d, Hk * hd), "v": lin(d, Hk * hd),
         "o": lin(H * hd, d),
         "q_norm": {"scale": ((hd,), one)},
         "k_norm": {"scale": ((hd,), one)},
         "post_attn_norm": {"scale": ((d,), one)},
         "post_mlp_norm": {"scale": ((d,), one)}}
    if cfg["mlp_layer_types"][l] == "dense":
        ff = cfg["intermediate_size"]
        p.update(gate=lin(d, ff), up=lin(d, ff), down=lin(ff, d))
    else:
        f = cfg["moe_intermediate_size"]
        sf = f * cfg["num_shared_experts"]
        n = _held(cfg)[1]
        p["moe"] = {
            "router": lin(d, _router_width(cfg)),
            "router_bias": ((_router_width(cfg),), BIAS_STD),
            "experts": {"gate": ((n, d, f), std), "up": ((n, d, f), std),
                        "down": ((n, f, d), std)},
            "shared": {"gate": lin(d, sf), "up": lin(d, sf),
                       "down": lin(sf, d)}}
    return p


def param_spec(cfg: dict) -> dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    return {
        "wte": {"embedding": ((V, d), std)},
        "layers": [layer_spec(cfg, l)
                   for l in range(cfg["num_hidden_layers"])],
        "norm_f": {"scale": ((d,), ("const", 1.0))},
        "lm_head": {"kernel": ((d, V), std)},
    }


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def param_dtypes(cfg: dict, served: str) -> dict:
    """Norm scales and the router's selection bias are float32 whatever
    the served type (the program keeps them so); the rest is served."""
    def walk(node, name=""):
        if _is_leaf(node):
            keep32 = isinstance(node[1], tuple) or name == "router_bias"
            return "float32" if keep32 else served
        if isinstance(node, list):
            return [walk(x) for x in node]
        return {k: walk(v, k) for k, v in node.items()}
    return walk(param_spec(cfg))


def _fq(x, axis, kind):
    if kind == "bf16":       # the served precision itself: a twin, no control
        return x.astype(jnp.bfloat16).astype(jnp.float32)
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


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * \
        scale.astype(jnp.float32)


def _rope(x, theta):
    """``x [H, T, hd]`` rotated at positions 0..T-1, half-split form."""
    H, T, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _swiglu(x, gate, up, down, precision):
    g = jax.nn.silu(_linear(x, gate, precision))
    return _linear(g * _linear(x, up, precision), down, precision)


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
    idx, w = route(h, p, cfg["num_experts_per_tok"],
                   float(cfg["routed_scaling_factor"]),
                   bool(cfg["norm_topk_prob"]))
    m = jnp.zeros_like(h)
    ex = p["experts"]
    for e in range(count):
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)      # [T]
        m = m + we[:, None] * _swiglu(h, ex["gate"][e], ex["up"][e],
                                      ex["down"][e], precision)
    if shared:
        sp = p["shared"]
        m = m + _swiglu(h, sp["gate"]["kernel"], sp["up"]["kernel"],
                        sp["down"]["kernel"], precision)
    return m


def _attention(q, k, v, window):
    """``q [H, T, hd]``, ``k``/``v [Hk, T, hd]``: causal (banded where
    ``window``) softmax attention, one KV head's group at a time."""
    H, T, hd = q.shape
    Hk = k.shape[0]
    i = jnp.arange(T)[:, None]
    j = jnp.arange(T)[None, :]
    see = j <= i
    if window:
        see = see & (j > i - window)

    def group(qkv):
        qg, kg, vg = qkv                     # [G, T, hd], [T, hd], [T, hd]
        s = jnp.einsum("gqd,kd->gqk", qg, kg,
                       precision=HIGHEST) / math.sqrt(hd)
        s = jnp.where(see[None], s, -jnp.inf)
        return jnp.einsum("gqk,kd->gqd", jax.nn.softmax(s, -1), vg,
                          precision=HIGHEST)

    o = lax.map(group, (q.reshape(Hk, H // Hk, T, hd), k, v))
    return o.reshape(H, T, hd)


@partial(jax.jit, static_argnames=("cfg_t", "l", "precision"))
def _attn_half(x, p, cfg_t, l, precision):
    """``h + RMSNorm_attn(attention(h))`` of layer ``l``."""
    cfg = _cfg_of(cfg_t)
    T, d = x.shape
    H, Hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    eps = float(cfg["rms_norm_eps"])
    sliding = cfg["layer_types"][l] == "sliding_attention"
    split = lambda a, n: a.reshape(T, n, hd).transpose(1, 0, 2)
    q = split(_linear(x, p["q"]["kernel"], precision), H)
    k = split(_linear(x, p["k"]["kernel"], precision), Hk)
    v = split(_linear(x, p["v"]["kernel"], precision), Hk)
    q = _rms(q, p["q_norm"]["scale"], eps)
    k = _rms(k, p["k_norm"]["scale"], eps)
    if sliding:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    o = _attention(q, k, v, cfg["sliding_window"] if sliding else None)
    a = _linear(o.transpose(1, 0, 2).reshape(T, H * hd), p["o"]["kernel"],
                precision)
    return x + _rms(a, p["post_attn_norm"]["scale"], eps)


@partial(jax.jit, static_argnames=("cfg_t", "l", "precision"))
def _mlp_half(x, p, cfg_t, l, precision):
    """``h + RMSNorm_mlp(mlp(h))`` of layer ``l``."""
    cfg = _cfg_of(cfg_t)
    if cfg["mlp_layer_types"][l] == "dense":
        m = _swiglu(x, p["gate"]["kernel"], p["up"]["kernel"],
                    p["down"]["kernel"], precision)
    else:
        m = moe_partial(x, p["moe"], cfg, _held(cfg), True, precision)
    return x + _rms(m, p["post_mlp_norm"]["scale"],
                    float(cfg["rms_norm_eps"]))


def _layer(x, p, cfg_t, l, precision):
    return _mlp_half(_attn_half(x, p, cfg_t, l, precision), p, cfg_t, l,
                     precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, scale, head, eps, precision):
    return _linear(_rms(x, scale, eps), head, precision)


def _static(cfg: dict) -> tuple:
    """The keys the layer reads, hashable (a static argument of the jit)."""
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "sliding_window", "num_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "router_num_experts")
    out = {k: cfg[k] for k in keys if k in cfg}
    out["layer_types"] = tuple(cfg["layer_types"])
    out["mlp_layer_types"] = tuple(cfg["mlp_layer_types"])
    out["experts_held"] = _held(cfg)
    out["rope_parameters"] = tuple(sorted(cfg["rope_parameters"].items()))
    return tuple(sorted(out.items()))


def _cfg_of(cfg_t) -> dict:
    cfg = dict(cfg_t)
    cfg["rope_parameters"] = dict(cfg["rope_parameters"])
    return cfg


def forward(params, tokens, cfg, precision="f32"):
    """``tokens [T]`` -> logits ``[T, V]`` (float32), one sequence."""
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    cfg_t = _static(cfg)
    for l in range(cfg["num_hidden_layers"]):
        x = _layer(x, params["layers"][l], cfg_t, l, precision)
    return _readout(x, params["norm_f"]["scale"],
                    params["lm_head"]["kernel"], float(cfg["rms_norm_eps"]),
                    precision)


def routing_report(params, tokens, cfg, precision="bf16"):
    """How near the router's choices are to a tie, and how many flip one
    precision below: per sparse layer over ``tokens [T]``, the margin
    between the last expert chosen and the first left out (of ``s + b``,
    in the float32 forward) and whether the forward at ``precision``
    (fed its own hidden states) chooses another set. Returns ``{"margin"
    [n_sparse, T], "flipped" [n_sparse, T] bool, "flipped_held" ...}``:
    the last counts a flip only where a HELD expert enters or leaves."""
    cfg_t = _static(cfg)
    first, count = _held(cfg)
    k = cfg["num_experts_per_tok"]
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    y = x
    out = {"margin": [], "flipped": [], "flipped_held": []}
    for l in range(cfg["num_hidden_layers"]):
        p = params["layers"][l]
        x = _attn_half(x, p, cfg_t, l, "f32")
        y = _attn_half(y, p, cfg_t, l, precision)
        if cfg["mlp_layer_types"][l] == "sparse":
            rb = p["moe"]["router_bias"].astype(jnp.float32)
            score = lambda h: jax.nn.sigmoid(jnp.matmul(
                h, p["moe"]["router"]["kernel"].astype(jnp.float32),
                precision=HIGHEST)) + rb
            sx, sy = score(x), score(y)
            top = lax.top_k(sx, k + 1)[0]
            chosen = lambda sc: sc >= lax.top_k(sc, k)[0][:, -1:]
            diff = chosen(sx) != chosen(sy)
            out["margin"].append(top[:, k - 1] - top[:, k])
            out["flipped"].append(jnp.any(diff, -1))
            out["flipped_held"].append(
                jnp.any(diff[:, first:first + count], -1))
        x = _mlp_half(x, p, cfg_t, l, "f32")
        y = _mlp_half(y, p, cfg_t, l, precision)
    return jax.device_get({k_: jnp.stack(v) for k_, v in out.items()})


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
    logits = forward(params, toks, cfg, "f32")[n - 1:n - 1 + m]
    out = {"served": _gaps(logits, jnp.asarray(served, jnp.int32))}
    for prec in control:
        low = forward(params, toks, cfg, prec)[n - 1:n - 1 + m]
        out[prec] = _gaps(logits, jnp.argmax(low, -1).astype(jnp.int32))
    return jax.device_get(out)


def served_token_gaps(params, prompt, served, cfg, pad_to=256,
                      control=()):
    """What the serve runner compares: for each served token THE MEAN GAP
    OF ITS REQUEST's served tokens (:func:`raw_token_gaps` has each
    token's own), so that the worst the runner takes is the worst
    request's mean; the same for each control precision.

    Why not a token's own gap, as the dense families report it: a routed
    model's worst token is a flip of the router at a near-tie, not
    rounding. On the chip at the published widths (PERF.md section 2, PR
    28) a third of the served tokens have a layer whose 8th and 9th expert
    lie within 1e-3 of each other, 2% of them choose another held expert
    than the float32 forward does once activations are rounded to
    bfloat16, and with 16 of 128 experts held such a flip swaps one of the
    two terms of that layer's output: the worst token's gap reads
    0.42-1.01 for the sound program and 0.70-1.31 for the int8 control,
    which it therefore cannot hold out. Lower precision does not make the
    flips larger, it makes a gap five times as frequent: a request's mean
    gap reads 0.0019-0.0055 sound against 0.0187-0.0259 int8."""
    raw = raw_token_gaps(params, prompt, served, cfg, pad_to, control)
    worst = {k: float(v.max()) for k, v in raw.items()}
    print(f"INFO exaone_moe_ref: request of {len(prompt)} + {len(served)} "
          f"tokens: a token's own gap at worst {worst}, tokens with a gap "
          f"{ {k: int((v > 0).sum()) for k, v in raw.items()} }; reported: "
          f"the request's mean", flush=True)
    return {k: [float(v.mean())] * len(v) for k, v in raw.items()}
