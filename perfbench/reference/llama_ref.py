"""Plain Mistral/Llama-style decoder in float32 ``jax.numpy``: the full
forward pass over a whole sequence, no cache, no batching, no kernels.
Imports nothing of the program.

Follows the published ``mistralai/Mistral-7B-v0.3`` description: RMSNorm
(pre-norm, float32 statistics), separate bias-free q/k/v/o projections,
rotary embeddings in the half-split (``rotate_half``) form with
``rope_theta``, grouped-query attention (query head h reads KV head
h // (H/Hk)), causal softmax(QK^T/sqrt(hd)), SwiGLU MLP
(``down(silu(gate(x)) * up(x))``), a final RMSNorm and an untied head.

Departures: weights are drawn here from the seed (normal, std 0.02 =
``initializer_range``; norms 1) IN THE SERVED TYPE (bfloat16) and handed
to the program; the reference multiplies their exact float32 values at
``Precision.HIGHEST``. No sliding window (v0.3 has none). The layers are
held stacked ``[L, ...]`` and walked one at a time so that only one
layer's float32 copy is live.

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS
for a bfloat16 cell: both operands of every linear layer rounded to
symmetric int8 or float8 e4m3 (scaled per row of the activations, per
column of the weights).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def param_spec(cfg: dict) -> dict:
    L, d, V = cfg["num_hidden_layers"], cfg["hidden_size"], cfg["vocab_size"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // H
    ff = cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    one = ("const", 1.0)
    return {
        "wte": {"embedding": ((V, d), std)},
        "blocks": {
            "attn_norm": {"scale": ((L, d), one)},
            "q": {"kernel": ((L, d, H * hd), std)},
            "k": {"kernel": ((L, d, Hk * hd), std)},
            "v": {"kernel": ((L, d, Hk * hd), std)},
            "o": {"kernel": ((L, H * hd, d), std)},
            "mlp_norm": {"scale": ((L, d), one)},
            "gate": {"kernel": ((L, d, ff), std)},
            "up": {"kernel": ((L, d, ff), std)},
            "down": {"kernel": ((L, ff, d), std)},
        },
        "norm_f": {"scale": ((d,), one)},
        "lm_head": {"kernel": ((d, V), std)},
    }


def param_dtypes(cfg: dict, served: str) -> dict:
    """The program keeps norm scales in float32 whatever the served
    type; everything else is in the served type."""
    spec = param_spec(cfg)
    return jax.tree.map(
        lambda leaf: "float32" if isinstance(leaf[1], tuple) else served,
        spec, is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))


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


@partial(jax.jit, static_argnames=("H", "Hk", "eps", "theta", "precision"))
def _layer(x, p, H, Hk, eps, theta, precision):
    T, d = x.shape
    hd = p["q"]["kernel"].shape[-1] // H
    h = _rms(x, p["attn_norm"]["scale"], eps)
    split = lambda a, n: a.reshape(T, n, hd).transpose(1, 0, 2)
    q = _rope(split(_linear(h, p["q"]["kernel"], precision), H), theta)
    k = _rope(split(_linear(h, p["k"]["kernel"], precision), Hk), theta)
    v = split(_linear(h, p["v"]["kernel"], precision), Hk)
    k = jnp.repeat(k, H // Hk, axis=0)
    v = jnp.repeat(v, H // Hk, axis=0)
    s = jnp.einsum("hqd,hkd->hqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v,
                   precision=HIGHEST)
    x = x + _linear(o.transpose(1, 0, 2).reshape(T, H * hd),
                    p["o"]["kernel"], precision)
    h = _rms(x, p["mlp_norm"]["scale"], eps)
    g = jax.nn.silu(_linear(h, p["gate"]["kernel"], precision))
    return x + _linear(g * _linear(h, p["up"]["kernel"], precision),
                       p["down"]["kernel"], precision)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, scale, head, eps, precision):
    return _linear(_rms(x, scale, eps), head, precision)


def forward(params, tokens, cfg, precision="f32"):
    """``tokens [T]`` -> logits ``[T, V]`` (float32), one sequence."""
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        x = _layer(x, p, cfg["num_attention_heads"],
                   cfg["num_key_value_heads"], eps, theta, precision)
    return _readout(x, params["norm_f"]["scale"],
                    params["lm_head"]["kernel"], eps, precision)


@jax.jit
def _gaps(logits, served):
    """Per position: the reference's best logit minus the reference's
    logit of the token that was served there."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


def served_token_gaps(params, prompt, served, cfg, pad_to=256,
                      control=()):
    """Teacher-forced over ``prompt + served``. Returns the gaps of the
    served tokens (reference's best logit minus its logit of the served
    token, one per served token) and, for each precision named in
    ``control``, the same gaps for the token that forward pass puts first
    at each of those positions."""
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
