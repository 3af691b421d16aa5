"""Plain ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash) in float32
``jax.numpy``: the full forward pass over one whole sequence in the EXPANDED
form of latent attention, no cache, no batching, no kernels. Imports nothing
of the program.

The layer, from the published ``config.json`` (``model_type:
joyai_llm_flash``), every key of which is a key of the DeepSeek-V2/V3
recipe. Residual stream ``h``, layer ``l``, ``x = RMSNorm_in(h)``:

- queries: ``c_q = RMSNorm_q(W_dq x)`` (``q_lora_rank`` wide, a learned
  scale of that width); ``q = W_uq c_q``, ``H`` heads of ``qk_nope_head_dim
  + qk_rope_head_dim`` = ``[q_nope, q_rope]``. No bias
  (``attention_bias: false``).
- compressed keys and values: ``[c_raw, k_rope_raw] = W_dkv x`` (ONE matrix
  to ``kv_lora_rank + qk_rope_head_dim``); ``c = RMSNorm_kv(c_raw)`` (a
  learned scale of ``kv_lora_rank``); ``k_rope_raw`` is ONE key of
  ``qk_rope_head_dim`` channels shared by all heads and is not normed.
- rotation of ``q_rope`` (each head) and ``k_rope_raw`` at the token's
  position over INTERLEAVED pairs: channels ``(2i, 2i+1)`` turn by ``pos *
  rope_theta ** (-2i / qk_rope_head_dim)`` (``rope_interleave: true``).
  assumed (``rope_order``): the published DeepSeek-V3 code de-interleaves
  first and then rotates halves, which is this rotation followed by one
  fixed permutation of the channels, the same for q and k, so every score
  is equal; the reference and the program both keep the interleaved order.
- expanded form: ``[k_nope, v]`` of each head ``= W_ukv c`` (to ``H x
  (qk_nope_head_dim + v_head_dim)``); ``k = [k_nope, k_rope]``; scores ``q
  . k / sqrt(qk_nope_head_dim + qk_rope_head_dim)``, causal softmax, ``o =
  softmax . v`` (``v_head_dim`` a head), ``a = W_o merge(o)``. assumed
  (``softmax_scale``): no extra softmax scale, since ``rope_scaling`` is
  null and the YaRN ``mscale`` of the family does not apply.
- block: ``h = h + a``; ``y = RMSNorm_post(h)``; ``h = h + m(y)``: the norm
  is on each sublayer's INPUT. ``logits = W_head RMSNorm_f(h)``, over the
  vocabulary slice held here.
- dense layer (``l < first_k_dense_replace``): ``m = W_down(silu(W_gate y) *
  W_up y)`` at ``intermediate_size``.
- sparse layer: ``s = sigmoid(W_r y)`` in float32 over all
  ``router_num_experts``; ``T`` = the ``num_experts_per_tok`` experts with
  the largest ``s_e + b_e`` (``b`` the ``e_score_correction_bias`` of
  ``topk_method: noaux_tc``, used for selection only; ``n_group =
  topk_group = 1``: no group limit); ``w_e = routed_scaling_factor * s_e /
  sum_{e' in T} s_e'`` (``norm_topk_prob``); ``m = sum_{e in T, e held} w_e
  E_e(y) + E_shared(y)``, every ``E`` a SwiGLU of ``moe_intermediate_size``
  (the shared one ``x n_shared_experts``). No token is dropped. assumed
  (``router_bias``): ``b`` is drawn from the seed (normal, std
  ``BIAS_STD`` = 0.01, float32): a trained buffer, and nothing is loaded.
  Training moves it until the experts' loads are even; a draw of std 0.1
  over a router drawn from the seed does the opposite (a layer's share of
  assignments on the held eighth read 5-20% from seed to seed, 9-15% at
  0.01 and with none), and since the admission's expert products cost
  what they are assigned, every seed then did another amount of work. At
  0.01 it still decides the selection wherever two scores lie close.
- assumed (``initializer_range``): 0.02 for every matrix; norm scales 1.

The cut (the configuration file states it): ONE chip of the
``deployment_chips`` that share each layer. It holds ``experts_held =
[first, count]`` of the router's experts and an equal slice of the
vocabulary; what the absent experts would have added is left out here as in
the program, and that partial result goes on to the next layer. The
multi-token-prediction layer (``num_nextn_predict_layers``) is left out: it
only drafts. ``moe_partial`` with ``held=None`` and all experts' weights is
the uncut layer: the share test adds the shares up to it.

Departures: weights are drawn here from the seed IN THE SERVED TYPE and
handed to the program; the reference multiplies their exact float32 values
at ``Precision.HIGHEST``. One layer is walked at a time and an expert's
weights are cast one expert at a time; attention runs one block of queries
at a time (all heads), so that the scores of a 16k + 3k-token request fit.
The absorbed decode form is NOT here: the program's equality with this
expanded form is what the tests and the cell's check hold it to.

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS for
a bfloat16 cell: both operands of every linear layer (the low-rank
projections, ``W_ukv``, ``W_o``, dense and expert SwiGLUs, the shared expert,
the head) rounded to symmetric int8 or float8 e4m3, scaled per row of the
activations and per column of the weights. The router stays in float32 in
the controls too (a deployment at a lower precision keeps it so).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
BIAS_STD = 0.01
Q_BLOCK = 512


def _held(cfg: dict) -> tuple:
    first, count = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return int(first), int(count)


def _router_width(cfg: dict) -> int:
    return cfg.get("router_num_experts") or cfg["n_routed_experts"]


def _is_dense(cfg: dict, l: int) -> bool:
    return l < cfg["first_k_dense_replace"]


def layer_spec(cfg: dict, l: int) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    std = cfg.get("initializer_range", 0.02)
    one = ("const", 1.0)
    lin = lambda i, o: {"kernel": ((i, o), std)}
    p = {"q_down": lin(d, ql), "q_norm": {"scale": ((ql,), one)},
         "q_up": lin(ql, H * (n + r)),
         "kv_down": lin(d, kvl + r), "kv_norm": {"scale": ((kvl,), one)},
         "kv_up": lin(kvl, H * (n + v)),
         "o": lin(H * v, d),
         "pre_attn_norm": {"scale": ((d,), one)},
         "pre_mlp_norm": {"scale": ((d,), one)}}
    if _is_dense(cfg, l):
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


def rope_interleaved(x, theta):
    """``x [..., T, r]`` rotated at positions 0..T-1 over interleaved
    pairs: ``(x[2i], x[2i+1])`` by the angle ``pos * theta ** (-2i / r)``."""
    T, r = x.shape[-2:]
    inv = theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv      # [T, r/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


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


def _attention(q, k, v):
    """``q``/``k [H, T, dq]``, ``v [H, T, dv]``: causal softmax attention,
    one block of ``Q_BLOCK`` queries (all heads) at a time."""
    H, T, dq = q.shape
    bq = min(Q_BLOCK, T)
    pad = (-T) % bq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    j = jnp.arange(T)[None, :]

    def block(args):
        qb, i0 = args                              # [H, bq, dq], scalar
        s = jnp.einsum("hqd,hkd->hqk", qb, k,
                       precision=HIGHEST) / math.sqrt(dq)
        see = j <= (i0 + jnp.arange(bq))[:, None]
        s = jnp.where(see[None], s, -jnp.inf)
        return jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v,
                          precision=HIGHEST)

    nblk = (T + pad) // bq
    o = lax.map(block, (qp.reshape(H, nblk, bq, dq).transpose(1, 0, 2, 3),
                        jnp.arange(nblk) * bq))
    return o.transpose(1, 0, 2, 3).reshape(H, T + pad, -1)[:, :T]


def latent_of(x, p, cfg, precision="f32"):
    """What a latent cache keeps of each token of ``x [T, d]`` (the normed
    mixer input): ``[c, k_rope]`` after norm and rotation, ``[T,
    kv_lora_rank + qk_rope_head_dim]``."""
    kvl = cfg["kv_lora_rank"]
    ckv = _linear(x, p["kv_down"]["kernel"], precision)
    c = _rms(ckv[:, :kvl], p["kv_norm"]["scale"],
             float(cfg["rms_norm_eps"]))
    return jnp.concatenate(
        [c, rope_interleaved(ckv[:, kvl:], float(cfg["rope_theta"]))], -1)


def attention_expanded(x, p, cfg, precision="f32"):
    """``a`` of one layer for the normed mixer input ``x [T, d]``: latent
    attention in the expanded form."""
    T = x.shape[0]
    H = cfg["num_attention_heads"]
    n, r, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"])
    kvl = cfg["kv_lora_rank"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    cq = _rms(_linear(x, p["q_down"]["kernel"], precision),
              p["q_norm"]["scale"], eps)
    q = _linear(cq, p["q_up"]["kernel"], precision).reshape(
        T, H, n + r).transpose(1, 0, 2)
    q = jnp.concatenate([q[..., :n], rope_interleaved(q[..., n:], theta)],
                        -1)
    lat = latent_of(x, p, cfg, precision)
    kv = _linear(lat[:, :kvl], p["kv_up"]["kernel"], precision).reshape(
        T, H, n + vd).transpose(1, 0, 2)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(lat[None, :, kvl:], (H, T, r))], -1)
    o = _attention(q, k, kv[..., n:])
    return _linear(o.transpose(1, 0, 2).reshape(T, H * vd),
                   p["o"]["kernel"], precision)


@partial(jax.jit, static_argnames=("cfg_t", "precision"))
def _attn_half(h, p, cfg_t, precision):
    """``h + attention(RMSNorm_in(h))``."""
    cfg = dict(cfg_t)
    x = _rms(h, p["pre_attn_norm"]["scale"], float(cfg["rms_norm_eps"]))
    return h + attention_expanded(x, p, cfg, precision)


@partial(jax.jit, static_argnames=("cfg_t", "dense", "precision"))
def _mlp_half(h, p, cfg_t, dense, precision):
    """``h + mlp(RMSNorm_post(h))``."""
    cfg = dict(cfg_t)
    y = _rms(h, p["pre_mlp_norm"]["scale"], float(cfg["rms_norm_eps"]))
    if dense:
        m = _swiglu(y, p["gate"]["kernel"], p["up"]["kernel"],
                    p["down"]["kernel"], precision)
    else:
        m = moe_partial(y, p["moe"], cfg, _held(cfg), True, precision)
    return h + m


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, scale, head, eps, precision):
    return _linear(_rms(x, scale, eps), head, precision)


def _static(cfg: dict) -> tuple:
    """The keys the layer reads, hashable (a static argument of the jit)."""
    keys = ("num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta", "rms_norm_eps", "n_routed_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "router_num_experts")
    out = {k: cfg[k] for k in keys if k in cfg}
    out["experts_held"] = _held(cfg)
    return tuple(sorted(out.items()))


def forward(params, tokens, cfg, precision="f32"):
    """``tokens [T]`` -> logits ``[T, V]`` (float32), one sequence."""
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    cfg_t = _static(cfg)
    for l in range(cfg["num_hidden_layers"]):
        p = params["layers"][l]
        x = _attn_half(x, p, cfg_t, precision)
        x = _mlp_half(x, p, cfg_t, _is_dense(cfg, l), precision)
    return _readout(x, params["norm_f"]["scale"],
                    params["lm_head"]["kernel"], float(cfg["rms_norm_eps"]),
                    precision)


def near_tie_share(params, tokens, cfg, margin=1e-3):
    """Share of ``tokens [T]`` with a sparse layer whose last expert chosen
    and first left out (of ``s + b``, in the float32 forward) lie within
    ``margin`` of each other."""
    cfg_t = _static(cfg)
    k = cfg["num_experts_per_tok"]
    eps = float(cfg["rms_norm_eps"])
    x = params["wte"]["embedding"][tokens].astype(jnp.float32)
    near = jnp.zeros(x.shape[:1], bool)
    for l in range(cfg["num_hidden_layers"]):
        p = params["layers"][l]
        x = _attn_half(x, p, cfg_t, "f32")
        if not _is_dense(cfg, l):
            y = _rms(x, p["pre_mlp_norm"]["scale"], eps)
            s = jax.nn.sigmoid(jnp.matmul(
                y, p["moe"]["router"]["kernel"].astype(jnp.float32),
                precision=HIGHEST)) + p["moe"]["router_bias"]
            top = lax.top_k(s, k + 1)[0]
            near = near | ((top[:, k - 1] - top[:, k]) < margin)
        x = _mlp_half(x, p, cfg_t, _is_dense(cfg, l), "f32")
    return float(jnp.mean(near))


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
    OF ITS REQUEST's served tokens (:func:`raw_token_gaps` has each token's
    own), so that the worst the runner takes is the worst request's mean;
    the same for each control precision.

    Why a request's mean and not a token's own gap: as in every routed
    model here, the worst token is a flip of the router at a near-tie and
    not rounding, so the worst token's gap of the sound program reaches
    into the int8 control's range, while lower precision makes a gap more
    frequent, which a mean shows (the readings at the published widths are
    in PERF.md section 2, PR 32). Each request's raw worst gap and count
    are printed beside what is reported."""
    raw = raw_token_gaps(params, prompt, served, cfg, pad_to, control)
    worst = {k: float(v.max()) for k, v in raw.items()}
    print(f"INFO joyai_llm_flash_ref: request of {len(prompt)} + "
          f"{len(served)} tokens: a token's own gap at worst {worst}, "
          f"tokens with a gap "
          f"{ {k: int((v > 0).sum()) for k, v in raw.items()} }; reported: "
          f"the request's mean", flush=True)
    return {k: [float(v.mean())] * len(v) for k, v in raw.items()}
