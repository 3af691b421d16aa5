"""Plain ``sdar_moe`` decoder (SDAR-30B-A3B-Chat) in float32 ``jax.numpy``:
the full forward pass over one whole sequence under the BLOCK mask, and the
block-diffusion generation loop. No cache, no kernels, no batching. Imports
nothing of the program.

Pre-norm residual, one vector a token: ``x <- x + Attn(RMSNorm(x))``, ``x <-
x + MoE(RMSNorm(x))``, a final RMSNorm, an untied head; position ``i``'s
logits are token ``i``'s (NO shift).

1. Attention, every layer: ``q = W_q x`` (``num_attention_heads`` heads of
   ``head_dim``), ``k, v = W_k x, W_v x`` (``num_key_value_heads`` heads;
   query head ``h`` reads key-value head ``h // (heads / kv heads)``), no
   bias; an RMSNorm over each head's channels of q and of k (one learned
   scale of ``head_dim`` each, shared by the heads), then half-split
   rotation at base ``rope_theta``; softmax of ``q . k / sqrt(head_dim)``
   under the BLOCK mask: positions are cut into blocks of ``block_length``
   from position 0, prompt included, and position ``i`` attends ``j`` iff
   ``j // block_length <= i // block_length``: every earlier block and ALL
   of its own, both directions.
2. Feed-forward, every layer sparse: ``p = softmax(W_r y)`` in float32 over
   ``num_experts``, the ``num_experts_per_tok`` largest, renormalised to sum
   1 (``norm_topk_prob``), ``sum w_e E_e(y)``; every expert a SwiGLU of
   width ``moe_intermediate_size``; no shared expert, no scaling, no
   selection bias.

Generation (:func:`generate`; ``cfg["generation"]``: ``block_length``,
``denoising_steps``, ``remasking``, ``mask_token_id``) appends one block at a
time. A prompt's tail that fills no block opens the first generated block;
the block's unknown positions hold the ``[MASK]`` token. A DENOISE pass runs
the sequence so far and reads the logits at the masked positions of the
block: each takes the greedy token, and the rule unmasks ``block_length /
denoising_steps`` of them (fewer if fewer are masked): ``sequential`` the
leftmost, ``low_confidence_static`` those whose greedy token has the largest
probability (ties to the left). When none is masked the block is final (the
served path spends one more pass there, which writes the block's keys and
values; without a cache there is nothing to write) and the next block opens.
The last block is denoised whole and cut to ``max_new``. Whether a position
is masked is STATE, never a comparison with ``mask_token_id``: a prompt may
hold that id.

:func:`served_token_gaps` is what the serve runner calls, with a request's
prompt and served tokens and nothing else. Under ``sequential`` those fix
every pass's input: pass ``s`` of a block knows the block's prompt tokens and
its first ``s x block_length / denoising_steps`` generated positions, the
rest is masked. ONE forward does all passes: the clean sequence and one
noised copy a pass side by side, copy ``s``'s block ``b`` attending the CLEAN
blocks before ``b`` and itself. A served token's gap is read at the pass that
unmasked it: the reference's largest logit there less its logit of the served
token. (Under a confidence rule the served tokens do not say in which order
they were unmasked, so this check cannot follow it: ``cfg["generation"]``
with another rule is refused here.)

What the published config does not settle is under ``assumed`` in the
configuration's file (the block length, the rules and the loop, the mask id,
QK-norm, the draws).

Departures: weights are drawn from the seed IN THE SERVED TYPE and handed to
the program; the reference multiplies their exact float32 values at
``Precision.HIGHEST``. One sublayer is walked at a time, an expert's weights
are cast one expert at a time and attention runs ``Q_BLOCK`` queries at a
time, so that 10 GB of served weights and a float32 forward of three streams
of 2.5k tokens fit one chip together.

``precision``: "f32" is the reference; "int8" and "fp8" are the CONTROLS for
a bfloat16 cell: both operands of every projection of attention, of every
SwiGLU and of the head rounded to symmetric int8 or float8 e4m3, per row of
the activations and per column of the weights. The router stays in float32
(a deployment at a lower precision keeps it so).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
# the embedding's draw: the stream keeps its token (``assumed.stream_draw`` of
# the configuration's file says why, and why nothing else is drawn apart)
EMBED_STD = 1.0
Q_BLOCK = 256
REMASKINGS = ("sequential", "low_confidence_static")


def layer_spec(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    std, one = cfg.get("initializer_range", 0.02), ("const", 1.0)
    lin = lambda i, o, s=std: {"kernel": ((i, o), s)}
    E = cfg["num_experts"]
    return {"q": lin(d, hq * hd), "k": lin(d, hk * hd), "v": lin(d, hk * hd),
            "o": lin(hq * hd, d),
            "q_norm": {"scale": ((hd,), one)},
            "k_norm": {"scale": ((hd,), one)},
            "pre_attn_norm": {"scale": ((d,), one)},
            "pre_mlp_norm": {"scale": ((d,), one)},
            "moe": {"router": lin(d, E),
                    "experts": {"gate": ((E, d, f), std),
                                "up": ((E, d, f), std),
                                "down": ((E, f, d), std)}}}


def param_spec(cfg: dict) -> dict:
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg.get("initializer_range", 0.02)
    return {"wte": {"embedding": ((V, d), EMBED_STD)},
            "layers": [layer_spec(cfg)
                       for _ in range(cfg["num_hidden_layers"])],
            "norm_f": {"scale": ((d,), ("const", 1.0))},
            "lm_head": {"kernel": ((d, V), std)}}


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def param_dtypes(cfg: dict, served: str) -> dict:
    """Norm scales are float32 whatever the served type (the program keeps
    them so); every matrix is served."""
    def walk(node):
        if _is_leaf(node):
            return "float32" if isinstance(node[1], tuple) else served
        if isinstance(node, list):
            return [walk(x) for x in node]
        return {k: walk(v) for k, v in node.items()}
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
    return (x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, theta: float):
    """Half-split rotation of ``x [.., T, H, hd]`` at positions ``0 .. T -
    1``: channel pairs ``(i, i + hd / 2)`` at ``theta ** (-2i / hd)``."""
    T, hd = x.shape[-3], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-b, a], -1) * sin


# ---- 1. attention under the block mask -----------------------------------

def _masked_attention(q, k, v, seen):
    """``q [T, H, hd]`` over ``k``/``v`` ``[K, hk, hd]`` where ``seen(rows)
    [Q_BLOCK, K]`` says which keys the query rows ``rows`` attend; a block
    of ``Q_BLOCK`` queries at a time."""
    T, H, hd = q.shape
    hk = k.shape[1]
    qb = math.gcd(Q_BLOCK, T)
    qg = q.reshape(T // qb, qb, hk, H // hk, hd)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,tkd->kgqt", qi, k, precision=HIGHEST)
        s = s * hd ** -0.5
        ok = seen(i * qb + jnp.arange(qb))
        p = jax.nn.softmax(jnp.where(ok[None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HIGHEST)

    o = lax.map(block, (jnp.arange(T // qb), qg))
    return o.reshape(T, H * hd)


def attention(x, p, cfg, precision="f32"):
    """``x [S, T, d]``: stream 0 the CLEAN sequence, streams ``1 ..`` its
    noised copies (S = 1: the plain forward). A clean query attends the
    clean keys of the blocks up to its own; a copy's query the CLEAN keys of
    the blocks before its own and the copy's own keys of its own block."""
    S, T, _ = x.shape
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    L, eps = cfg["block_length"], float(cfg["rms_norm_eps"])
    heads = lambda name, n: _linear(x, p[name]["kernel"], precision).reshape(
        S, T, n, hd)
    q = _rope(_rms(heads("q", hq), p["q_norm"]["scale"], eps),
              float(cfg["rope_theta"]))
    k = _rope(_rms(heads("k", hk), p["k_norm"]["scale"], eps),
              float(cfg["rope_theta"]))
    v = heads("v", hk)
    blk = jnp.arange(T) // L
    outs = [_masked_attention(
        q[0], k[0], v[0], lambda rows: blk[None, :] <= blk[rows][:, None])]
    for s in range(1, S):
        outs.append(_masked_attention(
            q[s], jnp.concatenate([k[0], k[s]]), jnp.concatenate([v[0], v[s]]),
            lambda rows: jnp.concatenate(
                [blk[None, :] < blk[rows][:, None],
                 blk[None, :] == blk[rows][:, None]], axis=1)))
    return _linear(jnp.stack(outs), p["o"]["kernel"], precision)


# ---- 2. the experts ----------------------------------------------------------

def _swiglu(x, gate, up, down, precision):
    return _linear(jax.nn.silu(_linear(x, gate, precision))
                   * _linear(x, up, precision), down, precision)


def route(h, p, top_k, norm_topk):
    """``h [N, d]`` -> (experts ``[N, k]``, weights ``[N, k]``): softmax
    over the whole router in float32, its top-k, renormalised."""
    probs = jax.nn.softmax(jnp.matmul(
        h, p["router"]["kernel"].astype(jnp.float32), precision=HIGHEST), -1)
    w, idx = lax.top_k(probs, top_k)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w


def moe_partial(h, p, cfg, held=None, precision="f32"):
    """The sparse layer's ``m`` for ``h [N, d]``: the sum over the HELD
    experts (``held = (first, count)``; ``p["experts"]`` stacks exactly
    those, in order; None = every expert of the router: the uncut layer)
    of ``w_e E_e(h)``."""
    first, count = held or (0, cfg["num_experts"])
    idx, w = route(h, p, cfg["num_experts_per_tok"],
                   bool(cfg["norm_topk_prob"]))
    ex = p["experts"]

    def add_expert(m, e_w):
        e, gate, up, down = e_w
        we = jnp.sum(jnp.where(idx == first + e, w, 0.0), -1)      # [N]
        return m + we[:, None] * _swiglu(h, gate, up, down, precision), None

    # one expert at a time (a scan, so that one expert's float32 copy is
    # live and the program is compiled once for all of them)
    m, _ = lax.scan(add_expert, jnp.zeros_like(h),
                    (jnp.arange(count), ex["gate"], ex["up"], ex["down"]))
    return m


# ---- the forward -------------------------------------------------------------

def _static(cfg: dict) -> tuple:
    """The keys a layer reads, hashable (a static argument of the jit)."""
    keys = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "rms_norm_eps", "rope_theta", "num_experts",
            "num_experts_per_tok", "norm_topk_prob", "moe_intermediate_size")
    out = {k: cfg[k] for k in keys}
    out["block_length"] = cfg["generation"]["block_length"]
    return tuple(sorted(out.items()))


@partial(jax.jit, static_argnames=("cfg_t", "precision"), donate_argnums=(0,))
def _attn_half(x, p, cfg_t, precision):
    cfg = dict(cfg_t)
    return x + attention(_rms(x, p["pre_attn_norm"]["scale"],
                              float(cfg["rms_norm_eps"])), p, cfg, precision)


@partial(jax.jit, static_argnames=("cfg_t", "precision"), donate_argnums=(0,))
def _mlp_half(x, p, cfg_t, precision):
    cfg = dict(cfg_t)
    y = _rms(x, p["pre_mlp_norm"]["scale"], float(cfg["rms_norm_eps"]))
    return x + moe_partial(y.reshape(-1, y.shape[-1]), p["moe"], cfg, None,
                           precision).reshape(y.shape)


@partial(jax.jit, static_argnames=("eps", "precision"))
def _readout(x, scale, head, eps, precision):
    return _linear(_rms(x, scale, eps), head, precision)


def hidden(params, streams, cfg, precision="f32"):
    """``streams [S, T]`` token ids (row 0 the clean sequence, the rest its
    noised copies; ``T`` whole blocks) -> the final hidden vectors ``[S, T,
    d]`` before the last norm."""
    x = params["wte"]["embedding"][streams].astype(jnp.float32)
    cfg_t = _static(cfg)
    for p in params["layers"]:
        x = _attn_half(x, p, cfg_t, precision)
        x = _mlp_half(x, p, cfg_t, precision)
    return x


def forward(params, tokens, cfg, precision="f32"):
    """``tokens [T]`` (whole blocks) -> logits ``[T, V]`` (float32) under
    the block mask: position ``i``'s logits are token ``i``'s."""
    x = hidden(params, jnp.asarray(tokens, jnp.int32)[None], cfg, precision)
    return _readout(x[0], params["norm_f"]["scale"],
                    params["lm_head"]["kernel"], float(cfg["rms_norm_eps"]),
                    precision)


# ---- generation ----------------------------------------------------------------

def take_positions(masked, conf, n: int, remasking: str):
    """Which of a block's ``masked`` positions (bool ``[L]``) a denoise pass
    unmasks, ``n`` of them or all that are left: ``sequential`` the
    leftmost, ``low_confidence_static`` those of largest ``conf`` (the
    probability of the position's greedy token), ties to the left."""
    masked = np.asarray(masked, bool)
    idx = np.flatnonzero(masked)
    if remasking == "low_confidence_static":
        idx = idx[np.argsort(-np.asarray(conf, np.float64)[idx],
                             kind="stable")]
    elif remasking != "sequential":
        raise ValueError(f"remasking {remasking!r} is none of {REMASKINGS}")
    take = np.zeros_like(masked)
    take[idx[:n]] = True
    return take


def generate(params, prompt, max_new: int, cfg, precision="f32",
             trace: list | None = None, pad_to: int = 1):
    """The block-diffusion loop, one request, no cache: returns the
    ``max_new`` generated tokens. ``trace`` (a list) is handed, per denoise
    pass, ``(block start, input tokens [L], masked [L], logits [L, V])``.
    ``pad_to``: every forward's length is rounded up to a multiple of it
    with blocks of mask tokens AFTER the current block, which no position up
    to it attends (fewer shapes to compile)."""
    g = cfg["generation"]
    L, mask_id = g["block_length"], g["mask_token_id"]
    per_pass = L // g["denoising_steps"]
    seq = [int(t) for t in prompt]
    n = len(seq)
    while len(seq) < n + max_new:
        start = len(seq) // L * L
        known = len(seq) - start
        block = np.asarray(seq[start:] + [mask_id] * (L - known), np.int64)
        masked = np.arange(L) >= known
        while masked.any():
            T = -(-(start + L) // pad_to) * pad_to
            toks = np.concatenate([np.asarray(seq[:start], np.int64),
                                   np.where(masked, mask_id, block),
                                   np.full((T - start - L,), mask_id)])
            logits = forward(params, toks, cfg, precision)[start:start + L]
            if trace is not None:
                trace.append((start, toks[start:start + L], masked.copy(),
                              np.asarray(logits)))
            probs = jax.nn.softmax(logits, axis=-1)
            pred = np.asarray(jnp.argmax(logits, -1))
            take = take_positions(masked, np.asarray(jnp.max(probs, -1)),
                                  per_pass, g["remasking"])
            block = np.where(take, pred, block)
            masked = masked & ~take
        seq = seq[:start] + [int(t) for t in block]
    return seq[n:n + max_new]


# ---- what the serve runner compares ----------------------------------------------

@jax.jit
def _gaps(logits, served):
    """Per position: the reference's best logit minus the reference's
    logit of the token that was served there."""
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, served[:, None], -1)[:, 0]
    return best - got


def noised_streams(prompt, served, cfg, pad_to: int):
    """The clean sequence and one noised copy a pass, ``[1 + denoising
    steps, T]`` (``T`` whole multiples of ``pad_to``), and for every served
    token ``(stream, position)``: the copy of the pass that unmasked it.
    Copy ``s`` (stream ``1 + s``) knows, of every block, its prompt tokens
    and its first ``s x block_length / denoising_steps`` generated
    positions; the rest hold the mask token. The clean stream's positions
    past the served tokens hold it too."""
    g = cfg["generation"]
    if g["remasking"] != "sequential":
        raise ValueError(
            f"served tokens fix the passes' inputs under 'sequential' only; "
            f"the configuration generates by {g['remasking']!r}")
    L, steps, mask_id = (g["block_length"], g["denoising_steps"],
                         g["mask_token_id"])
    per_pass = L // steps
    n, m = len(prompt), len(served)
    T = -(-(n + m) // pad_to) * pad_to
    assert pad_to % L == 0, (pad_to, L)
    seq = np.full((T,), mask_id, np.int64)
    seq[:n + m] = list(prompt) + list(served)
    pos = np.arange(T)
    from_prompt = np.clip(n - pos // L * L, 0, L)     # of the position's block
    streams = [seq] + [
        np.where(pos % L < from_prompt + s * per_pass, seq, mask_id)
        for s in range(steps)]
    at = n + np.arange(m)
    stream = 1 + (at % L - from_prompt[at]) // per_pass
    return np.stack(streams), stream, at


def raw_token_gaps(params, prompt, served, cfg, pad_to=256, control=()):
    """For every served token the reference's best logit at its position, in
    the pass that unmasked it, minus the reference's logit of the token
    itself (``"served"``) and, per precision named in ``control``, the same
    for the token that forward pass puts first."""
    streams, stream, at = noised_streams(prompt, served, cfg, pad_to)
    eps = float(cfg["rms_norm_eps"])

    def logits(precision):
        x = hidden(params, jnp.asarray(streams, jnp.int32), cfg, precision)
        return _readout(x[stream, at], params["norm_f"]["scale"],
                        params["lm_head"]["kernel"], eps, precision)

    ref = logits("f32")
    out = {"served": _gaps(ref, jnp.asarray(served, jnp.int32))}
    for prec in control:
        out[prec] = _gaps(ref, jnp.argmax(logits(prec), -1).astype(jnp.int32))
    return jax.device_get(out)


def served_token_gaps(params, prompt, served, cfg, pad_to=256,
                      control=()):
    """What the serve runner compares: for each served token THE MEAN GAP OF
    ITS REQUEST's served tokens (:func:`raw_token_gaps` has each token's
    own), so that the worst the runner takes is the worst request's mean;
    the same for each control precision. As in every routed model here the
    worst token is a flip of the router at a near-tie and not rounding,
    while lower precision makes a gap more frequent, which a mean shows
    (the readings at the published widths are in PERF.md section 6, PR 48).
    Each request's raw worst gap and count are printed beside what is
    reported."""
    raw = raw_token_gaps(params, prompt, served, cfg, pad_to, control)
    worst = {k: float(v.max()) for k, v in raw.items()}
    print(f"INFO sdar_moe_ref: request of {len(prompt)} + {len(served)} "
          f"tokens: a token's own gap at worst {worst}, tokens with a gap "
          f"{ {k: int((v > 0).sum()) for k, v in raw.items()} }; reported: "
          f"the request's mean", flush=True)
    return {k: [float(v.mean())] * len(v) for k, v in raw.items()}
