"""Plain GPT-2 in float32 ``jax.numpy``: forward pass, loss, gradients and
the AdamW steps the training check follows. Imports nothing of the program.

Follows Radford et al. 2019 / the ``openai-community/gpt2*`` checkpoints:
learned token and position embeddings, pre-LayerNorm blocks (eps 1e-5),
fused QKV split into thirds and then into heads, softmax(QK^T/sqrt(hd)) with
a causal mask, GELU in its tanh form (``gelu_new``), a final LayerNorm and a
read-out tied to the token embedding; next-token cross-entropy averaged
over every predicted position.

Departures, each deliberate:
- dropout is not applied (the cells run with the three ``*_pdrop`` at 0,
  listed in the configuration's ``reduced``: a comparison against a
  reference needs the same arithmetic on both sides);
- weights are drawn here from the seed (normal, std 0.02; residual
  projections 0.02/sqrt(2L) as the released code does; biases 0, LayerNorm
  1/0) and handed to the program, not the other way round;
- the layers are held stacked ``[L, ...]`` and walked with ``lax.scan``
  under ``jax.checkpoint`` so that a batch of rows fits beside the float32
  optimizer state; the arithmetic is the unrolled loop's.

``precision``: "f32" multiplies at ``Precision.HIGHEST`` (the reference);
"int8" and "fp8" are the CONTROLS: every linear layer's two operands are
rounded to symmetric int8 or to float8 e4m3 (scaled per row of the
activations, per column of the weights) before the multiply, with a
straight-through gradient.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def param_spec(cfg: dict) -> dict:
    """name -> (shape, std or ('const', value)). Nested dict of leaves."""
    L, d, V, T = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    ff = cfg.get("n_inner") or 4 * d
    std = 0.02
    proj = std / math.sqrt(2 * L)
    one, zero = ("const", 1.0), ("const", 0.0)
    return {
        "wte": {"embedding": ((V, d), std)},
        "wpe": {"embedding": ((T, d), std)},
        "blocks": {
            "ln1": {"scale": ((L, d), one), "bias": ((L, d), zero)},
            "qkv": {"kernel": ((L, d, 3 * d), std), "bias": ((L, 3 * d), zero)},
            "attn_out": {"kernel": ((L, d, d), proj), "bias": ((L, d), zero)},
            "ln2": {"scale": ((L, d), one), "bias": ((L, d), zero)},
            "mlp_in": {"kernel": ((L, d, ff), std), "bias": ((L, ff), zero)},
            "mlp_out": {"kernel": ((L, ff, d), proj), "bias": ((L, d), zero)},
        },
        "ln_f": {"scale": ((d,), one), "bias": ((d,), zero)},
    }


def _fq(x, axis, kind):
    """Rounding to int8 (symmetric) or float8 e4m3, scaled to the largest
    magnitude along ``axis``, with a straight-through gradient."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    top = 127.0 if kind == "int8" else 448.0
    scale = jnp.where(amax > 0, amax / top, 1.0)
    if kind == "int8":
        q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    else:
        q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _linear(x, w, b, precision):
    if precision != "f32":
        x, w = _fq(x, -1, precision), _fq(w, 0, precision)
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y if b is None else y + b


def _layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, eps, precision):
    B, T, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"], eps)
    qkv = _linear(h, p["qkv"]["kernel"], p["qkv"]["bias"], precision)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    heads = lambda a: a.reshape(B, T, n_head, hd).transpose(0, 2, 1, 3)
    q, k, v = heads(q), heads(k), heads(v)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", a, v, precision=HIGHEST)
    o = o.transpose(0, 2, 1, 3).reshape(B, T, d)
    x = x + _linear(o, p["attn_out"]["kernel"], p["attn_out"]["bias"], precision)
    h = _layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"], eps)
    h = _gelu_new(_linear(h, p["mlp_in"]["kernel"], p["mlp_in"]["bias"], precision))
    return x + _linear(h, p["mlp_out"]["kernel"], p["mlp_out"]["bias"], precision)


def forward(params, tokens, cfg, precision="f32"):
    """``tokens [B, T]`` -> logits ``[B, T, V]`` (float32)."""
    eps = cfg.get("layer_norm_epsilon", 1e-5)
    T = tokens.shape[1]
    x = params["wte"]["embedding"][tokens] + params["wpe"]["embedding"][:T]

    @jax.checkpoint
    def body(x, p):
        return _block(x, p, cfg["n_head"], eps, precision), None

    x, _ = lax.scan(body, x, params["blocks"])
    x = _layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"], eps)
    return _linear(x, params["wte"]["embedding"].T, None, precision)


def loss_sum(params, tokens, cfg, precision="f32"):
    """Summed next-token cross-entropy of ``tokens [B, T]``."""
    logits = forward(params, tokens, cfg, precision)[:, :-1]
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
    return -jnp.sum(picked)


@partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _grad_block(params, tokens, cfg_items, precision):
    return jax.value_and_grad(loss_sum)(params, tokens, dict(cfg_items),
                                        precision)


@partial(jax.jit, donate_argnums=(0,))
def _add_into(acc, g):
    return jax.tree.map(jnp.add, acc, g)


def loss_and_grad(params, tokens, cfg, precision="f32", rows_per_block=4,
                  devices=None):
    """Mean loss and its gradient over ``tokens [B, T]``, computed in
    blocks of rows so that the activations of one block are all that is
    ever live. ``devices``: chips to spread the blocks over (each gets a
    copy of the parameters and sums its own blocks; the sums meet on the
    first); the default is where ``params`` live."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, type(None)))))
    B, T = tokens.shape
    tokens = jax.device_get(tokens)
    home = next(iter(jax.tree.leaves(params)[0].devices()))
    devices = list(devices or [home])
    copies = [params if d == home else jax.device_put(params, d)
              for d in devices]
    totals, grads = [0.0] * len(devices), [None] * len(devices)
    for n_blk, i in enumerate(range(0, B, rows_per_block)):
        j = n_blk % len(devices)
        blk = jax.device_put(tokens[i:i + rows_per_block], devices[j])
        l, g = _grad_block(copies[j], blk, items, precision)
        totals[j] = totals[j] + l
        grads[j] = g if grads[j] is None else _add_into(grads[j], g)
    del copies
    first = devices[0]
    total, acc = totals[0], grads[0]
    for j in range(1, len(devices)):
        if grads[j] is None:
            continue
        total = total + jax.device_put(totals[j], first)
        acc = _add_into(acc, jax.device_put(grads[j], first))
        grads[j] = None
    n = B * (T - 1)
    return total / n, jax.tree.map(lambda g: g / n, acc)


def lr_at(count: int, opt: dict) -> float:
    """The schedule the cell states: linear warm-up from 0 over
    ``warmup_steps`` (at least 1), then a cosine from ``lr`` to 0 that ends
    at ``total_steps``."""
    warm = max(int(opt.get("warmup_steps", 0)), 1)
    total = max(int(opt["total_steps"]), warm + 1)
    if count < warm:
        return opt["lr"] * count / warm
    frac = min((count - warm) / (total - warm), 1.0)
    return opt["lr"] * 0.5 * (1.0 + math.cos(math.pi * frac))


@partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam_update(params, grads, m, v, lr, t, b1, b2, eps, wd_mask_scale):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v, w: p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                     + w * p),
        params, m, v, wd_mask_scale)
    return params, m, v


def leaf_norms(tree) -> dict:
    """Frobenius norm of every leaf; leaves under ``blocks`` give one norm
    per layer. Returns {path: [norms]} with float32 arrays."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        leaf = leaf.astype(jnp.float32)
        if name.startswith("blocks/"):
            out[name] = jnp.sqrt(jnp.sum(
                jnp.square(leaf), axis=tuple(range(1, leaf.ndim))))
        else:
            out[name] = jnp.sqrt(jnp.sum(jnp.square(leaf)))[None]
    return out


def sample_elems(tree, per_slice: int = 4096) -> dict:
    """A fixed strided sample of every leaf's elements (per layer for the
    leaves under ``blocks``): {path: [slices, <=per_slice]} float32. The
    element-by-element comparison of two gradients needs only these."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        flat = leaf.astype(jnp.float32).reshape(
            (leaf.shape[0], -1) if name.startswith("blocks/") else (1, -1))
        stride = max(flat.shape[1] // per_slice, 1)
        out[name] = flat[:, ::stride][:, :per_slice]
    return out


def train_steps(params, batches, cfg, opt, precision="f32",
                rows_per_block=4, devices=None, make_p0=None):
    """Follow ``len(batches)`` AdamW steps from ``params``. Returns the
    per-step losses, the leaf norms of the first gradient, and the leaf
    norms of the parameters' change over all the steps.

    With several ``devices`` the row blocks are spread over all but the
    last, and the last holds AdamW's moments and makes the updates, so
    that no chip holds parameters, two gradients and both moments at once.
    ``make_p0`` re-draws the starting parameters at the end (instead of a
    copy kept all along)."""
    b1, b2, eps = opt.get("b1", 0.9), opt.get("b2", 0.999), opt.get("eps", 1e-8)
    wd = float(opt.get("weight_decay", 0.0))
    decays = {"kernel", "embedding"}
    wd_mask = jax.tree_util.tree_map_with_path(
        lambda path, _: wd if getattr(path[-1], "key", None) in decays else 0.0,
        params)
    devices = list(devices) if devices else None
    workers = devices[:-1] if devices and len(devices) > 1 else devices
    opt_dev = devices[-1] if devices and len(devices) > 1 else None
    put = (lambda t: jax.device_put(t, opt_dev)) if opt_dev else (lambda t: t)
    p0 = None if make_p0 is not None else jax.tree.map(jnp.copy, params)
    m = put(jax.tree.map(jnp.zeros_like, params))
    v = jax.tree.map(jnp.zeros_like, m)
    wd_mask = put(wd_mask)
    losses, first_grad_norms = [], None
    for step, tokens in enumerate(batches):
        loss, grads = loss_and_grad(params, tokens, cfg, precision,
                                    rows_per_block, workers)
        losses.append(float(loss))
        if step == 0:
            first_grad_norms = jax.device_get(leaf_norms(grads))
            first_grad_sample = jax.device_get(sample_elems(grads))
        home = next(iter(jax.tree.leaves(params)[0].devices()))
        params, grads = put(params), put(grads)
        params, m, v = _adam_update(
            params, grads, m, v, jnp.float32(lr_at(step, opt)),
            jnp.float32(step + 1), b1, b2, eps, wd_mask)
        del grads
        if opt_dev is not None:
            params = jax.device_put(params, home)
    del m, v
    if p0 is None:
        p0 = make_p0()
    delta = jax.tree.map(jnp.subtract, params, p0)
    return {"losses": losses, "grad_norms": first_grad_norms,
            "grad_sample": first_grad_sample,
            "delta_norms": jax.device_get(leaf_norms(delta))}
