"""Functional layer library — the framework's own, no flax/haiku dependency.

Design: a layer is a small dataclass with

- ``init(key) -> params`` (a pytree of ``jax.Array``), and
- ``apply(params, x, ...) -> y`` — a *pure function* of its inputs.

Stateful layers (BatchNorm) additionally take/return a ``state`` pytree;
stochastic layers (Dropout) take an explicit ``rng``. Models compose layers
explicitly, so the whole forward pass is one traceable pure function —
exactly what ``jax.jit``/``pjit`` want, and the reason gradient sync can be a
compiled ``psum`` instead of the reference's DDP wrapper
(``/root/reference/main.py:122``).

Initialisation follows the PyTorch defaults the reference inherits from
``nn.Conv2d``/``nn.Linear`` (kaiming-uniform with a=sqrt(5): weights and
biases ~ U(-1/sqrt(fan_in), 1/sqrt(fan_in))), so seeded training curves are
comparable with the reference's.

Layouts are TPU-native: images NHWC, conv kernels HWIO (the reference's torch
uses NCHW/OIHW; XLA:TPU strongly prefers channels-last).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_compute_pytorch_tpu.obs.tracing import scope


def _uniform(key, shape, bound, dtype):
    return jax.random.uniform(key, shape, dtype, minval=-bound, maxval=bound)


# --------------------------------------------------------------------------


@dataclass(frozen=True)
class Dense:
    """Affine layer ≈ ``nn.Linear`` (reference ``main.py:27-28``)."""

    in_features: int
    out_features: int
    use_bias: bool = True
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        kw, kb = jax.random.split(key)
        bound = 1.0 / math.sqrt(self.in_features)
        p = {"kernel": _uniform(kw, (self.in_features, self.out_features),
                                bound, self.param_dtype)}
        if self.use_bias:
            p["bias"] = _uniform(kb, (self.out_features,), bound, self.param_dtype)
        return p

    def apply(self, params, x):
        k = params["kernel"]
        if isinstance(k, dict):      # weight-only int8 (utils/quantize.py)
            from distributed_compute_pytorch_tpu.ops.int8_matmul import (
                int8_matmul)
            from distributed_compute_pytorch_tpu.utils.quantize import (
                is_quantized)
            if not is_quantized(k):   # not assert: must survive python -O
                raise ValueError(f"unknown kernel-dict keys {set(k)}")
            y = int8_matmul(x, k["q"], k["scale"])
        else:
            y = x @ k.astype(x.dtype)
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return y


@dataclass(frozen=True)
class Conv2d:
    """2-D convolution ≈ ``nn.Conv2d`` (reference ``main.py:23-24``), NHWC/HWIO.

    ``padding='VALID'`` matches torch's default ``padding=0`` the reference
    uses for both convs.
    """

    in_channels: int
    out_channels: int
    kernel_size: int | tuple[int, int]
    stride: int | tuple[int, int] = 1
    padding: str | Sequence[tuple[int, int]] = "VALID"
    use_bias: bool = True
    param_dtype: jnp.dtype = jnp.float32

    def _ks(self) -> tuple[int, int]:
        k = self.kernel_size
        return (k, k) if isinstance(k, int) else tuple(k)

    def init(self, key):
        kh, kwd = self._ks()
        kw, kb = jax.random.split(key)
        fan_in = self.in_channels * kh * kwd
        bound = 1.0 / math.sqrt(fan_in)
        p = {"kernel": _uniform(kw, (kh, kwd, self.in_channels, self.out_channels),
                                bound, self.param_dtype)}
        if self.use_bias:
            p["bias"] = _uniform(kb, (self.out_channels,), bound, self.param_dtype)
        return p

    def apply(self, params, x):
        s = self.stride
        strides = (s, s) if isinstance(s, int) else tuple(s)
        y = lax.conv_general_dilated(
            x, params["kernel"].astype(x.dtype),
            window_strides=strides, padding=self.padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if self.use_bias:
            y = y + params["bias"].astype(x.dtype)
        return y


def max_pool2d(x, window: int = 2, stride: int | None = None, padding: int = 0):
    """``F.max_pool2d`` equivalent (reference ``main.py:36``), NHWC.

    ``padding`` is symmetric spatial padding in pixels (torch convention).
    """
    stride = stride or window
    pads = ((0, 0), (padding, padding), (padding, padding), (0, 0))
    return lax.reduce_window(
        x, -jnp.inf, lax.max,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1), padding=pads)


def avg_pool2d(x, window: int = 2, stride: int | None = None):
    stride = stride or window
    summed = lax.reduce_window(
        x, 0.0, lax.add,
        window_dimensions=(1, window, window, 1),
        window_strides=(1, stride, stride, 1), padding="VALID")
    return summed / (window * window)


def _mask_and_scale(x, seed, keep: float, mask_shape):
    """``x / keep`` where the generator's 32 bits of an element lie under
    ``keep * 2**32`` and 0 elsewhere: Bernoulli(keep) decided on integers,
    with no uniform float in between. The same ``seed`` gives the same bits,
    which is what lets the backward pass draw them again."""
    threshold = min(round(keep * 2 ** 32), 2 ** 32 - 1)
    with scope("dropout"):
        _, bits = lax.rng_bit_generator(seed, mask_shape, dtype=jnp.uint32)
        return jnp.where(bits < jnp.uint32(threshold), x / keep,
                         0.0).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _masked(x, seed, keep: float, mask_shape):
    return _mask_and_scale(x, seed, keep, mask_shape)


def _masked_fwd(x, seed, keep, mask_shape):
    # the seed is all autodiff keeps between the passes; a compiler that
    # merges the two draws keeps the mask as it sees fit (PERF.md, PR 31)
    return _mask_and_scale(x, seed, keep, mask_shape), seed


def _masked_bwd(keep, mask_shape, seed, g):
    return _mask_and_scale(g, seed, keep, mask_shape), None


_masked.defvjp(_masked_fwd, _masked_bwd)


def dropout(x, rate: float, rng, train: bool,
            broadcast_dims: Sequence[int] = ()):
    """``nn.Dropout`` equivalent (reference ``main.py:25-26``). Pure: identity
    when not training or rate==0; otherwise inverted-scaling mask from ``rng``.

    ``broadcast_dims`` are axes the mask is shared across: ``nn.Dropout2d``
    (reference ``main.py:25``) zeroes whole channels, i.e. in NHWC the mask
    is drawn per ``[B, 1, 1, C]`` and broadcast over the spatial dims (1, 2).
    """
    if not train or rate == 0.0:
        return x
    mask_shape = tuple(1 if d in tuple(broadcast_dims) else s
                       for d, s in enumerate(x.shape))
    # XLA's ``RngBitGenerator`` starts from ``uint32[4]``: the caller's key's
    # words, twice, as JAX's ``rbg`` implementation seeds itself. Every key
    # derivation above (``fold_in``, ``split``) stays on the caller's key.
    seed = jnp.resize(jax.random.key_data(rng), (4,))
    return _masked(x, seed, 1.0 - rate, mask_shape)


@dataclass(frozen=True)
class BatchNorm:
    """Batch normalisation ≈ ``nn.BatchNorm1d`` (reference ``main.py:29``).

    Normalises over all axes but the last; keeps running stats with torch's
    momentum convention (``new = (1-m)*old + m*batch``, m=0.1, eps=1e-5).

    SPMD note (SURVEY §7 hard part b): ``jnp.mean``/``var`` here reduce over
    the *global* batch dimension of the sharded array — under jit the SPMD
    partitioner inserts the cross-device reduction, so this is **sync-BN**
    (global-batch statistics) whenever the batch is sharded over mesh axes.
    That is a deliberate deviation from the reference, whose DDP syncs
    gradients but not BN stats (per-replica stats): global stats are what
    make DP-N numerically equal to one big-device run, which our tests pin
    (``tests/test_step.py``, ``tests/test_batchnorm.py``).

    Inside a shard_map region MANUAL over the dp axes (the step-level
    grad-accum body, ``train/step.py``) the partitioner never sees the
    batch dim — it is shard-local — so the layer restores sync-BN itself:
    ``core.mesh.manual_batch_axes`` names the manual batch axes and the
    statistics pmean over them (variance via E[x²]−E[x]², the shard-
    composable form). Outside manual regions the formula (and so the
    numerics) is unchanged.
    """

    num_features: int
    momentum: float = 0.1
    eps: float = 1e-5
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        del key
        f = self.num_features
        return {"scale": jnp.ones((f,), self.param_dtype),
                "bias": jnp.zeros((f,), self.param_dtype)}

    def init_state(self):
        f = self.num_features
        return {"mean": jnp.zeros((f,), jnp.float32),
                "var": jnp.ones((f,), jnp.float32)}

    def apply(self, params, state, x, train: bool):
        reduce_axes = tuple(range(x.ndim - 1))
        if train:
            from distributed_compute_pytorch_tpu.core.mesh import (
                manual_batch_axes)
            axes, world = manual_batch_axes()
            if axes:
                # shard-local batch dim: psum the moments back to global
                # (sync-BN) statistics; equal-size shards (the feeder's
                # guarantee) make pmean-of-means the global mean
                mean = lax.pmean(jnp.mean(x, reduce_axes), axes)
                msq = lax.pmean(jnp.mean(jnp.square(x), reduce_axes), axes)
                var = jnp.maximum(msq - jnp.square(mean), 0.0)
            else:
                mean = jnp.mean(x, reduce_axes)
                var = jnp.var(x, reduce_axes)
            n = (x.size // x.shape[-1]) * world
            unbiased = var * (n / max(n - 1, 1))
            new_state = {
                "mean": (1 - self.momentum) * state["mean"]
                        + self.momentum * mean.astype(jnp.float32),
                "var": (1 - self.momentum) * state["var"]
                       + self.momentum * unbiased.astype(jnp.float32),
            }
        else:
            mean, var = state["mean"].astype(x.dtype), state["var"].astype(x.dtype)
            new_state = state
        inv = lax.rsqrt(var.astype(x.dtype) + self.eps)
        y = (x - mean.astype(x.dtype)) * inv
        y = y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)
        return y, new_state


@dataclass(frozen=True)
class LayerNorm:
    """Layer normalisation over the last axis (transformer rungs)."""

    num_features: int
    eps: float = 1e-5
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        del key
        return {"scale": jnp.ones((self.num_features,), self.param_dtype),
                "bias": jnp.zeros((self.num_features,), self.param_dtype)}

    def apply(self, params, x):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        y = (x - mean) * lax.rsqrt(var + self.eps)
        return y * params["scale"].astype(x.dtype) + params["bias"].astype(x.dtype)


@dataclass(frozen=True)
class RMSNorm:
    """Root-mean-square norm (no mean subtraction, no bias) — the Llama
    family's normalisation. Stats in float32 regardless of activation
    dtype (bf16 squares underflow), matching the HF reference numerics."""

    num_features: int
    eps: float = 1e-6
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        del key
        return {"scale": jnp.ones((self.num_features,), self.param_dtype)}

    def apply(self, params, x):
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


@dataclass(frozen=True)
class Embedding:
    """Token/position embedding table."""

    vocab_size: int
    features: int
    param_dtype: jnp.dtype = jnp.float32
    init_std: float = 0.02

    def init(self, key):
        return {"embedding": self.init_std * jax.random.normal(
            key, (self.vocab_size, self.features), self.param_dtype)}

    def apply(self, params, ids):
        t = params["embedding"]
        if isinstance(t, dict):      # int8 table: dequant after gather
            from distributed_compute_pytorch_tpu.utils.quantize import (
                is_quantized)
            if not is_quantized(t):   # not assert: must survive python -O
                raise ValueError(f"unknown embedding-dict keys {set(t)}")
            out = (t["q"][ids].astype(jnp.float32)
                   * t["scale"][ids].astype(jnp.float32)
                   ).astype(t["scale"].dtype)
        else:
            out = t[ids]
        # Pin the gather's output layout. Under 3-axis meshes (batch over
        # data x fsdp, table over fsdp x tensor) XLA's SPMD partitioner
        # MISCOMPILES an unannotated gather feeding a residual + TP-matmul
        # chain — wrong values on the mixed (data, fsdp) shards, repro'd
        # pure-jax on jax 0.9.0 CPU (see tests/test_generate.py mesh
        # cases). An explicit constraint on the gather output sidesteps
        # the bad partition choice; it is also simply the layout we want
        # (activations batch-sharded, features replicated). No-op without
        # a mesh context.
        from distributed_compute_pytorch_tpu.core.mesh import constrain
        if out.ndim == 3:
            return constrain(out, P(("data", "fsdp"), None, None))
        if out.ndim == 2:
            # position-table lookups ([T, d]) and single-token embeds:
            # leading dim is NOT batch; keep fully replicated
            from distributed_compute_pytorch_tpu.core.mesh import (
                constrain_replicated)
            return constrain_replicated(out)
        return out

    def attend(self, params, x):
        """Tied-softmax readout: ``x @ E^T``."""
        t = params["embedding"]
        if isinstance(t, dict):      # per-row scales = transposed channels
            from distributed_compute_pytorch_tpu.ops.int8_matmul import (
                int8_matmul)
            from distributed_compute_pytorch_tpu.utils.quantize import (
                is_quantized)
            if not is_quantized(t):   # not assert: must survive python -O
                raise ValueError(f"unknown embedding-dict keys {set(t)}")
            return int8_matmul(x, t["q"], t["scale"], transpose=True)
        return x @ t.astype(x.dtype).T


def clamped_swiglu(gate, up, limit: float = 0.0):
    """``silu(min(gate, limit)) * clip(up(), -limit, limit)`` (``limit`` 0:
    unclamped). ``up`` is a thunk: its product is traced after the gate's
    SiLU, where an unclamped SwiGLU has always had it."""
    g = jax.nn.silu(jnp.minimum(gate, limit) if limit else gate)
    u = up()
    return g * (jnp.clip(u, -limit, limit) if limit else u)


def log_softmax(x, axis: int = -1):
    """``F.log_softmax`` equivalent (reference ``main.py:44``)."""
    return jax.nn.log_softmax(x, axis=axis)


def nll_loss(log_probs, targets, reduction: str = "mean"):
    """``F.nll_loss`` equivalent (reference ``main.py:61,81``): negative
    log-likelihood given *log-probabilities* and integer targets."""
    picked = jnp.take_along_axis(log_probs, targets[..., None], axis=-1)[..., 0]
    if reduction == "mean":
        return -picked.mean()
    if reduction == "sum":
        return -picked.sum()
    return -picked


def cross_entropy_with_logits(logits, targets, reduction: str = "mean"):
    """Fused log_softmax + nll for the transformer rungs."""
    return nll_loss(jax.nn.log_softmax(logits, -1), targets, reduction)


def token_eval_metrics(per_tok_loss, correct, valid=None, token_mask=None):
    """Weighted token-level eval sums shared by the LM models.

    ``per_tok_loss``/``correct``: float ``[B, T']`` per-token values.
    ``valid``: optional float ``[B]`` sequence mask — 0.0 rows are the
    feeder's wraparound padding and contribute nothing (exact eval).
    ``token_mask``: optional float ``[B, T]`` per-token mask (1 = real
    token) — padded positions of variable-length batches weight out. The
    weight of a loss entry follows its TARGET token: for shifted causal-LM
    losses (``T' = T-1``, column j scores token j+1) a full-width mask is
    cropped to its last ``T'`` columns, i.e. ``mask[:, 1:]``; for unshifted
    losses (BERT, ``T' = T``) it is used as-is.
    """
    per_tok_loss = per_tok_loss.astype(jnp.float32)
    w = (jnp.ones_like(per_tok_loss) if valid is None
         else jnp.broadcast_to(valid[:, None].astype(jnp.float32),
                               per_tok_loss.shape))
    if token_mask is not None:
        shift = token_mask.shape[1] - per_tok_loss.shape[1]
        w = w * token_mask[:, shift:].astype(jnp.float32)
    return {
        "loss_sum": jnp.sum(per_tok_loss * w),
        "correct": jnp.sum(correct.astype(jnp.float32) * w).astype(jnp.int32),
        "count": jnp.sum(w).astype(jnp.int32),
    }
