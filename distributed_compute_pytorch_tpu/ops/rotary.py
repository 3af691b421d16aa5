"""Rotary position embeddings (RoPE) — the Llama family's positional
encoding.

Capability beyond the reference (whose only model is a position-free CNN,
``/root/reference/main.py:20-45``); needed for the modern decoder rung.
Convention matches the open Llama implementations (half-split
``rotate_half``, NOT interleaved pairs) so weights/numerics port 1:1;
:func:`apply_rope_interleaved` is the other convention (adjacent pairs,
the DeepSeek-V2/V3 latent-attention recipe with ``rope_interleave``).

TPU notes: cos/sin are computed in float32 (bf16 phases lose precision at
long context) and the rotation is two fused elementwise multiplies — XLA
folds it into the surrounding matmul epilogue, so RoPE adds no HBM
round-trip.

Because rotations are absolute-position phases whose *differences* carry
the relative offset, applying RoPE before K/V leave for a ring rotation
(sequence parallelism) is exact: each chunk bakes its own global positions
in, wherever it later travels (``parallel/ring_attention.py``).
"""

from __future__ import annotations

import jax.numpy as jnp


def rope_cos_sin(positions, head_dim: int, theta: float = 10000.0):
    """``cos, sin`` tables for integer ``positions`` of shape ``[T]``
    (shared across the batch) or ``[B, T]`` (per-row — left-padded
    variable-length decoding gives every row its own logical positions).

    Frequencies follow ``theta ** (-2i/d)`` for the first ``d/2`` feature
    pairs; each table duplicates its ``d/2`` half so the rotation is a
    plain elementwise multiply against the half-split layout.
    """
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(freqs), jnp.cos(freqs)], axis=-1)
    sin = jnp.concatenate([jnp.sin(freqs), jnp.sin(freqs)], axis=-1)
    return cos, sin


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def apply_rope(x, positions, theta: float = 10000.0,
               rotary_dim: int | None = None):
    """Rotate ``x [B, H, T, hd]`` by integer ``positions`` — ``[T]``
    (shared) or ``[B, T]`` (per-row).

    ``positions`` may be traced (the pipeline's seq-manual path offsets
    them by ``axis_index('seq') * chunk``).

    ``rotary_dim`` rotates the FIRST ``rotary_dim`` channels only
    (half-split pairs ``(i, i + rotary_dim / 2)`` at ``theta ** (-2i /
    rotary_dim)``: a ``partial_rotary_factor``); the rest pass through.
    """
    hd = x.shape[-1]
    if rotary_dim is not None and rotary_dim != hd:
        return jnp.concatenate(
            [apply_rope(x[..., :rotary_dim], positions, theta),
             x[..., rotary_dim:]], axis=-1)
    cos, sin = rope_cos_sin(positions, hd, theta)
    if cos.ndim == 3:              # [B, T, hd] -> broadcast over heads
        cos, sin = cos[:, None], sin[:, None]
    else:                          # [T, hd] -> broadcast over batch+heads
        cos, sin = cos[None, None], sin[None, None]
    x32 = x.astype(jnp.float32)
    out = x32 * cos + _rotate_half(x32) * sin
    return out.astype(x.dtype)


def apply_rope_interleaved(x, positions, theta: float = 10000.0,
                           rotary_dim: int | None = None):
    """Rotate the LAST ``rotary_dim`` channels of ``x [B, H, T, hd]``
    (all of them by default) as INTERLEAVED pairs: channels ``(2i, 2i+1)``
    of that tail turn by the angle ``pos * theta ** (-2i / rotary_dim)``;
    the channels before it pass through. ``positions`` as
    :func:`apply_rope`. A latent-attention head rotates 64 of its 192
    channels this way (``models/hybrid.py``). The half-split form is this
    rotation followed by one fixed permutation of the channels, so scores
    between vectors rotated the same way are equal in both."""
    hd = x.shape[-1]
    rd = hd if rotary_dim is None else rotary_dim
    half = rd // 2
    inv_freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    freqs = positions.astype(jnp.float32)[..., None] * inv_freq   # [.., T, rd/2]
    lift = (lambda a: a[:, None]) if freqs.ndim == 3 else (
        lambda a: a[None, None])
    cos, sin = lift(jnp.cos(freqs)), lift(jnp.sin(freqs))
    tail = x[..., hd - rd:].astype(jnp.float32)
    pairs = tail.reshape(tail.shape[:-1] + (half, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1)
    out = out.reshape(tail.shape).astype(x.dtype)
    if rd == hd:
        return out
    return jnp.concatenate([x[..., :hd - rd], out], axis=-1)
