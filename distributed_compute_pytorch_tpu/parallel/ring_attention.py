"""Ring attention — sequence/context parallelism over a ``seq`` mesh axis.

Long-context support the reference never had (its model has no attention at
all, SURVEY.md §5.7); first-class here per the framework mandate. The design
is the TPU-idiomatic ring schedule (Liu et al., Ring Attention with Blockwise
Transformers): Q stays put, K/V blocks rotate around the ``seq`` axis via
``lax.ppermute`` (neighbour exchange rides the ICI torus), and each step
folds one K/V block into a running flash-attention-style online softmax
(running max ``m``, normaliser ``l``, accumulator ``o``). Peak memory per
chip is O(T/P) in sequence instead of O(T), and logits never materialise as
a [T, T] tensor.

Causal masking is chunk-aware: a device skips compute-masking only where
needed — each rotation step knows which global K/V chunk it holds, so the
mask is exact across chunk boundaries.

The public entry nests ``shard_map`` inside the caller's jit, so it composes
with the data/fsdp/tensor axes of the same mesh.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

_NEG_INF = -1e30  # finite "minus infinity": keeps the online softmax NaN-free


def _block_attend(q, kb, vb, o, m, l, q_pos, k_pos, scale, causal,
                  mask_b=None):
    """Fold one K/V block into the running (o, m, l) online softmax.

    ``mask_b``: optional ``[b, chunk]`` key-validity block (padding mask)
    that travelled around the ring with this K/V block."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kb,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        allowed = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(allowed, s, _NEG_INF)
    if mask_b is not None:
        s = jnp.where(mask_b[:, None, None, :] > 0.5, s, _NEG_INF)
    row_max = jnp.max(s, axis=-1)                       # [b,h,q]
    m_new = jnp.maximum(m, row_max)
    corr = jnp.exp(m - m_new)                           # rescale old mass
    p = jnp.exp(s - m_new[..., None])
    if causal:
        p = jnp.where(allowed[None, None], p, 0.0)
    if mask_b is not None:
        p = jnp.where(mask_b[:, None, None, :] > 0.5, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum(
        "bhqk,bhkd->bhqd", p, vb.astype(p.dtype))
    return o_new, m_new, l_new


def ring_attention_manual(q, k, v, axis: str, n_chunks: int, *,
                          causal: bool = False, scale: float | None = None,
                          kv_mask=None, vary: tuple = ()):
    """Ring-attention body for callers ALREADY inside a manual region.

    The pipeline (``parallel/pipeline.py``) runs its stages inside a
    ``shard_map`` that is manual over ``pipe`` (and, when the mesh carries
    one, ``seq``) — a nested ``shard_map`` cannot sit inside that region,
    but this body can: it is plain ``ppermute``/``axis_index`` code. This
    is what lifts the former pipe-x-seq ``NotImplementedError``.

    Args:
      q, k, v: LOCAL blocks ``[b, h, t_local, d]`` (seq already split over
        ``axis``).
      n_chunks: ring size (``mesh.shape[axis]`` at trace time — callers
        inside a manual region still know their mesh statically).
      kv_mask: optional LOCAL ``[b, t_local]`` key-validity chunk; rotates
        with its K/V block.
      vary: every manual axis the inputs vary over (the online-softmax
        carries must be pcast to match before mixing with them).

    GQA: ``q`` may carry ``G x`` more heads than ``k``/``v`` (query head
    ``h`` reads kv head ``h // G``). The group dim is folded into q's
    sequence dim (positions tiled to match) so the ring rotates ONLY the
    true kv heads — a ``jnp.repeat`` before the ring would move ``G x``
    the bytes over ICI and hold ``G x`` the K/V block memory per chip.

    Returns the LOCAL attention output ``[b, h_q, t_local, d]``.
    """
    b, hq, chunk, d = q.shape
    hk = k.shape[1]
    assert hq % hk == 0, (hq, hk)
    groups = hq // hk
    scale = (d ** -0.5) if scale is None else scale
    mk = None if kv_mask is None else kv_mask.astype(jnp.float32)
    my_chunk = lax.axis_index(axis)
    my_pos = my_chunk * chunk + jnp.arange(chunk)   # this device's chunk
    q_pos = my_pos
    if groups > 1:
        # [b, hk*G, t, d] -> [b, hk, G*t, d]: query head kv*G+g lands at
        # group-sequence slot g*t+i of kv head kv, positions tiled to
        # match; KEY positions stay chunk-length (K/V are not folded)
        q = q.reshape(b, hk, groups * chunk, d)
        q_pos = jnp.tile(q_pos, groups)
    tq = q.shape[2]            # group-folded query length (G * chunk)
    vary = tuple(vary) or (axis,)
    o = lax.pcast(jnp.zeros((b, hk, tq, d), jnp.float32), vary,
                  to="varying")
    m = lax.pcast(jnp.full((b, hk, tq), _NEG_INF, jnp.float32), vary,
                  to="varying")
    l = lax.pcast(jnp.zeros((b, hk, tq), jnp.float32), vary, to="varying")

    # local block first (no communication), then permute-then-attend for
    # the remaining n-1 blocks — exactly n-1 neighbour exchanges total.
    o, m, l = _block_attend(q, k, v, o, m, l, q_pos, my_pos, scale,
                            causal, mk)
    perm = [(j, (j + 1) % n_chunks) for j in range(n_chunks)]

    def body(carry, step):
        o, m, l, kb, vb, mb = carry
        kb = lax.ppermute(kb, axis, perm)
        vb = lax.ppermute(vb, axis, perm)
        if mb is not None:
            mb = lax.ppermute(mb, axis, perm)
        # after `step` rotations we hold the block that started on
        # device (my_chunk - step) mod P
        src = (my_chunk - step) % n_chunks
        k_pos = src * chunk + jnp.arange(chunk)
        o, m, l = _block_attend(q, kb, vb, o, m, l, q_pos, k_pos,
                                scale, causal, mb)
        return (o, m, l, kb, vb, mb), None

    if n_chunks > 1:
        (o, m, l, *_), _ = lax.scan(body, (o, m, l, k, v, mk),
                                    jnp.arange(1, n_chunks))
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    if groups > 1:
        out = out.reshape(b, hq, chunk, d)   # unfold the group dim
    return out


def ring_attention(q, k, v, mesh: Mesh, axis: str = "seq", *,
                   causal: bool = False, scale: float | None = None,
                   kv_mask=None):
    """Sequence-parallel attention over ``mesh``'s ``axis``.

    Args:
      q, k, v: ``[batch, heads, seq, head_dim]`` global arrays whose ``seq``
        dim is (or will be) sharded over ``axis``. batch may additionally be
        sharded over the batch axes; heads over ``tensor``.
      kv_mask: optional ``[batch, seq]`` key-validity (padding) mask, True =
        attend; its seq dim shards over ``axis`` and each chunk rotates
        around the ring with its K/V block.
    Returns the attention output with the same sharding as ``q``.
    """
    head_dim = q.shape[-1]
    scale = (head_dim ** -0.5) if scale is None else scale
    n_chunks = mesh.shape[axis]
    if n_chunks == 1:
        from distributed_compute_pytorch_tpu.ops.attention import (
            dot_product_attention)
        if k.shape[1] != q.shape[1]:   # GQA: dense path needs full heads
            rep = q.shape[1] // k.shape[1]
            k = jnp.repeat(k, rep, axis=1)
            v = jnp.repeat(v, rep, axis=1)
        mask = (None if kv_mask is None
                else kv_mask[:, None, None, :].astype(bool))
        return dot_product_attention(q, k, v, causal=causal, scale=scale,
                                     mask=mask)
    # batch/head dims keep whatever sharding they already have; we only
    # manage the seq dim explicitly. data/fsdp shard batch, tensor shards
    # heads — all compose because shard_map specs name only mesh axes that
    # exist.
    names = mesh.axis_names
    batch_axes = tuple(a for a in ("data", "fsdp") if a in names) or None
    head_axes = "tensor" if "tensor" in names else None
    spec = P(batch_axes, head_axes, axis, None)

    vary = tuple(a for a in ((batch_axes or ()) + ((head_axes,)
                 if head_axes else ()) + (axis,)))
    mask_spec = P(batch_axes, axis)
    masked = kv_mask is not None
    if masked:
        kv_mask = kv_mask.astype(jnp.float32)

    @partial(jax.shard_map, mesh=mesh,
             in_specs=((spec, spec, spec, mask_spec) if masked
                       else (spec, spec, spec)),
             out_specs=spec)
    def _ring(q, k, v, *maybe_mask):
        mk = maybe_mask[0] if masked else None
        return ring_attention_manual(q, k, v, axis, n_chunks, causal=causal,
                                     scale=scale, kv_mask=mk, vary=vary)

    return _ring(q, k, v, kv_mask) if masked else _ring(q, k, v)
