"""Operations a KERNEL's call needs, from its shapes: what belongs to a
kernel and to no model. A model's own counts (matmul parameters, FLOPs a
token) live in its family's module, ``perfbench/family/<family>.py``;
``families.count_fn`` looks a name up there first and here second."""

from __future__ import annotations


def flash_fwd_flops(batch_heads: int, q_len: int, kv_len: int, head_dim: int,
                    causal: bool) -> float:
    """QK^T and PV, 2 flops per multiply-add; causal counts the half
    (plus the diagonal) that is needed."""
    pairs = (q_len * (q_len + 1) / 2 + q_len * (kv_len - q_len)
             if causal else q_len * kv_len)
    return 4.0 * batch_heads * pairs * head_dim


def flash_bwd_flops(batch_heads: int, q_len: int, kv_len: int, head_dim: int,
                    causal: bool) -> float:
    """The backward needs five matmuls over the same pairs (recompute
    QK^T, dV, dP, dQ, dK) against the forward's two."""
    return 2.5 * flash_fwd_flops(batch_heads, q_len, kv_len, head_dim, causal)


def paged_decode_attn_flops(live_context_tokens: float, q_heads: int,
                            kv_heads: int, head_dim: int,
                            itemsize: int = 2) -> float:
    """One query position a slot against the context tokens live in the
    pool: QK^T and PV, 2 flops per multiply-add, every query head (same
    arguments as ``bytes.paged_decode_attn_bytes``: one call's shape)."""
    return 4.0 * live_context_tokens * q_heads * head_dim
