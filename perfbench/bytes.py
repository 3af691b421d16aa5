"""Bytes a KERNEL's call has to move, from its shapes: what belongs to a
kernel and to no model. A model's own counts (weight bytes, K/V bytes a
token, the bytes of a decode tick) live in its family's module,
``perfbench/family/<family>.py``; ``families.count_fn`` looks a name up
there first and here second."""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def flash_fwd_bytes(batch_heads: int, q_len: int, kv_len: int, head_dim: int,
                    itemsize: int = 2, *, causal: bool = False) -> float:
    """q, k, v read once and o written once (lse is noise; the mask spares
    operations, not bytes: ``causal`` is taken so that a call's shape goes
    to the operation and the byte function alike)."""
    return float(batch_heads * head_dim * itemsize * (2 * q_len + 2 * kv_len))


def flash_bwd_bytes(batch_heads: int, q_len: int, kv_len: int, head_dim: int,
                    itemsize: int = 2, *, causal: bool = False) -> float:
    """q, k, v, o, do read; dq, dk, dv written."""
    return float(batch_heads * head_dim * itemsize * (4 * q_len + 4 * kv_len))


def paged_decode_attn_bytes(live_context_tokens: float, q_heads: int,
                            kv_heads: int, head_dim: int,
                            itemsize: int = 2) -> float:
    """K and V of every context token live in the pool, one layer's, read
    once. The slots' q rows and output rows (slots x query heads x head
    size, twice: 0.5 MB at 32 slots of 32 heads of 128 against 14 MB of
    K/V at 3.5k live tokens) are left out, so the floor is never too
    high."""
    return float(live_context_tokens * 2 * kv_heads * head_dim * itemsize)
