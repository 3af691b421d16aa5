"""Bytes the algorithm has to move, from shapes."""

from __future__ import annotations

_ITEM = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """K and V of every layer for one cached token."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_key_value_heads"] * hd * _ITEM[dtype]
            * cfg["num_hidden_layers"])


def llama_weight_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Every weight a decode tick has to read once: the block matrices,
    the read-out head and the norms (the embedding table is a gather of
    one row per slot, not a read of the table)."""
    from perfbench.flops import llama_matmul_params
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return (llama_matmul_params(cfg) + norms) * _ITEM[dtype]


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One decode tick over all slots: the weights once + the K/V of every
    live context token once."""
    return (llama_weight_bytes(cfg, dtype)
            + live_context_tokens * kv_bytes_per_token(cfg, dtype))


def flash_fwd_bytes(batch_heads: int, q_len: int, kv_len: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """q, k, v read once and o written once (lse is noise)."""
    return float(batch_heads * head_dim * itemsize * (2 * q_len + 2 * kv_len))


def flash_bwd_bytes(batch_heads: int, q_len: int, kv_len: int, head_dim: int,
                    itemsize: int = 2) -> float:
    """q, k, v, o, do read; dq, dk, dv written."""
    return float(batch_heads * head_dim * itemsize * (4 * q_len + 4 * kv_len))
