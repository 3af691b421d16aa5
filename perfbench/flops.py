"""Operations the algorithm needs, from shapes. Recomputation (remat) is
never counted: these are the model's operations, not the program's."""

from __future__ import annotations


def gpt2_matmul_params(cfg: dict) -> int:
    """Parameters that take part in a matmul per token: the four block
    matrices of every layer and the tied read-out (biases, LayerNorms and
    the position table do no matmul)."""
    d, ff = cfg["n_embd"], cfg["n_inner"] or 4 * cfg["n_embd"]
    per_layer = d * 3 * d + d * d + d * ff + ff * d
    return cfg["n_layer"] * per_layer + cfg["vocab_size"] * d


def gpt2_train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """6 x matmul parameters (2 forward, 4 backward) + causal attention at
    the half that is needed: QK^T and PV are 2*T*d each per token over the
    full square, T*d each over the causal half; x3 for forward+backward
    => 6*T*d per layer per token."""
    return (6.0 * gpt2_matmul_params(cfg)
            + 6.0 * cfg["n_layer"] * seq_len * cfg["n_embd"])


def llama_matmul_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    per_layer = q + kv + o + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def llama_forward_flops_per_token(cfg: dict, context: float) -> float:
    """One token's forward pass attending ``context`` cached positions:
    2 x matmul parameters (read-out included) + 4*context*H*hd attention
    (QK^T and PV, 2 flops per multiply-add each)."""
    hd = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    attn = 4.0 * context * cfg["num_attention_heads"] * hd
    return (2.0 * llama_matmul_params(cfg)
            + cfg["num_hidden_layers"] * attn)


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
