"""Family ``mistral``: a RoPE / RMSNorm / SwiGLU decoder with grouped-query
attention over full contexts and an untied read-out, built by the
program's ``build_model("llama")``. What a family module says is listed in
``perfbench/README.md``, "Adding things"."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "llama"
REFERENCE = "perfbench.reference.llama_ref"
DROPOUT_KEYS = ()


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        param_dtype=jnp.dtype(run.get("param_dtype", "bfloat16")))


def _head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``decode``: one call of the paged decode-attention kernel, i.e. one
    layer's attention of one tick over every slot, as
    ``flops.paged_decode_attn_flops`` / ``bytes.paged_decode_attn_bytes``
    take it: the context tokens live in the pool (the traced slice's mean),
    heads, head size, the pool's item size (bfloat16, as every cell of this
    family keeps its pool; an int8 pool goes through the gather, not this
    kernel)."""
    live = counters.get("mean_live_context_tokens")
    if which != "decode" or live is None:
        return None
    return dict(live_context_tokens=live,
                q_heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=_head_dim(cfg), itemsize=ITEMSIZE["bfloat16"])


def llama_matmul_params(cfg: dict) -> int:
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = _head_dim(cfg)
    q = d * cfg["num_attention_heads"] * hd
    kv = 2 * d * cfg["num_key_value_heads"] * hd
    o = cfg["num_attention_heads"] * hd * d
    per_layer = q + kv + o + 3 * d * ff
    return cfg["num_hidden_layers"] * per_layer + cfg["vocab_size"] * d


def llama_forward_flops_per_token(cfg: dict, context: float) -> float:
    """One token's forward pass attending ``context`` cached positions:
    2 x matmul parameters (read-out included) + 4*context*H*hd attention
    (QK^T and PV, 2 flops per multiply-add each)."""
    attn = 4.0 * context * cfg["num_attention_heads"] * _head_dim(cfg)
    return (2.0 * llama_matmul_params(cfg)
            + cfg["num_hidden_layers"] * attn)


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """K and V of every layer for one cached token."""
    return (2 * cfg["num_key_value_heads"] * _head_dim(cfg) * ITEMSIZE[dtype]
            * cfg["num_hidden_layers"])


def llama_weight_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """Every weight a decode tick has to read once: the block matrices,
    the read-out head and the norms (the embedding table is a gather of
    one row per slot, not a read of the table)."""
    d = cfg["hidden_size"]
    norms = (2 * cfg["num_hidden_layers"] + 1) * d
    return (llama_matmul_params(cfg) + norms) * ITEMSIZE[dtype]


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One decode tick over all slots: the weights once + the K/V of every
    live context token once (every layer is dense and attends its whole
    context: a family with a window, a latent cache or routed experts
    states another floor under this name)."""
    return (llama_weight_bytes(cfg, dtype)
            + live_context_tokens * kv_bytes_per_token(cfg, dtype))
