"""Family ``exaone_moe``: a decoder of layer kinds (sliding-window and full
attention mixed, a leading dense layer, then routed experts with a shared
expert of which this chip holds a share), built by the program's
``build_model("hybrid")``. What a family module says is listed in
``perfbench/README.md``, "Adding things". The configuration's file states
the cut: ``num_experts`` is the number of experts HELD here
(``experts_held``), ``router_num_experts`` the router's width."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "hybrid"
REFERENCE = "perfbench.reference.exaone_moe_ref"
DROPOUT_KEYS = ()


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        layer_types=tuple(cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        sliding_window=cfg["sliding_window"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        qk_norm=True, rope_sliding_only=True,
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        num_experts=cfg["router_num_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        param_dtype=jnp.dtype(run.get("param_dtype", "bfloat16")))


def _kinds(cfg: dict, key: str, kind: str) -> int:
    return sum(k == kind for k in cfg[key])


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``decode``: one call of the paged decode-attention kernel, i.e. the
    FULL layer's attention of one tick over every slot (the window layers
    read their rings, not the pool). ``admit_band``: one call of the banded
    flash forward, i.e. one window layer's attention of one admission wave:
    the wave's mean rows over the window (``prefill_rows / prefill_calls``)
    x the query heads, each row a whole prefill window."""
    if which == "decode":
        live = counters.get("mean_live_context_tokens")
        if live is None:
            return None
        return dict(live_context_tokens=live,
                    q_heads=cfg["num_attention_heads"],
                    kv_heads=cfg["num_key_value_heads"],
                    head_dim=cfg["head_dim"], itemsize=ITEMSIZE["bfloat16"])
    if which == "admit_band":
        calls, rows = counters.get("prefill_calls"), counters.get("prefill_rows")
        if not calls or not rows:
            return None
        return dict(rows=rows / calls, q_heads=cfg["num_attention_heads"],
                    kv_heads=cfg["num_key_value_heads"],
                    q_len=cfg["serving"]["prefill_window"],
                    head_dim=cfg["head_dim"], window=cfg["sliding_window"],
                    itemsize=ITEMSIZE["bfloat16"])
    return None


def band_pairs(q_len: int, window: int) -> float:
    """(query, key) pairs of a causal band: row ``i`` sees ``min(i + 1,
    window)`` keys."""
    w = min(window, q_len)
    return w * (w + 1) / 2 + (q_len - w) * w


def flash_band_fwd_flops(rows: float, q_heads: int, kv_heads: int,
                         q_len: int, head_dim: int, window: int,
                         itemsize: int = 2) -> float:
    """QK^T and PV over the band's pairs only, 2 flops per multiply-add,
    every query head."""
    return 4.0 * rows * q_heads * band_pairs(q_len, window) * head_dim


def flash_band_fwd_bytes(rows: float, q_heads: int, kv_heads: int,
                         q_len: int, head_dim: int, window: int,
                         itemsize: int = 2) -> float:
    """q read and o written at the query heads, k and v read once at the
    KV heads (the kernel reads grouped heads in place; what a query head's
    re-read of its group's K/V costs is the kernel's, not the floor's)."""
    return float(rows * q_len * head_dim * itemsize
                 * (2 * q_heads + 2 * kv_heads))


def attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert (the shared expert is num_shared_experts of
    them)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def sparse_mlp_params(cfg: dict) -> int:
    """Router at its published width, the shared expert, the experts HELD."""
    return (cfg["hidden_size"] * cfg["router_num_experts"]
            + (cfg["num_shared_experts"] + cfg["num_experts"])
            * expert_params(cfg))


def exaone_matmul_params(cfg: dict) -> int:
    """Every matrix a decode tick multiplies by: the layers and the head
    (the embedding is a gather of one row a slot)."""
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + _kinds(cfg, "mlp_layer_types", "dense") * dense_mlp_params(cfg)
            + _kinds(cfg, "mlp_layer_types", "sparse") * sparse_mlp_params(cfg)
            + cfg["hidden_size"] * cfg["vocab_size"])


def exaone_weight_bytes(cfg: dict, dtype: str = "bfloat16") -> int:
    """What the chip HOLDS: the matrices, the embedding, the norm scales
    and selection biases (float32)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    f32 = (cfg["num_hidden_layers"] * (2 * d + 2 * hd) + d
           + _kinds(cfg, "mlp_layer_types", "sparse")
           * cfg["router_num_experts"])
    return ((exaone_matmul_params(cfg) + cfg["vocab_size"] * d)
            * ITEMSIZE[dtype] + f32 * 4)


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """K and V of ONE layer for one cached token."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE[dtype]


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One decode tick over all slots: every held weight once (each held
    expert has a token in every tick at this load, so all are read), the
    K/V of every live context token for the FULL layers, and for the window
    layers the live part of the rings: at most ``sliding_window`` tokens a
    slot, whatever the context (``serving.slots`` says how many slots the
    deployment's chip serves)."""
    full = _kinds(cfg, "layer_types", "full_attention")
    sliding = _kinds(cfg, "layer_types", "sliding_attention")
    in_rings = min(live_context_tokens,
                   cfg["serving"]["slots"] * cfg["sliding_window"])
    return (exaone_matmul_params(cfg) * ITEMSIZE[dtype]
            + (full * live_context_tokens + sliding * in_rings)
            * kv_bytes_per_token(cfg, dtype))
