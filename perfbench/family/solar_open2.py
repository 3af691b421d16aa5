"""Family ``solar_open2`` (``model_type: solar_open2``): a pre-norm decoder
of two mixers in a period of four: one FULL layer (softmax GQA, 64 query
heads on 8 key-value heads of 128, no rotation, the merged heads times the
sigmoid of an output gate before ``W_o``; a K/V pair a token in the paged
pool) to three KDA linear-attention layers (three short causal
convolutions, unit-length q/k, a per-channel softplus decay with NO floor,
the delta rule with ``beta`` up to 2 on a ``[128, 128]`` float32 state a
head, which is all a slot keeps of its context), every layer's feed-forward
routed experts with a shared expert of which this chip holds a share. Built
by the program's ``build_model("hybrid")``. What a family module says is
listed in ``perfbench/README.md``, "Adding things". The configuration's file
states the cut: ``n_routed_experts`` is the number of experts HELD here
(``experts_held``), ``router_num_experts`` the router's width.

Kernels: the full layer's decode read is ``dcp_paged_decode_attn`` (shape
``decode``), the KDA layers' admission recurrence ``dcp_kda_chunk_scan``
(read by its share, as the accepted benchmark reads it); the one-token step
of the state is XLA's, read by its scope against the state's own bytes
(:func:`kda_step_bytes`)."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "hybrid"
REFERENCE = "perfbench.reference.solar_open2_ref"
DROPOUT_KEYS = ()


def is_full(cfg: dict, l: int) -> bool:
    return l in cfg["gqa_layers"]


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    la = cfg["linear_attn_config"]
    depth = cfg["num_hidden_layers"]
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        layer_types=tuple("full_attention" if is_full(cfg, l)
                          else "linear_attention" for l in range(depth)),
        mlp_layer_types=("sparse",) * depth,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        # use_rope false: nothing rotates (the KDA layers never do)
        qk_norm=False, rope_sliding_only=True,
        attn_gate=bool(cfg["use_gqa_gate"]), norm_placement="pre",
        rms_eps=float(cfg["rms_norm_eps"]),
        kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        kda_conv=la["short_conv_kernel_size"],
        kda_gate_rank=cfg["kda_gate_rank"],
        kda_gate_lower_bound=None,
        kda_allow_neg_eigval=bool(cfg["kda_allow_neg_eigval"]),
        num_experts=cfg["router_num_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        param_dtype=jnp.dtype(run.get("param_dtype", "bfloat16")))


# ---- what a layer holds, in matrix elements -------------------------------

def _kda_width(cfg: dict) -> int:
    la = cfg["linear_attn_config"]
    return la["num_heads"] * la["head_dim"]


def _kda_layers(cfg: dict) -> int:
    return sum(not is_full(cfg, l) for l in range(cfg["num_hidden_layers"]))


def kda_params(cfg: dict) -> int:
    """One KDA mixer: q, k, v and o, the two low-rank gates, beta, the
    three convolutions."""
    d, C, R = cfg["hidden_size"], _kda_width(cfg), cfg["kda_gate_rank"]
    la = cfg["linear_attn_config"]
    return (4 * d * C + 2 * (d * R + R * C) + d * la["num_heads"]
            + 3 * la["short_conv_kernel_size"] * C)


def full_params(cfg: dict) -> int:
    """One full mixer: q, o and the output gate at the query heads' width,
    k and v at the key-value heads'."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (3 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def expert_params(cfg: dict) -> int:
    """One routed expert (the shared expert is n_shared_experts of them)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["router_num_experts"]


def solar_matmul_params(cfg: dict) -> int:
    """Every matrix a decode tick multiplies by: the layers with ALL the
    held experts and the head (the embedding is a gather of one row a
    slot)."""
    total = cfg["hidden_size"] * cfg["vocab_size"]
    for l in range(cfg["num_hidden_layers"]):
        total += full_params(cfg) if is_full(cfg, l) else kda_params(cfg)
        total += router_params(cfg) + (
            cfg["n_shared_experts"] + cfg["n_routed_experts"]
        ) * expert_params(cfg)
    return total


def solar_weight_params(cfg: dict) -> int:
    """What the chip HOLDS, in matrix elements: the matrices a tick
    multiplies by and the embedding (norm scales, rates and biases, a few
    tens of thousands of float32, apart)."""
    return solar_matmul_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]


# ---- what a slot and a token keep ------------------------------------------

def kda_state_bytes_per_slot(cfg: dict, dtype: str = "bfloat16") -> int:
    """ONE KDA layer's keep of one slot: the float32 state of every head
    and the last ``short_conv_kernel_size - 1`` tokens' projected q, k, v
    (in the served type). It does not grow with the context."""
    la = cfg["linear_attn_config"]
    return (la["num_heads"] * la["head_dim"] ** 2 * ITEMSIZE["float32"]
            + (la["short_conv_kernel_size"] - 1) * 3 * _kda_width(cfg)
            * ITEMSIZE[dtype])


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """K and V of ONE full layer for one cached token."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE[dtype]


def kda_step_bytes(cfg: dict, rows: float, dtype: str = "bfloat16") -> float:
    """What the KDA layers' one-token steps have to move for ``rows``
    slot-ticks: each layer reads a row's state and tail and writes them
    back."""
    return 2.0 * rows * _kda_layers(cfg) * kda_state_bytes_per_slot(cfg, dtype)


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One decode tick over all slots: every matrix once (ALL the held
    experts: 160 rows of 8 over 320 touch ``1 - (1 - 8 / 320) ^ 160`` = 98%
    of them under a uniform router), every KDA
    layer's state and tail of every slot read and written once, and the
    full layers' K/V of the live context."""
    full = cfg["num_hidden_layers"] - _kda_layers(cfg)
    return (solar_matmul_params(cfg) * ITEMSIZE[dtype]
            + kda_step_bytes(cfg, cfg["serving"]["slots"], dtype)
            + full * live_context_tokens * kv_bytes_per_token(cfg, dtype))


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``decode``: one call of the paged decode-attention kernel, i.e. the
    FULL layer's attention of one tick over every slot."""
    if which != "decode":
        return None
    live = counters.get("mean_live_context_tokens")
    if live is None:
        return None
    return dict(live_context_tokens=live,
                q_heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], itemsize=ITEMSIZE["bfloat16"])
