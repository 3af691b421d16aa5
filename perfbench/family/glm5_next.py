"""Family ``glm5_next`` (``model_type: glm5_next_text``): a decoder of two
mixers in a period of four: three KDA linear-attention layers (three short
causal convolutions, unit-length q/k, a per-channel bounded decay, the
delta rule on a ``[128, 128]`` float32 state a head, which is ALL a slot
keeps of its context) to one SPARSE LATENT layer (NoPE latent attention
over the tokens a learned indexer chose by pooled index keys, a latent
pool and a pooled index-key pool on one block table), every sublayer
wrapped in a four-stream hyper-connection (three maps a sublayer, the
residual one through Sinkhorn), a leading dense layer, then routed experts
with a shared expert of which this chip holds a share, every SwiGLU
clamped. Built by the program's ``build_model("hybrid")``. What a family
module says is listed in ``perfbench/README.md``, "Adding things". The
configuration's file states the cut: ``n_routed_experts`` is the number of
experts HELD here (``experts_held``), ``router_num_experts`` the router's
width.

No kernel of this family's own is in the program yet (the chunked scan, the
state step and the selected read are XLA's: ``PERF.md`` section 3), so
the module has no ``kernel_shapes`` and every new mechanism is read by its
scope's share."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "hybrid"
REFERENCE = "perfbench.reference.glm5_next_ref"
DROPOUT_KEYS = ()

_MIXER = {"linear_attention": "linear_attention",
          "deepseek_sparse_attention": "sparse_latent_attention"}


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    la = cfg["linear_attn_config"]
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        layer_types=tuple(_MIXER[k] for k in cfg["layer_types"]),
        mlp_layer_types=tuple(cfg["mlp_layer_types"]),
        num_heads=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], norm_placement="pre", qk_norm=False,
        rms_eps=float(cfg["rms_norm_eps"]),
        kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        kda_conv=la["short_conv_kernel_size"],
        kda_gate_rank=cfg["kda_gate_rank"],
        kda_gate_lower_bound=float(la["gate_lower_bound"]),
        index_heads=cfg["index_n_heads"],
        index_head_dim=cfg["index_head_dim"],
        index_topk=cfg["index_topk"], index_pool=cfg["index_kpool"],
        index_rope_dim=cfg["index_rope_dim"],
        index_rope_theta=float(cfg["index_rope_theta"]),
        hc_mult=cfg["hc_mult"], hc_sinkhorn_iters=cfg["hc_sinkhorn_iters"],
        hc_eps=float(cfg["hc_eps"]),
        swiglu_limit=float(cfg["swiglu_limit"]),
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


def kda_params(cfg: dict) -> int:
    """One KDA mixer: q, k, v and o, the two low-rank gates, beta, the
    three convolutions."""
    d, C, R = cfg["hidden_size"], _kda_width(cfg), cfg["kda_gate_rank"]
    la = cfg["linear_attn_config"]
    return (4 * d * C + 2 * (d * R + R * C) + d * la["num_heads"]
            + 3 * la["short_conv_kernel_size"] * C)


def sparse_latent_params(cfg: dict) -> int:
    """One sparse latent mixer: the latent projections and the indexer."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    ql, kvl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    Hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (d * ql + ql * H * (n + r) + d * (kvl + r) + kvl * H * (n + v)
            + H * v * d + ql * Hi * di + d * di + d * Hi)


def hyper_params(cfg: dict) -> int:
    """The three maps of BOTH sublayers of a layer."""
    n, d = cfg["hc_mult"], cfg["hidden_size"]
    return 2 * n * d * (2 * n + n * n)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert (the shared expert is n_shared_experts of them)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["router_num_experts"]


def glm_matmul_params(cfg: dict, held_share: float = 1.0) -> float:
    """Every matrix a decode tick multiplies by: the layers and the head
    (the embedding is a gather of one row a slot), the held routed experts
    counted at ``held_share`` of them."""
    total = float(cfg["hidden_size"] * cfg["vocab_size"])
    for mixer, mlp in zip(cfg["layer_types"], cfg["mlp_layer_types"]):
        total += hyper_params(cfg) + (
            kda_params(cfg) if mixer == "linear_attention"
            else sparse_latent_params(cfg))
        total += dense_mlp_params(cfg) if mlp == "dense" else (
            router_params(cfg) + (cfg["n_shared_experts"] + held_share
                                  * cfg["n_routed_experts"])
            * expert_params(cfg))
    return total


def glm_weight_params(cfg: dict) -> int:
    """What the chip HOLDS, in matrix elements: the matrices a tick
    multiplies by and the embedding (norm scales, gains and biases, a few
    tens of thousands of float32, apart)."""
    return int(glm_matmul_params(cfg)) + cfg["vocab_size"] * cfg["hidden_size"]


# ---- what a slot and a token keep ------------------------------------------

def kda_state_bytes_per_slot(cfg: dict, dtype: str = "bfloat16") -> int:
    """ONE KDA layer's keep of one slot: the float32 state of every head
    and the last ``short_conv_kernel_size - 1`` tokens' projected q, k, v
    (in the served type). It does not grow with the context."""
    la = cfg["linear_attn_config"]
    return (la["num_heads"] * la["head_dim"] ** 2 * ITEMSIZE["float32"]
            + (la["short_conv_kernel_size"] - 1) * 3 * _kda_width(cfg)
            * ITEMSIZE[dtype])


def sparse_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> float:
    """ONE sparse latent layer's keep of one cached token: the compressed
    vector and a ``index_kpool``-th of a pooled index key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
            + cfg["index_head_dim"] / cfg["index_kpool"]) * ITEMSIZE[dtype]


def tokens_attended(cfg: dict) -> float:
    """Tokens a row of a long context attends in a sparse layer: the chosen
    groups and, on average, half a group of tail."""
    return cfg["index_topk"] + (cfg["index_kpool"] + 1) / 2


def experts_touched_share(cfg: dict, rows: int) -> float:
    """Share of the held experts that a tick of ``rows`` tokens sends at
    least one token to, under a uniform router: ``1 - (1 - k / E) ^
    rows``."""
    k, E = cfg["num_experts_per_tok"], cfg["router_num_experts"]
    return 1.0 - (1.0 - k / E) ** rows


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One decode tick over all slots: every matrix once, the held routed
    experts at the share a tick of ``serving.slots`` rows touches under a
    UNIFORM router (59% at 32 rows), every KDA layer's state and tail of
    every slot read and written once, and a sparse layer's pooled index
    keys of the live context with the latent vectors of the tokens a row
    attends (``index_topk`` + tail, never more than its context: the cell's
    contexts are all past that). The program's decode form reads all the
    held experts, so its share of this floor stays under 100%."""
    slots = cfg["serving"]["slots"]
    n_kda = cfg["layer_types"].count("linear_attention")
    n_sparse = len(cfg["layer_types"]) - n_kda
    share = experts_touched_share(cfg, slots)
    attended = min(slots * tokens_attended(cfg), live_context_tokens)
    return (glm_matmul_params(cfg, share) * ITEMSIZE[dtype]
            + n_kda * slots * 2 * kda_state_bytes_per_slot(cfg, dtype)
            + n_sparse * (live_context_tokens * cfg["index_head_dim"]
                          / cfg["index_kpool"]
                          + attended * cfg["kv_lora_rank"])
            * ITEMSIZE[dtype])
