"""Family ``joyai_llm_flash``: a decoder whose every layer is LATENT
attention (MLA: low-rank queries, one joint compressed K/V vector a token
with a shared rotary key, an expanded prefill form and an absorbed decode
form over one compressed paged cache), a leading dense layer, then routed
experts with a shared expert of which this chip holds a share; norms on
each sublayer's input. Built by the program's ``build_model("hybrid")``.
What a family module says is listed in ``perfbench/README.md``, "Adding
things". The configuration's file states the cut: ``n_routed_experts`` is
the number of experts HELD here (``experts_held``), ``router_num_experts``
the router's width."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "hybrid"
REFERENCE = "perfbench.reference.joyai_llm_flash_ref"
DROPOUT_KEYS = ()


def _mlp_kinds(cfg: dict) -> tuple:
    dense = cfg["first_k_dense_replace"]
    return ("dense",) * dense + ("sparse",) * (cfg["num_hidden_layers"]
                                               - dense)


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        layer_types=("latent_attention",) * cfg["num_hidden_layers"],
        mlp_layer_types=_mlp_kinds(cfg),
        num_heads=cfg["num_attention_heads"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], norm_placement="pre", qk_norm=False,
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        num_experts=cfg["router_num_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"],
        shared_d_ff=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
        routed_scale=float(cfg["routed_scaling_factor"]),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        param_dtype=jnp.dtype(run.get("param_dtype", "bfloat16")))


def latent_width(cfg: dict) -> int:
    """Channels the cache keeps of a token, one layer: the compressed K/V
    and the shared rotary key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``decode_latent``: one call of the latent decode-attention kernel,
    i.e. one layer's attention of one tick over every slot.
    ``admit_latent``: one call of the flash forward at q/k width 192 and v
    width 128, i.e. one layer's attention of one admission dispatch: the
    run's mean rows a dispatch (``prefill_rows / prefill_calls``, at least
    one) of the mean window a row (``prefill_window_tokens /
    prefill_calls`` over those rows), never a fixed window. The pairs of a
    causal row grow with the square of its length, so the mean row counts
    no more operations than the run's mix of rows needed."""
    heads = cfg["num_attention_heads"]
    if which == "decode_latent":
        live = counters.get("mean_live_context_tokens")
        if live is None:
            return None
        return dict(live_context_tokens=live, q_heads=heads,
                    latent_width=latent_width(cfg),
                    value_width=cfg["kv_lora_rank"],
                    itemsize=ITEMSIZE["bfloat16"])
    if which == "admit_latent":
        calls, rows = counters.get("prefill_calls"), counters.get("prefill_rows")
        window = counters.get("prefill_window_tokens")
        if not calls or not rows or not window:
            return None
        rows_a_call = max(rows / calls, 1.0)
        return dict(rows=rows_a_call, q_heads=heads,
                    q_len=window / calls / rows_a_call,
                    qk_head_dim=cfg["qk_nope_head_dim"]
                    + cfg["qk_rope_head_dim"],
                    v_head_dim=cfg["v_head_dim"],
                    itemsize=ITEMSIZE["bfloat16"])
    return None


def latent_decode_attn_flops(live_context_tokens: float, q_heads: int,
                             latent_width: int, value_width: int,
                             itemsize: int = 2) -> float:
    """One absorbed query a head a slot against the tokens live in the
    pool: scores over the whole cached vector, values over its compressed
    part, 2 flops per multiply-add."""
    return 2.0 * live_context_tokens * q_heads * (latent_width + value_width)


def latent_decode_attn_bytes(live_context_tokens: float, q_heads: int,
                             latent_width: int, value_width: int,
                             itemsize: int = 2) -> float:
    """The cached vector of every live token, one layer's, read ONCE at
    its published width (the pool lays 576 channels out in 640 lanes; what
    the padding costs is the kernel's, not the floor's). Queries and
    outputs (slots x heads x ~1100 channels) are left out, so the floor is
    never too high."""
    return float(live_context_tokens * latent_width * itemsize)


def causal_pairs(q_len: float) -> float:
    return q_len * (q_len + 1) / 2


def latent_flash_fwd_flops(rows: float, q_heads: int, q_len: float,
                           qk_head_dim: int, v_head_dim: int,
                           itemsize: int = 2) -> float:
    """QK^T at the q/k width and PV at the v width over the causal pairs,
    2 flops per multiply-add, every head: the published operations,
    whatever a kernel pads."""
    return (2.0 * rows * q_heads * causal_pairs(q_len)
            * (qk_head_dim + v_head_dim))


def latent_flash_fwd_bytes(rows: float, q_heads: int, q_len: float,
                           qk_head_dim: int, v_head_dim: int,
                           itemsize: int = 2) -> float:
    """q and k read at the q/k width, v read and o written at the v width,
    once each."""
    return float(rows * q_len * q_heads * itemsize
                 * (2 * qk_head_dim + 2 * v_head_dim))


def attention_params(cfg: dict) -> int:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    n, r, v = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
               cfg["v_head_dim"])
    return (d * cfg["q_lora_rank"] + cfg["q_lora_rank"] * H * (n + r)
            + d * latent_width(cfg) + cfg["kv_lora_rank"] * H * (n + v)
            + H * v * d)


def dense_mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg: dict) -> int:
    """One routed expert (the shared expert is n_shared_experts of them)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["router_num_experts"]


def joyai_matmul_params(cfg: dict, held_share: float = 1.0) -> float:
    """Every matrix a decode tick multiplies by: the layers and the head
    (the embedding is a gather of one row a slot), the held routed experts
    counted at ``held_share`` of them."""
    dense = cfg["first_k_dense_replace"]
    sparse = cfg["num_hidden_layers"] - dense
    return (cfg["num_hidden_layers"] * attention_params(cfg)
            + dense * dense_mlp_params(cfg)
            + sparse * (router_params(cfg)
                        + (cfg["n_shared_experts"]
                           + held_share * cfg["n_routed_experts"])
                        * expert_params(cfg))
            + cfg["hidden_size"] * cfg["vocab_size"])


def joyai_weight_params(cfg: dict) -> int:
    """What the chip HOLDS, in matrix elements: the matrices a tick
    multiplies by and the embedding (norm scales and selection biases, a
    few thousand float32, apart)."""
    return int(joyai_matmul_params(cfg)) + cfg["vocab_size"] * cfg["hidden_size"]


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
    UNIFORM router (87% at 64 rows; an expert no row chose need not be
    read), and the cached vector of every live context token for every
    layer at its published width. A skewed router touches fewer (the cell
    reads ``expert_load_max_over_mean`` 3.5-4.1), so in principle this floor
    can sit above what a tick must read; the program's decode form reads
    all the held experts, so its share of this floor stays under 100%."""
    share = experts_touched_share(cfg, cfg["serving"]["slots"])
    return (joyai_matmul_params(cfg, share) * ITEMSIZE[dtype]
            + cfg["num_hidden_layers"] * live_context_tokens
            * latent_width(cfg) * ITEMSIZE[dtype])
