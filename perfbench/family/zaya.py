"""Family ``zaya``: a decoder whose every layer is COMPRESSED CONVOLUTIONAL
attention (CCA: queries and keys projected down to the heads' width, two
short causal convolutions over the sequence, a query-key mean, unit-length
heads with a key temperature, partial rotation, half of the value heads of
the token before; a K/V pair a token in the paged pool and a fixed-size
tail a slot beside it) over top-1 experts behind a router that is a small
network with state carried from layer to layer and a skip choice; scaled
residual merges, tied embedding and head. Built by the program's
``build_model("hybrid")``. What a family module says is listed in
``perfbench/README.md``, "Adding things". The configuration's file states
the cut: one stage of a four-stage pipeline, every expert held."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "hybrid"
REFERENCE = "perfbench.reference.zaya_ref"
DROPOUT_KEYS = ()


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    L = cfg["num_hidden_layers"]
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        layer_types=("cca_attention",) * L,
        mlp_layer_types=("sparse_top1",) * L,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_model=cfg["hidden_size"], qk_norm=False, norm_placement="pre",
        cca_time0=cfg["cca_time0"], cca_time1=cfg["cca_time1"],
        partial_rotary_factor=float(cfg["partial_rotary_factor"]),
        rope_theta=float(cfg["rope_theta"]),
        rms_eps=float(cfg["rms_norm_eps"]),
        # the router's choices: every expert and, last, the skip
        num_experts=cfg["num_experts"] + 1,
        experts_held=(0, cfg["num_experts"]),
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"], shared_d_ff=0,
        router_hidden=cfg["router_hidden_size"],
        scale_residual_merge=True,
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        param_dtype=jnp.dtype(run.get("param_dtype", "bfloat16")))


def cca_width(cfg: dict) -> int:
    """Channels of the compressed query and key together."""
    return ((cfg["num_attention_heads"] + cfg["num_key_value_heads"])
            * cfg["head_dim"])


def tail_width(cfg: dict) -> int:
    """Channels of a layer's per-slot tail: ``u`` of the last two tokens
    and the value half the last one leaves the next."""
    return (2 * cca_width(cfg)
            + cfg["num_key_value_heads"] * cfg["head_dim"] // 2)


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """K and V of ONE layer for one cached token."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE[dtype]


def tail_bytes_per_slot(cfg: dict, dtype: str = "bfloat16") -> int:
    """ONE layer's tail of one slot."""
    return tail_width(cfg) * ITEMSIZE[dtype]


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``decode``: one call of the paged decode-attention kernel, i.e. one
    layer's attention of one tick over every slot (as ``family/mistral.py``
    answers it). ``admit_cca``: one call of the flash forward, i.e. one
    layer's attention of one admission dispatch: the run's mean rows a
    dispatch (``prefill_rows / prefill_calls``, at least one) times the
    query heads (the program repeats the two key heads to eight) of the
    mean window a row (``prefill_window_tokens / prefill_calls`` over those
    rows), never a fixed window: the pairs of a causal row grow with the
    square of its length, so the mean row counts no more operations than
    the run's mix of rows needed."""
    if which == "decode":
        live = counters.get("mean_live_context_tokens")
        if live is None:
            return None
        return dict(live_context_tokens=live,
                    q_heads=cfg["num_attention_heads"],
                    kv_heads=cfg["num_key_value_heads"],
                    head_dim=cfg["head_dim"], itemsize=ITEMSIZE["bfloat16"])
    if which == "admit_cca":
        calls, rows = counters.get("prefill_calls"), counters.get("prefill_rows")
        window = counters.get("prefill_window_tokens")
        if not calls or not rows or not window:
            return None
        rows_a_call = max(rows / calls, 1.0)
        q_len = window / calls / rows_a_call
        return dict(batch_heads=rows_a_call * cfg["num_attention_heads"],
                    q_len=q_len, kv_len=q_len, head_dim=cfg["head_dim"],
                    causal=True)
    return None


def attention_params(cfg: dict) -> int:
    """The matrices of one attention sublayer: ``W_q``, ``W_k``, ``W_v1``,
    ``W_v2``, ``W_o``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * hq * hd + d * hk * hd + d * hk * hd + hq * hd * d


def conv_params(cfg: dict) -> int:
    """Both convolutions with their biases, and the key temperature."""
    hd, C = cfg["head_dim"], cca_width(cfg)
    groups = C // hd
    return (cfg["cca_time0"] * C + C + cfg["cca_time1"] * groups * hd * hd
            + C + cfg["num_key_value_heads"])


def router_params(cfg: dict) -> int:
    d, R, n = (cfg["hidden_size"], cfg["router_hidden_size"],
               cfg["num_experts"] + 1)
    return (d * R + R) + R + R + 2 * (R * R + R) + R * n + n


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def vector_params(cfg: dict) -> int:
    """A layer's norm scales and merge vectors."""
    return (2 + 8) * cfg["hidden_size"]


def layer_params(cfg: dict, held_share: float = 1.0) -> float:
    return (attention_params(cfg) + conv_params(cfg) + router_params(cfg)
            + vector_params(cfg)
            + held_share * cfg["num_experts"] * expert_params(cfg))


def zaya_weight_params(cfg: dict) -> int:
    """What the chip HOLDS, in elements: the layers, the tied matrix ONCE,
    the embedding's merge and the final norm."""
    return int(cfg["num_hidden_layers"] * layer_params(cfg)
               + cfg["vocab_size"] * cfg["hidden_size"]
               + 3 * cfg["hidden_size"])


def experts_touched_share(cfg: dict, rows: int) -> float:
    """Share of the experts that a tick of ``rows`` tokens sends at least
    one token to, under a uniform router over the experts and the skip:
    ``1 - (1 - 1 / (E + 1)) ^ rows`` (70% at 20 rows)."""
    return 1.0 - (1.0 - 1.0 / (cfg["num_experts"] + 1)) ** rows


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One decode tick over all slots: every matrix a tick multiplies by
    once (the tied matrix once: the embedding is a gather of one row a
    slot, the head reads it all), the experts at the share a tick of
    ``serving.slots`` rows touches under a UNIFORM router (an expert no
    row chose need not be read; the program's decode form reads them all,
    so its share of this floor stays under 100%), the K/V pair of every
    live context token for every layer, and the tails read and written."""
    slots = cfg["serving"]["slots"]
    share = experts_touched_share(cfg, slots)
    L = cfg["num_hidden_layers"]
    return ((L * layer_params(cfg, share)
             + cfg["vocab_size"] * cfg["hidden_size"]) * ITEMSIZE[dtype]
            + L * live_context_tokens * kv_bytes_per_token(cfg, dtype)
            + 2 * L * slots * tail_bytes_per_slot(cfg, dtype))
