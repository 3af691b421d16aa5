"""Family ``sdar_moe`` (``model_type: sdar_moe``; SDAR-30B-A3B-Chat): a
pre-norm decoder of identical layers: full attention (GQA, 32 query heads on
4 key-value heads of 128, an RMSNorm over each head's channels of q and of
k, half-split rotation of every layer; a K/V pair a token in the paged pool)
and 128 routed experts behind a SOFTMAX router (8 a token, renormalised, no
shared expert), which GENERATES BY BLOCK DIFFUSION: positions are cut into
blocks of ``block_length`` from position 0, a position attends the earlier
blocks and all of its own, and a pass of the served path takes a block of a
row and yields none to ``block_length`` tokens (``serve.py::_row_passes``).
Built by the program's ``build_model("hybrid")``. What a family module says
is listed in ``perfbench/README.md``, "Adding things". The configuration's
file states the cut (depth alone) and, under ``generation``, the block
length, the denoising steps, the rule and the mask id, which the reference
reads there and a cell's ``run`` repeats.

Kernel: a pass's read of the pool is ``dcp_paged_decode_attn`` with the
block's queries beside the head group (shape ``decode``: ``block_length x
num_attention_heads`` query rows a slot); the operation and byte functions
are the kernel's own in ``flops.py`` / ``bytes.py``."""

from __future__ import annotations

from perfbench.bytes import ITEMSIZE

BUILD_MODEL = "hybrid"
REFERENCE = "perfbench.reference.sdar_moe_ref"
DROPOUT_KEYS = ()
GENERATION_KEYS = ("block_length", "denoising_steps", "remasking")


def generation(cfg: dict, run: dict) -> dict:
    """How the model generates: the configuration's ``generation``, which a
    cell's ``run`` may repeat and may not contradict (the reference is
    handed the configuration alone)."""
    g = dict(cfg["generation"])
    for k in GENERATION_KEYS:
        if k in run and run[k] != g[k]:
            raise ValueError(
                f"the cell's run says {k} = {run[k]!r}, the configuration's "
                f"generation {g[k]!r}: the reference reads the latter")
    return g


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    depth = cfg["num_hidden_layers"]
    g = generation(cfg, run)
    return dict(
        vocab_size=cfg["vocab_size"], max_seq_len=run["max_seq_len"],
        layer_types=("full_attention",) * depth,
        mlp_layer_types=("sparse",) * depth,
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_model=cfg["hidden_size"], d_ff=cfg["intermediate_size"],
        qk_norm=True, rope_sliding_only=False,
        rope_theta=float(cfg["rope_theta"]), norm_placement="pre",
        rms_eps=float(cfg["rms_norm_eps"]),
        num_experts=cfg["num_experts"],
        experts_held=tuple(cfg["experts_held"]),
        top_k=cfg["num_experts_per_tok"],
        moe_d_ff=cfg["moe_intermediate_size"], shared_d_ff=0,
        router="softmax", norm_topk_prob=bool(cfg["norm_topk_prob"]),
        block_length=g["block_length"],
        denoising_steps=g["denoising_steps"], remasking=g["remasking"],
        mask_token_id=g["mask_token_id"],
        param_dtype=jnp.dtype(run.get("param_dtype", "bfloat16")))


# ---- what a layer holds, in matrix elements -------------------------------

def attn_params(cfg: dict) -> int:
    """q and o at the query heads' width, k and v at the key-value heads'."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def sdar_matmul_params(cfg: dict) -> int:
    """Every matrix a pass multiplies by: the layers with ALL the held
    experts and the head (the embedding is a gather of a row a position)."""
    held = cfg["experts_held"][1]
    return (cfg["hidden_size"] * cfg["vocab_size"]
            + cfg["num_hidden_layers"] * (
                attn_params(cfg) + router_params(cfg)
                + held * expert_params(cfg)))


def sdar_weight_params(cfg: dict) -> int:
    """What the chip HOLDS, in matrix elements: the matrices a pass
    multiplies by and the embedding (norm scales, 32,512 float32, apart)."""
    return sdar_matmul_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]


def kv_bytes_per_token(cfg: dict, dtype: str = "bfloat16") -> int:
    """K and V of ONE layer for one cached token."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * ITEMSIZE[dtype]


def decode_tick_bytes(cfg: dict, live_context_tokens: float,
                      dtype: str = "bfloat16") -> float:
    """One PASS over all slots (the unit the runner's ``segment`` counts for
    this family): every matrix once (ALL the held experts: 96 rows of 4
    positions of 8 over 128 put ``1 - (1 - 8 / 128) ^ 384`` = all of them
    on some position) and every layer's K/V of the live context once (a
    row's block of queries shares one read). The block's own K/V, written
    and read back, and the activations are left out, so the floor is never
    too high."""
    return (sdar_matmul_params(cfg) * ITEMSIZE[dtype]
            + cfg["num_hidden_layers"] * live_context_tokens
            * kv_bytes_per_token(cfg, dtype))


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``decode``: one call of the paged decode-attention kernel, i.e. one
    layer's attention of one pass over every slot: a block's
    ``block_length`` positions of all query heads against each slot's live
    context, read once."""
    if which != "decode":
        return None
    live = counters.get("mean_live_context_tokens")
    if live is None:
        return None
    return dict(live_context_tokens=live,
                q_heads=cfg["num_attention_heads"]
                * cfg["generation"]["block_length"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["head_dim"], itemsize=ITEMSIZE["bfloat16"])
