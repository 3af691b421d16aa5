"""Family ``gpt2``: GPT-2 as published (learned positions, LayerNorm with
biases, GELU MLP of 4 x the width, tied read-out), built by the program's
``build_model("gpt2")``. What a family module says is listed in
``perfbench/README.md``, "Adding things"."""

from __future__ import annotations

BUILD_MODEL = "gpt2"
REFERENCE = "perfbench.reference.gpt2_ref"
DROPOUT_KEYS = ("attn_pdrop", "embd_pdrop", "resid_pdrop")


def model_kwargs(cfg: dict, run: dict) -> dict:
    import jax.numpy as jnp
    # the program has one rate, for the embedding and residual dropout;
    # its flash-attention path has no dropout on the attention weights
    # (``attn_pdrop`` is not applied: PERF.md, Open questions)
    assert cfg["embd_pdrop"] == cfg["resid_pdrop"]
    return dict(
        num_layers=cfg["n_layer"], d_model=cfg["n_embd"],
        num_heads=cfg["n_head"], d_ff=cfg.get("n_inner") or 4 * cfg["n_embd"],
        vocab_size=cfg["vocab_size"], max_seq_len=cfg["n_positions"],
        dropout_rate=float(cfg["resid_pdrop"]),
        remat=run.get("remat", False),
        param_dtype=jnp.dtype(run.get("param_dtype", "float32")))


def kernel_shapes(cfg: dict, which: str, counters: dict, chips: int):
    """``train``: one flash-attention call of a train step, as
    ``flops.flash_*_flops`` / ``bytes.flash_*_bytes`` take it: per-chip
    batch x heads, lengths, head size, causal."""
    if which != "train":
        return None
    return dict(batch_heads=counters["global_batch"] // chips * cfg["n_head"],
                q_len=counters["seq_len"], kv_len=counters["seq_len"],
                head_dim=cfg["n_embd"] // cfg["n_head"], causal=True)


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
    => 6*T*d per layer per token. Recomputation (remat) is never counted:
    these are the model's operations, not the program's."""
    return (6.0 * gpt2_matmul_params(cfg)
            + 6.0 * cfg["n_layer"] * seq_len * cfg["n_embd"])
