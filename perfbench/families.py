"""The one place a model is named in code: a configuration's ``family``
maps to the program's ``build_model`` name, the keyword arguments made
from the published config's keys, the plain reference that goes with it,
the config's dropout keys, and the shapes of its attention kernel's calls.
A new family adds one entry here and a reference module."""

from __future__ import annotations

import importlib


def _gpt2_kwargs(cfg: dict, run: dict) -> dict:
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


def _llama_kwargs(cfg: dict, run: dict) -> dict:
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


def _gpt2_train_attention(cfg: dict, counters: dict, chips: int) -> dict:
    """Shapes of one flash-attention call of a train step: per-chip batch
    x heads, lengths, head size."""
    return dict(batch_heads=counters["global_batch"] // chips * cfg["n_head"],
                q_len=counters["seq_len"], kv_len=counters["seq_len"],
                head_dim=cfg["n_embd"] // cfg["n_head"])


FAMILIES = {
    "gpt2": {"build_model": "gpt2", "kwargs": _gpt2_kwargs,
             "reference": "perfbench.reference.gpt2_ref",
             "dropout_keys": ("attn_pdrop", "embd_pdrop", "resid_pdrop"),
             "train_attention_shape": _gpt2_train_attention},
    "mistral": {"build_model": "llama", "kwargs": _llama_kwargs,
                "reference": "perfbench.reference.llama_ref",
                "dropout_keys": ()},
}


def without_dropout(cfg: dict) -> dict:
    """``cfg`` with the family's dropout keys at 0 (``cfg`` itself, equal,
    where they already are)."""
    return {k: (0.0 if k in FAMILIES[cfg["family"]]["dropout_keys"] else v)
            for k, v in cfg.items()}


def attention_shape(cfg: dict, which: str, counters: dict, chips: int):
    """The call shapes of the family's attention kernel in a run of kind
    ``which`` (a key ``<which>_attention_shape`` of the family's entry);
    None where the family does not say."""
    fn = FAMILIES[cfg["family"]].get(f"{which}_attention_shape")
    return None if fn is None else fn(cfg, counters, chips)


def reference_module(cfg: dict):
    return importlib.import_module(FAMILIES[cfg["family"]]["reference"])


def build_program_model(cfg: dict, run: dict):
    from distributed_compute_pytorch_tpu.models.registry import build_model
    fam = FAMILIES[cfg["family"]]
    return build_model(fam["build_model"], **fam["kwargs"](cfg, run))
