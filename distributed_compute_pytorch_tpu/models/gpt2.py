"""GPT-2 (decoder-only causal LM) — BASELINE.md ladder rung 4
("GPT-2-small with XLA FSDP", ``BASELINE.json`` configs[4]).

Standard GPT-2 topology: learned token + position embeddings, pre-LN
transformer blocks with fused-QKV causal attention, final LayerNorm, and a
weight-tied readout through the token embedding. Sizes default to GPT-2-small
(12 layers, 12 heads, 768 d_model, 50257 vocab) but every dimension is a
config knob so tests run tiny.

Parallelism: ``partition_rules()`` provides the Megatron TP layout for the
block weights (see ``models/transformer.py``); pair with the ``fsdp`` axis
for FSDP, ``seq`` + ``parallel/ring_attention`` for long context, and
``pipe`` for pipeline parallelism — the blocks are *stacked* (leading
``[num_layers]`` dim, scanned off-pipeline; GPipe schedule over ``pipe``
when the mesh carries one — see ``parallel/pipeline.py``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from distributed_compute_pytorch_tpu.core.mesh import current_mesh
from distributed_compute_pytorch_tpu.models import layers as L
from distributed_compute_pytorch_tpu.obs.tracing import scope
from distributed_compute_pytorch_tpu.models.transformer import (
    TransformerBlock, tp_partition_rules)
from distributed_compute_pytorch_tpu.parallel.pipeline import (
    pipeline_blocks, scan_blocks, stacked_layers)


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    dropout_rate: float = 0.1
    # GPipe microbatch count under a pipe axis (None = pipe size). Bubble
    # fraction is (P-1)/(M+P-1): raise M to amortise.
    pipeline_microbatches: int | None = None
    # Megatron interleaved schedule: each device owns v non-contiguous
    # layer chunks (parallel/pipeline.py::pipeline_blocks)
    virtual_stages: int = 1
    # rematerialise blocks on backward (jax.checkpoint): ~2-4x batch for one
    # extra forward — the HBM-bound trade (proven: B=32 GPT-2-small fits one
    # v5e chip with remat; B=16 doesn't without)
    remat: bool | str = False   # True/"block" per-block; "stage" = 1F1B
                                # memory profile under a pipe mesh
    # python-loop the blocks instead of lax.scan: XLA schedules across the
    # whole depth and residuals skip the scan's dynamic-update-slice
    # stacking (-17% step time on v5e at 12 layers); scan for very deep
    # stacks where compile time binds
    unroll_layers: bool = True
    # Megatron sequence-parallel activations on TP meshes (see
    # transformer.TransformerBlock.seq_shard_activations)
    seq_shard_activations: bool = False
    param_dtype: jnp.dtype = jnp.float32

    @classmethod
    def small(cls) -> "GPT2Config":
        return cls()

    @classmethod
    def tiny(cls) -> "GPT2Config":
        """For tests/dryruns: real topology, toy sizes (multiples of mesh
        axes so every sharding strategy applies)."""
        return cls(vocab_size=256, max_seq_len=64, num_layers=2,
                   num_heads=4, d_model=64, d_ff=128, dropout_rate=0.0)


@dataclass(frozen=True)
class GPT2:
    config: GPT2Config = GPT2Config()

    def _block(self) -> TransformerBlock:
        c = self.config
        return TransformerBlock(c.d_model, c.num_heads, c.d_ff,
                                c.dropout_rate, pre_ln=True, causal=True,
                                seq_shard_activations=c.seq_shard_activations,
                                param_dtype=c.param_dtype)

    def init(self, key):
        c = self.config
        ks = jax.random.split(key, c.num_layers + 2)
        wte = L.Embedding(c.vocab_size, c.d_model, param_dtype=c.param_dtype)
        wpe = L.Embedding(c.max_seq_len, c.d_model, param_dtype=c.param_dtype,
                          init_std=0.01)
        block = self._block()
        params = {
            "wte": wte.init(ks[0]),
            "wpe": wpe.init(ks[1]),
            # stacked [num_layers, ...] leaves: scanned (or pipelined over
            # the pipe axis) instead of python-looped
            "blocks": stacked_layers(
                [block.init(ks[2 + i]) for i in range(c.num_layers)]),
            "ln_f": L.LayerNorm(c.d_model).init(None),
        }
        return params, {}   # no batch-stat state in transformers

    def embed(self, params, tokens, positions=None):
        """Token + learned-position embeddings; ``positions`` defaults to
        ``arange(T)`` (decode passes the cache position, ``infer.py``)."""
        c = self.config
        if positions is None:
            positions = jnp.arange(tokens.shape[1])
        with scope("embed"):
            return (L.Embedding(c.vocab_size, c.d_model).apply(
                        params["wte"], tokens)
                    + L.Embedding(c.max_seq_len, c.d_model).apply(
                        params["wpe"], positions))

    def readout(self, params, x):
        """Final LayerNorm + weight-tied readout.

        The entry pin completes the block-boundary layout discipline (see
        ``core.mesh.constrain_activations``): without it the tied attend
        against the (fsdp x tensor)-sharded table is the last place the
        3-axis-mesh partitioner bug can strike."""
        from distributed_compute_pytorch_tpu.core.mesh import (
            constrain_activations)
        c = self.config
        x = constrain_activations(x)
        with scope("head"):
            x = L.LayerNorm(c.d_model).apply(params["ln_f"], x)
            return L.Embedding(c.vocab_size, c.d_model).attend(
                params["wte"], x)

    def kv_cache_spec(self):
        """(num_kv_heads, head_dim) a decode cache must hold per layer."""
        c = self.config
        return c.num_heads, c.d_model // c.num_heads

    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        """``tokens [B, T] int32`` -> logits ``[B, T, vocab]``."""
        c = self.config
        x = self.embed(params, tokens)
        layers_rng = None
        if train and rng is not None:
            emb_rng, layers_rng = jax.random.split(rng)
            with scope("embed"):
                x = L.dropout(x, c.dropout_rate, emb_rng, train)
        block = self._block()
        mesh = current_mesh()
        if (mesh is not None and "pipe" in mesh.axis_names
                and mesh.shape["pipe"] > 1):
            x = pipeline_blocks(block.apply, params["blocks"], x, mesh,
                                num_microbatches=c.pipeline_microbatches,
                                rng=layers_rng, train=train, remat=c.remat,
                                virtual_stages=c.virtual_stages)
        else:
            x = scan_blocks(block.apply, params["blocks"], x,
                            rng=layers_rng, train=train, remat=c.remat,
                            unroll=c.unroll_layers)
        return self.readout(params, x), state

    # --- loss protocol (next-token prediction: shift inside) ---

    def loss_fn(self, logits, tokens):
        with scope("loss"):
            return L.cross_entropy_with_logits(logits[:, :-1],
                                               tokens[:, 1:], "mean")

    def loss_sum(self, logits, tokens):
        with scope("loss"):
            return L.cross_entropy_with_logits(logits[:, :-1],
                                               tokens[:, 1:], "sum")

    def eval_metrics(self, logits, tokens, valid=None):
        """Token-level sums for eval aggregation (step.py eval protocol).

        ``valid`` (float ``[B]``) weights whole sequences — 0.0 rows are the
        feeder's wraparound padding and contribute nothing."""
        pred = jnp.argmax(logits[:, :-1], axis=-1)
        tgt = tokens[:, 1:]
        per_tok = L.cross_entropy_with_logits(logits[:, :-1], tgt, "none")
        return L.token_eval_metrics(per_tok, pred == tgt, valid)

    def partition_rules(self):
        return tp_partition_rules()
