"""Llama-family decoder LM — the modern-architecture rung of the zoo.

The reference repo has one CNN (``/root/reference/main.py:20-45``); the
framework mandate asks for the model families a user would expect, and the
post-GPT-2 decoder recipe is this one: RMSNorm (pre-norm, no biases
anywhere), rotary position embeddings instead of learned absolute
positions, SwiGLU MLP, grouped-query attention (``num_kv_heads <
num_heads``), untied output head. Conventions (half-split RoPE, separate
q/k/v/o projections, gate/up/down MLP naming) match the open Llama
implementations so torch checkpoints port weight-for-weight — proven
against HF ``transformers``' implementation in ``tests/test_llama.py``.

Parallelism: same contract as GPT-2 — stacked blocks scan off-pipeline and
GPipe over a ``pipe`` axis; ``partition_rules()`` gives the Megatron
column/row layout for q/k/v/gate/up (column) and o/down (row); ring
attention engages on a ``seq`` axis, including inside the pipeline's
manual region (RoPE bakes each chunk's global positions in before K/V
rotate, which is exact — see ``ops/rotary.py``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from distributed_compute_pytorch_tpu.core.mesh import current_mesh
from distributed_compute_pytorch_tpu.models import layers as L
from distributed_compute_pytorch_tpu.obs.tracing import scope
from distributed_compute_pytorch_tpu.models.transformer import (
    dispatch_attention)
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.rotary import apply_rope
from distributed_compute_pytorch_tpu.parallel.pipeline import (
    pipeline_blocks, scan_blocks, stacked_layers)


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_seq_len: int = 2048
    num_layers: int = 12
    num_heads: int = 12
    num_kv_heads: int = 4          # GQA: K/V heads shared by query groups
    d_model: int = 768
    d_ff: int = 2048               # SwiGLU hidden width
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    pipeline_microbatches: int | None = None
    # Megatron interleaved schedule (parallel/pipeline.py)
    virtual_stages: int = 1
    remat: bool | str = False      # True/"block" per-block; "stage" = 1F1B
                                   # memory profile under a pipe mesh
    unroll_layers: bool = True
    # Megatron sequence-parallel activations on TP meshes (see
    # transformer.TransformerBlock.seq_shard_activations)
    seq_shard_activations: bool = False
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        assert self.num_heads % self.num_kv_heads == 0, (
            f"num_heads={self.num_heads} must be a multiple of "
            f"num_kv_heads={self.num_kv_heads}")
        assert self.d_model % self.num_heads == 0

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """Real topology (GQA 4:2, SwiGLU, RoPE), toy sizes for tests."""
        return cls(vocab_size=256, max_seq_len=64, num_layers=2,
                   num_heads=4, num_kv_heads=2, d_model=64, d_ff=128)


@dataclass(frozen=True)
class LlamaBlock:
    """Pre-RMSNorm attention + SwiGLU MLP, both bias-free."""

    config: LlamaConfig

    def init(self, key):
        c = self.config
        ks = iter(jax.random.split(key, 7))
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False,
                                          param_dtype=c.param_dtype)
        return {
            "attn_norm": L.RMSNorm(d, c.rms_eps).init(None),
            "q": dense(d, c.num_heads * hd).init(next(ks)),
            "k": dense(d, c.num_kv_heads * hd).init(next(ks)),
            "v": dense(d, c.num_kv_heads * hd).init(next(ks)),
            "o": dense(c.num_heads * hd, d).init(next(ks)),
            "mlp_norm": L.RMSNorm(d, c.rms_eps).init(None),
            "gate": dense(d, c.d_ff).init(next(ks)),
            "up": dense(d, c.d_ff).init(next(ks)),
            "down": dense(c.d_ff, d).init(next(ks)),
        }

    def _positions(self, T: int, manual_axes: tuple):
        """Global token positions for this activation chunk: under the
        pipeline's seq-manual region the local T is one ring chunk and the
        offset is this device's place on the ring."""
        pos = jnp.arange(T)
        if "seq" in manual_axes:
            pos = pos + lax.axis_index("seq") * T
        return pos

    def _qkv(self, params, h, positions):
        """Projected + roped q/k/v (K/V at GQA kv-head width).

        The three projection outputs carry the "qkv" checkpoint tag
        (pre-rope — rope is elementwise and cheap to recompute), so
        ``remat="dots"`` re-runs no projection matmul in the backward,
        matching the transformer.py attention sublayer."""
        from jax.ad_checkpoint import checkpoint_name
        c = self.config
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False)
        q = A.split_heads(checkpoint_name(
            dense(d, c.num_heads * hd).apply(params["q"], h), "qkv"),
            c.num_heads)
        k = A.split_heads(checkpoint_name(
            dense(d, c.num_kv_heads * hd).apply(params["k"], h), "qkv"),
            c.num_kv_heads)
        v = A.split_heads(checkpoint_name(
            dense(d, c.num_kv_heads * hd).apply(params["v"], h), "qkv"),
            c.num_kv_heads)
        q = apply_rope(q, positions, c.rope_theta)
        k = apply_rope(k, positions, c.rope_theta)
        return q, k, v

    def _mlp(self, params, x):
        from jax.ad_checkpoint import checkpoint_name
        c = self.config
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False)
        with scope("mlp"):
            h = L.RMSNorm(c.d_model, c.rms_eps).apply(params["mlp_norm"], x)
            # both d->d_ff projections saved under remat="dots" (the
            # product alone would not do: silu' needs gate_out and the gate
            # grad needs up_out, so saving only silu(gate)*up still re-runs
            # both matmuls)
            gate_out = checkpoint_name(
                dense(c.d_model, c.d_ff).apply(params["gate"], h), "mlp_pre")
            up_out = checkpoint_name(
                dense(c.d_model, c.d_ff).apply(params["up"], h), "mlp_pre")
            gated = jax.nn.silu(gate_out) * up_out
            return x + dense(c.d_ff, c.d_model).apply(params["down"], gated)

    def _ssa(self, x, manual_axes):
        """Residual-stream layout pin at the block boundaries: Megatron
        sequence-parallel when opted in, the canonical batch-sharded
        layout otherwise (doubles as the 3-axis-mesh numerics guard —
        see ``core.mesh.constrain_activations``)."""
        from distributed_compute_pytorch_tpu.core.mesh import (
            constrain_activations, constrain_seq_parallel)
        if self.config.seq_shard_activations:
            return constrain_seq_parallel(x, manual_axes)
        return constrain_activations(x, manual_axes)

    def apply(self, params, x, *, rng=None, train: bool = False,
              kv_mask=None, manual_axes=(), kv_sink=None, positions=None,
              kv_prefix=None):
        """``positions`` overrides the rope positions (default
        ``arange(T)``, seq-ring-offset under a manual region): the
        serving layer's admission prefill (``serve.py``) ropes prompt
        keys at their ABSOLUTE cache slots so later decode queries —
        roped at their own slots — see the right position differences.

        ``kv_prefix``: optional ``(k0, v0, prefix_mask)`` cached-prefix
        K/V prepended before attention (kv-head width, post-rope at
        their own absolute slots) — the chunked suffix-prefill path of
        the serving prefix cache; see
        ``transformer.attention_sublayer``. The suffix ``positions``
        must then start at the prefix length so query/key rope slots
        stay globally consistent."""
        del rng, train    # the Llama recipe has no dropout
        c = self.config
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False)

        x = self._ssa(x, manual_axes)
        with scope("attn"):
            h = L.RMSNorm(d, c.rms_eps).apply(params["attn_norm"], x)
            pos = (self._positions(x.shape[1], tuple(manual_axes))
                   if positions is None else positions)
            q, k, v = self._qkv(params, h, pos)
            if kv_sink is not None:
                # prefill capture: post-rope, kv-head width — exactly what
                # the decode cache stores (suffix-only under a kv_prefix)
                kv_sink.append((k, v))
            if kv_prefix is not None:
                from distributed_compute_pytorch_tpu.models.transformer \
                    import _concat_kv_prefix
                k, v, kv_mask = _concat_kv_prefix(kv_prefix, k, v, kv_mask)
            # GQA K/V stay at num_kv_heads width: the dispatcher repeats
            # heads only for the kernels that need it (ring paths rotate
            # the narrow K/V — see dispatch_attention)
            o = dispatch_attention(q, k, v, causal=True, kv_mask=kv_mask,
                                   manual_axes=manual_axes)
            from jax.ad_checkpoint import checkpoint_name
            o = checkpoint_name(o, "attn_ctx")   # saved under remat="dots"
            x = x + dense(c.num_heads * hd, d).apply(params["o"],
                                                     A.merge_heads(o))
        return self._mlp(params, self._ssa(x, manual_axes))

    def decode_step(self, params, x, cache, pos, slot_mask=None):
        """One KV-cached decode tick: ``x [B, 1, d]`` at cache slot
        ``pos`` — a scalar (lockstep decode, every row at the same slot)
        or an int32 ``[B]`` vector (per-row decode, each row at its own
        slot — the serving loop's contract).

        The cache stays at kv-head width ([B, Hk, T_max, hd]) — GQA's
        memory/bandwidth saving — and stores POST-rope keys roped at
        their SLOT indices. The new query ropes at its slot too — under
        a ``[B]`` pos, at its own ROW's slot (``apply_rope`` takes
        ``[B, 1]`` positions): RoPE scores depend only on position
        differences within a row, so absolute-per-row slots are exactly
        as valid as absolute-shared slots, and under left padding slot
        differences equal logical differences — exact for
        variable-length batches (``slot_mask`` keeps the pad slots
        unattended). The kv-pair cache write is one window DMA per row
        (``ops/attention.py::cache_write_and_attend``).
        """
        c = self.config
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False)
        with scope("attn"):
            h = L.RMSNorm(d, c.rms_eps).apply(params["attn_norm"], x)
            # scalar pos -> [1] (shared across rows); [B] pos -> [B, 1]
            # (each row ropes this tick's single token at its own slot)
            rope_pos = (pos[:, None] if jnp.ndim(pos) == 1
                        else jnp.atleast_1d(pos))
            q, k, v = self._qkv(params, h, rope_pos)
            o, cache = A.cache_write_and_attend(q, k, v, cache, pos,
                                                slot_mask=slot_mask)
            x = x + dense(c.num_heads * hd, d).apply(params["o"],
                                                     A.merge_heads(o))
        return self._mlp(params, x), cache

    def verify_step(self, params, x, cache, positions, slot_mask=None):
        """One speculative VERIFY step: ``x [B, W, d]`` scores a whole
        draft window at per-query ``positions [B, W]`` against the PAGED
        cache in one pass. Window queries/keys rope at their OWN absolute
        slots (``apply_rope`` broadcasts ``[B, W]`` positions), so
        position differences — all RoPE sees — match ``W`` sequential
        :meth:`decode_step` ticks exactly; the staircase attention mask
        (``ops/attention.py::cache_verify_and_attend``) supplies the same
        slots-at-or-before-query visibility. GQA folds the group dim into
        the window dim on the read, keeping the cache at kv-head width."""
        c = self.config
        d, hd = c.d_model, c.head_dim
        dense = lambda din, dout: L.Dense(din, dout, use_bias=False)
        with scope("attn"):
            h = L.RMSNorm(d, c.rms_eps).apply(params["attn_norm"], x)
            q, k, v = self._qkv(params, h, positions)
            o, cache = A.cache_verify_and_attend(q, k, v, cache, positions,
                                                 slot_mask=slot_mask)
            x = x + dense(c.num_heads * hd, d).apply(params["o"],
                                                     A.merge_heads(o))
        return self._mlp(params, x), cache


@dataclass(frozen=True)
class LlamaLM:
    config: LlamaConfig = LlamaConfig()

    def _block(self) -> LlamaBlock:
        return LlamaBlock(self.config)

    def init(self, key):
        c = self.config
        ks = jax.random.split(key, c.num_layers + 2)
        block = self._block()
        return {
            "wte": L.Embedding(c.vocab_size, c.d_model,
                               param_dtype=c.param_dtype).init(ks[0]),
            "blocks": stacked_layers(
                [block.init(ks[1 + i]) for i in range(c.num_layers)]),
            "norm_f": L.RMSNorm(c.d_model, c.rms_eps).init(None),
            "lm_head": L.Dense(c.d_model, c.vocab_size, use_bias=False,
                               param_dtype=c.param_dtype).init(ks[-1]),
        }, {}   # no batch-stat state

    def embed(self, params, tokens, positions=None):
        """Token embeddings (positions unused — RoPE lives in the blocks;
        accepted for the shared decode protocol, ``infer.py``)."""
        del positions
        c = self.config
        with scope("embed"):
            return L.Embedding(c.vocab_size, c.d_model).apply(params["wte"],
                                                              tokens)

    def readout(self, params, x):
        """Final norm + untied LM head: ``[.., d]`` -> ``[.., vocab]``.

        Entry pin: block-boundary layout discipline (see
        ``core.mesh.constrain_activations``)."""
        from distributed_compute_pytorch_tpu.core.mesh import (
            constrain_activations)
        c = self.config
        x = constrain_activations(x)
        with scope("head"):
            x = L.RMSNorm(c.d_model, c.rms_eps).apply(params["norm_f"], x)
            return L.Dense(c.d_model, c.vocab_size,
                           use_bias=False).apply(params["lm_head"], x)

    def kv_cache_spec(self):
        """(num_kv_heads, head_dim) a decode cache must hold per layer."""
        return self.config.num_kv_heads, self.config.head_dim

    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        """``tokens [B, T] int32`` -> logits ``[B, T, vocab]``."""
        c = self.config
        x = self.embed(params, tokens)
        block = self._block()
        mesh = current_mesh()
        if (mesh is not None and "pipe" in mesh.axis_names
                and mesh.shape["pipe"] > 1):
            x = pipeline_blocks(block.apply, params["blocks"], x, mesh,
                                num_microbatches=c.pipeline_microbatches,
                                rng=rng, train=train, remat=c.remat,
                                virtual_stages=c.virtual_stages)
        else:
            x = scan_blocks(block.apply, params["blocks"], x,
                            rng=rng, train=train, remat=c.remat,
                            unroll=c.unroll_layers)
        return self.readout(params, x), state

    # --- loss protocol (next-token prediction, same as GPT-2) ---

    def loss_fn(self, logits, tokens):
        with scope("loss"):
            return L.cross_entropy_with_logits(logits[:, :-1],
                                               tokens[:, 1:], "mean")

    def loss_sum(self, logits, tokens):
        with scope("loss"):
            return L.cross_entropy_with_logits(logits[:, :-1],
                                               tokens[:, 1:], "sum")

    def eval_metrics(self, logits, tokens, valid=None):
        pred = jnp.argmax(logits[:, :-1], axis=-1)
        tgt = tokens[:, 1:]
        per_tok = L.cross_entropy_with_logits(logits[:, :-1], tgt, "none")
        return L.token_eval_metrics(per_tok, pred == tgt, valid)

    def partition_rules(self):
        """Megatron TP layout for the Llama param names: q/k/v/gate/up are
        column-parallel (output features over ``tensor``), o/down are
        row-parallel (input features over ``tensor``); stacked-layer dim
        over ``pipe``; embeddings/head over fsdp x tensor."""
        from jax.sharding import PartitionSpec as P
        return (
            (r"blocks/(q|k|v|gate|up)/kernel$",
             P("pipe", "fsdp", "tensor")),
            (r"blocks/(o|down)/kernel$", P("pipe", "tensor", "fsdp")),
            (r"blocks/", P("pipe")),
            (r"embedding$", P("fsdp", "tensor")),
            (r"lm_head/kernel$", P("fsdp", "tensor")),
        )
