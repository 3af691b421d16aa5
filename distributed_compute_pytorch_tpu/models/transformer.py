"""Shared transformer building blocks for the BERT/GPT-2 rungs.

TPU-first layout decisions:
- attention/MLP widths chosen by config stay multiples of 128 so XLA tiles
  cleanly onto the MXU;
- QKV are one fused projection (one big matmul beats three small ones);
- tensor-parallel sharding is expressed as data layout in
  ``partition_rules`` — column-parallel fused QKV and MLP-in shard their
  *output* feature dim over ``tensor``; row-parallel attn-out and MLP-out
  shard their *input* dim, so XLA's partitioner inserts exactly the two
  all-reduces per block Megatron-LM prescribes;
- sequence axis can additionally be sharded over ``seq`` (ring attention in
  ``parallel/ring_attention.py``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from distributed_compute_pytorch_tpu.models import layers as L
from distributed_compute_pytorch_tpu.obs.tracing import scope
from distributed_compute_pytorch_tpu.ops import attention as A


def dispatch_attention(q, k, v, *, causal: bool = False,
                       seq_axis: str = "seq", attn_impl: str = "auto",
                       kv_mask=None, manual_axes: tuple = (),
                       mask_block: int = 0):
    """Route split-head ``[B, H, T, hd]`` attention to the right engine.

    One dispatcher for every model family: the Pallas flash kernel (or
    dense XLA) when the mesh has no ``seq`` axis, shard_map ring attention
    when it does, and the manual ring body when the caller is already
    inside a manual region over ``seq`` (pipeline stages — a nested
    shard_map cannot sit there).

    GQA (``k``/``v`` with fewer heads than ``q``, grouped as head ``h`` ->
    kv head ``h // G``) is handled per-engine: the ring paths consume the
    narrow K/V directly — rotating pre-repeated heads would move ``G x``
    the bytes over ICI — while the flash/dense kernels get an explicit
    head repeat.

    ``mask_block`` (static, with ``causal``): the BLOCK mask of a
    block-diffusion model in place of the causal one: row ``i`` sees key
    ``j`` iff ``j // mask_block <= i // mask_block`` (every earlier block
    and all of its own). Flash and dense engines only: a ``seq`` mesh axis
    refuses it.
    """
    from distributed_compute_pytorch_tpu.core.mesh import current_mesh
    from distributed_compute_pytorch_tpu.parallel.ring_attention import (
        ring_attention, ring_attention_manual)

    mesh = current_mesh()
    seq_sharded = (mesh is not None and seq_axis in mesh.axis_names
                   and mesh.shape[seq_axis] > 1)
    if mask_block and (seq_sharded or not causal):
        raise ValueError("mask_block is the flash and dense engines' form "
                         "of causal self-attention: no seq axis")
    if seq_sharded and seq_axis in manual_axes:
        return ring_attention_manual(q, k, v, seq_axis,
                                     mesh.shape[seq_axis], causal=causal,
                                     kv_mask=kv_mask, vary=manual_axes)
    if seq_sharded:
        return ring_attention(q, k, v, mesh, seq_axis, causal=causal,
                              kv_mask=kv_mask)
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    return A.attention(q, k, v, causal=causal, impl=attn_impl,
                       kv_mask=kv_mask,
                       **({"mask_block": mask_block} if mask_block else {}))


def attention_sublayer(params, x, *, num_heads: int, causal: bool = False,
                       seq_axis: str = "seq", attn_impl: str = "auto",
                       dropout_rate: float = 0.0, rng=None,
                       train: bool = False, kv_mask=None,
                       manual_axes: tuple = (), kv_sink: list | None = None,
                       kv_prefix=None):
    """Fused-QKV multi-head attention + output projection + dropout.

    The shared attention half of every transformer variant (dense blocks
    here, MoE blocks in ``models/moe.py``), so all of them get the same
    dispatch: the Pallas flash kernel on TPU for eligible shapes, and ring
    attention when the current mesh carries a ``seq`` axis > 1.

    ``kv_mask``: optional ``[batch, seq]`` key-validity (padding) mask —
    True = attend; honoured by all three paths (flash / dense / ring).

    ``manual_axes``: mesh axes the CALLER is already manual over (the
    pipeline's shard_map region, ``parallel/pipeline.py``). When it
    includes ``seq_axis``, ``x`` is a local seq chunk and the ring runs
    directly via ``ring_attention_manual`` — a nested shard_map cannot sit
    inside a manual region.

    ``kv_prefix``: optional ``(k0, v0, prefix_mask)`` — ALREADY-COMPUTED
    K/V (kv-head width ``[B, Hk, Lp, hd]``, ``prefix_mask [B, Lp]``,
    1 = valid) prepended to this window's keys/values before attention.
    This is the chunked suffix-prefill path (the serving layer's prefix
    cache, ``serve.ContinuousBatcher``): the window holds only a
    prompt's UNSHARED suffix, its queries attend the cached prefix plus
    the causal window, and only the suffix K/V are captured into
    ``kv_sink``. The bottom-right-aligned causal mask (``ops/attention.
    dot_product_attention``: ``row >= col - (kv_len - q_len)``) gives
    exactly "all prefix + window up to self" with no extra mask code.
    Unsupported under a seq/ring mesh axis (the serve layer rejects
    those meshes already).

    ``params``: ``{"qkv": Dense(d, 3d), "attn_out": Dense(d, d)}`` trees.
    """
    from jax.ad_checkpoint import checkpoint_name
    d = x.shape[-1]
    # "qkv"/"attn_ctx" tags: saved under remat="dots" so the backward
    # re-runs neither the projections nor the attention kernel
    # (parallel/pipeline.py SAVED_MATMUL_NAMES)
    qkv = checkpoint_name(L.Dense(d, 3 * d).apply(params["qkv"], x), "qkv")
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = A.split_heads(q, num_heads)
    k = A.split_heads(k, num_heads)
    v = A.split_heads(v, num_heads)
    if kv_sink is not None:
        kv_sink.append((k, v))   # prefill capture for KV-cache decoding
                                 # (suffix-only when a prefix is attached)
    if kv_prefix is not None:
        k, v, kv_mask = _concat_kv_prefix(kv_prefix, k, v, kv_mask)
    o = dispatch_attention(q, k, v, causal=causal, seq_axis=seq_axis,
                           attn_impl=attn_impl, kv_mask=kv_mask,
                           manual_axes=manual_axes)
    o = checkpoint_name(o, "attn_ctx")
    o = A.merge_heads(o)
    o = L.Dense(d, d).apply(params["attn_out"], o)
    return L.dropout(o, dropout_rate, rng, train)


def _concat_kv_prefix(kv_prefix, k, v, kv_mask):
    """Prepend cached-prefix K/V (and validity) to a window's keys:
    shared by every family's ``apply`` (dense/MoE here, Llama in
    ``models/llama.py``). The window mask defaults to all-real when the
    caller passed none."""
    pk, pv, pmask = kv_prefix
    k2 = jnp.concatenate([pk.astype(k.dtype), k], axis=2)
    v2 = jnp.concatenate([pv.astype(v.dtype), v], axis=2)
    if kv_mask is None:
        kv_mask = jnp.ones((k.shape[0], k.shape[2]), jnp.float32)
    mask2 = jnp.concatenate([pmask.astype(kv_mask.dtype), kv_mask], axis=1)
    return k2, v2, mask2


def attention_decode_tick(params, x, cache, pos, *, num_heads: int,
                          slot_mask=None):
    """The shared attention half of one KV-cached decode tick:
    ln1 -> fused QKV -> one-window kv-pair cache write + masked
    attention (``ops/attention.py::cache_write_and_attend``, bf16 or
    int8 cache) -> attn_out residual. ``pos`` is a scalar (lockstep
    decode) or an int32 ``[B]`` vector (per-row decode — every row
    writes and attends at its own slot; the serving loop's contract).
    One implementation for every learned-position causal block (dense
    GPT-2 and MoE — Llama's tick differs: RMSNorm, RoPE, GQA). Returns
    ``(x + attn_residual, new_cache)``."""
    d = x.shape[-1]
    h = L.LayerNorm(d).apply(params["ln1"], x)
    qkv = L.Dense(d, 3 * d).apply(params["qkv"], h)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = A.split_heads(q, num_heads)
    k = A.split_heads(k, num_heads)
    v = A.split_heads(v, num_heads)
    o, cache = A.cache_write_and_attend(q, k, v, cache, pos,
                                        slot_mask=slot_mask)
    return (x + L.Dense(d, d).apply(params["attn_out"], A.merge_heads(o)),
            cache)


def attention_verify_tick(params, x, cache, positions, *, num_heads: int,
                          slot_mask=None):
    """The shared attention half of one speculative VERIFY step: like
    :func:`attention_decode_tick` but over a ``W``-token draft window —
    ``x [B, W, d]`` at per-query ``positions [B, W]``, one fused QKV for
    the whole window, one paged-pool scatter + staircase-masked attention
    (``ops/attention.py::cache_verify_and_attend``). Returns
    ``(x + attn_residual, new_cache)``."""
    d = x.shape[-1]
    h = L.LayerNorm(d).apply(params["ln1"], x)
    qkv = L.Dense(d, 3 * d).apply(params["qkv"], h)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = A.split_heads(q, num_heads)
    k = A.split_heads(k, num_heads)
    v = A.split_heads(v, num_heads)
    o, cache = A.cache_verify_and_attend(q, k, v, cache, positions,
                                         slot_mask=slot_mask)
    return (x + L.Dense(d, d).apply(params["attn_out"], A.merge_heads(o)),
            cache)


@dataclass(frozen=True)
class TransformerBlock:
    """Pre/post-LN transformer block with fused-QKV MHA and GELU MLP."""

    d_model: int
    num_heads: int
    d_ff: int
    dropout_rate: float = 0.1
    pre_ln: bool = True            # GPT-2 style; False = BERT (post-LN)
    causal: bool = False
    seq_axis: str = "seq"          # ring attention engages when the current
                                   # mesh has this axis with size > 1
    attn_impl: str = "auto"        # 'auto' = Pallas flash kernel on TPU
    # Megatron-style sequence-parallel ACTIVATIONS for TP meshes: pin the
    # residual stream's token dim over `tensor` at the block boundaries,
    # so XLA lowers the two per-block all-reduces to reduce-scatter +
    # all-gather pairs and LayerNorm/dropout work is sharded instead of
    # replicated. Numerics-transparent (== DP, tested); engages only when
    # the mesh has tensor > 1 and no seq/ring axis competes for the token
    # dim. Opt-in: on single-chip runs the constraint is a no-op anyway.
    seq_shard_activations: bool = False
    param_dtype: jnp.dtype = jnp.float32

    def init(self, key):
        ks = jax.random.split(key, 6)
        pd = self.param_dtype
        d = self.d_model
        return {
            "ln1": L.LayerNorm(d).init(None),
            "qkv": L.Dense(d, 3 * d, param_dtype=pd).init(ks[0]),
            "attn_out": L.Dense(d, d, param_dtype=pd).init(ks[1]),
            "ln2": L.LayerNorm(d).init(None),
            "mlp_in": L.Dense(d, self.d_ff, param_dtype=pd).init(ks[2]),
            "mlp_out": L.Dense(self.d_ff, d, param_dtype=pd).init(ks[3]),
        }

    def _attn(self, params, x, rng, train, kv_mask=None, manual_axes=(),
              kv_sink=None, kv_prefix=None):
        return attention_sublayer(
            params, x, num_heads=self.num_heads, causal=self.causal,
            seq_axis=self.seq_axis, attn_impl=self.attn_impl,
            dropout_rate=self.dropout_rate, rng=rng, train=train,
            kv_mask=kv_mask, manual_axes=manual_axes, kv_sink=kv_sink,
            kv_prefix=kv_prefix)

    def _mlp(self, params, x, rng, train):
        from jax.ad_checkpoint import checkpoint_name
        h = L.Dense(self.d_model, self.d_ff).apply(params["mlp_in"], x)
        h = checkpoint_name(h, "mlp_pre")   # saved under remat="dots"
        h = jax.nn.gelu(h)
        h = L.Dense(self.d_ff, self.d_model).apply(params["mlp_out"], h)
        return L.dropout(h, self.dropout_rate, rng, train)

    def _ssa(self, x, manual_axes):
        """Residual-stream layout pin at the block boundaries: the
        Megatron sequence-parallel layout when opted in, the canonical
        batch-sharded layout otherwise (which doubles as the 3-axis-mesh
        numerics guard — see ``core.mesh.constrain_activations``)."""
        from distributed_compute_pytorch_tpu.core.mesh import (
            constrain_activations, constrain_seq_parallel)
        if self.seq_shard_activations:
            return constrain_seq_parallel(x, manual_axes, self.seq_axis)
        return constrain_activations(x, manual_axes, self.seq_axis)

    def apply(self, params, x, *, rng=None, train: bool = False,
              kv_mask=None, manual_axes=(), kv_sink=None, kv_prefix=None):
        r1 = r2 = None
        if train and rng is not None:
            r1, r2 = jax.random.split(rng)
        ln1 = L.LayerNorm(self.d_model)
        ln2 = L.LayerNorm(self.d_model)
        x = self._ssa(x, manual_axes)
        # each sublayer's scope takes its norm and its residual add
        if self.pre_ln:
            with scope("attn"):
                x = x + self._attn(params, ln1.apply(params["ln1"], x), r1,
                                   train, kv_mask, manual_axes, kv_sink,
                                   kv_prefix)
            x = self._ssa(x, manual_axes)
            with scope("mlp"):
                x = x + self._mlp(params, ln2.apply(params["ln2"], x), r2,
                                  train)
        else:  # post-LN (BERT)
            with scope("attn"):
                x = ln1.apply(params["ln1"],
                              x + self._attn(params, x, r1, train, kv_mask,
                                             manual_axes, kv_sink))
            x = self._ssa(x, manual_axes)
            with scope("mlp"):
                x = ln2.apply(params["ln2"],
                              x + self._mlp(params, x, r2, train))
        return x

    def decode_step(self, params, x, cache, pos, slot_mask=None):
        """One KV-cached decode tick: ``x [B, 1, d]`` at position ``pos``
        (scalar, or ``[B]`` for per-row decode positions).

        This block has no rotary embedding — GPT-2's (possibly per-row)
        learned positions enter through the model's ``embed``.

        Writes this step's K/V into ``cache`` (``{"kv": [2, B, H, T_max,
        hd]}``, one window DMA) and attends over slots ``0..pos`` (minus
        ``slot_mask``-invalid pad slots). Pre-LN causal blocks only —
        post-LN blocks are bidirectional (BERT) and have no
        autoregressive decode.
        """
        assert self.causal and self.pre_ln, "decode needs a causal pre-LN block"
        d = self.d_model
        with scope("attn"):
            x, cache = attention_decode_tick(params, x, cache, pos,
                                             num_heads=self.num_heads,
                                             slot_mask=slot_mask)
        with scope("mlp"):
            h = L.LayerNorm(d).apply(params["ln2"], x)
            return x + self._mlp(params, h, None, False), cache

    def verify_step(self, params, x, cache, positions, slot_mask=None):
        """One speculative VERIFY step: ``x [B, W, d]`` scores a whole
        draft window at per-query ``positions [B, W]`` (consecutive
        per-row slots) against the PAGED cache in one forward pass.
        Position ``w``'s output depends only on cache slots ``<=
        positions[b, w]`` — identical semantics to ``W`` sequential
        :meth:`decode_step` ticks, which is what the exact accept/reject
        rule relies on (``serve.ContinuousBatcher``)."""
        assert self.causal and self.pre_ln, "verify needs a causal pre-LN block"
        d = self.d_model
        with scope("attn"):
            x, cache = attention_verify_tick(params, x, cache, positions,
                                             num_heads=self.num_heads,
                                             slot_mask=slot_mask)
        with scope("mlp"):
            h = L.LayerNorm(d).apply(params["ln2"], x)
            return x + self._mlp(params, h, None, False), cache


# Megatron-style tensor-parallel layout for the block param names above.
# Blocks are STACKED (leading [num_layers] dim, see parallel/pipeline.py),
# so every block rule leads with the ``pipe`` axis: under pipeline
# parallelism each stage holds only its layers; on pipe-less meshes
# ShardingRules drops the absent axis. Combined with FSDP fallback by
# ShardingRules(fallback=FSDP()). Order matters: first match wins, the
# ``blocks/`` catch-all (ln scales/biases — layer dim over pipe only) must
# come after the specific kernels.
TP_RULES = (
    # column-parallel: shard output features
    (r"blocks/qkv/kernel$", ("pipe", "fsdp", "tensor")),
    (r"blocks/qkv/bias$", ("pipe", "tensor")),
    (r"blocks/mlp_in/kernel$", ("pipe", "fsdp", "tensor")),
    (r"blocks/mlp_in/bias$", ("pipe", "tensor")),
    # row-parallel: shard input features
    (r"blocks/attn_out/kernel$", ("pipe", "tensor", "fsdp")),
    (r"blocks/mlp_out/kernel$", ("pipe", "tensor", "fsdp")),
    # remaining stacked leaves (ln/bias): layer dim over pipe
    (r"blocks/", ("pipe",)),
    # embeddings (not stacked): shard vocab over fsdp, features over tensor
    (r"embedding$", ("fsdp", "tensor")),
)


def tp_partition_rules():
    """As ``ShardingRules``-ready (regex, PartitionSpec) pairs."""
    from jax.sharding import PartitionSpec as P
    return tuple((pattern, P(*axes)) for pattern, axes in TP_RULES)
