"""Attention ops.

The reference has no attention anywhere (its model is a 7-layer CNN,
``/root/reference/main.py:20-45``); these ops serve the BERT/GPT-2 ladder
rungs (``BASELINE.json`` configs[3-4]) and the framework's long-context
support (ring attention over a ``seq`` mesh axis lives in
``parallel/ring_attention.py``; a fused Pallas kernel in ``ops/pallas/``).

This module is the portable XLA path: einsum-based multi-head attention that
compiles to MXU matmuls and lets XLA fuse the softmax chain. Numerically
stable (max-subtracted softmax in float32) regardless of compute dtype.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from distributed_compute_pytorch_tpu.obs.tracing import scope


def dot_product_attention(q, k, v, *, causal: bool = False, bias=None,
                          mask=None, scale: float | None = None):
    """Multi-head scaled dot-product attention.

    Args:
      q, k, v: ``[batch, heads, seq, head_dim]``.
      causal: apply a lower-triangular mask (decoder-only models).
      bias: optional additive logits bias broadcastable to
        ``[batch, heads, q_len, kv_len]``.
      mask: optional boolean mask, True = attend, same broadcast rules.
      scale: logit scale; default ``1/sqrt(head_dim)``.

    Returns ``[batch, heads, seq, head_dim]`` in q's dtype.
    """
    *_, q_len, head_dim = q.shape
    kv_len = k.shape[-2]
    scale = (head_dim ** -0.5) if scale is None else scale
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
        causal_mask = row >= col - (kv_len - q_len)
        logits = jnp.where(causal_mask, logits, -jnp.inf)
    if mask is not None:
        # finite fill (not -inf): a fully-masked row (padded query) yields a
        # uniform-garbage softmax instead of NaN, matching the flash kernel;
        # callers exclude padded positions from every loss, so the garbage
        # never propagates (and its gradient is zero because do is zero)
        logits = jnp.where(mask, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


_BLOCKS = (1024, 512, 256, 128)


def _pick_block(t: int) -> int | None:
    """Largest MXU-friendly block dividing ``t`` (bigger blocks = fewer grid
    steps; 2048 blocks exceed the compile budget). A causal block is not
    multiplied whole for being large: the backward kernels walk the block
    on the diagonal in sub-tiles inside its grid step and drop what lies
    above the diagonal (``ops/pallas/flash_attention.py``). Smaller blocks
    would let the grid skip the same pairs and lose more than they skip: a
    block body costs 1.3-1.7 us of latency whatever it holds, and at
    T = 1024 the train step takes 191 ms at blocks of 1024, 211 at 512 and
    272 at 256 (PERF.md PR 35)."""
    for b in _BLOCKS:
        if t % b == 0:
            return b
    return None


def attention(q, k, v, *, causal: bool = False, scale: float | None = None,
              kv_mask=None, impl: str = "auto", block_q: int | None = None,
              block_k: int | None = None, window: int | None = None,
              mask_block: int = 0):
    """Attention dispatcher: the Pallas flash kernel on TPU when shapes
    allow, the fused-by-XLA dense path otherwise.

    ``kv_mask``: optional ``[batch, kv_len]`` key-validity (padding) mask,
    True = attend — supported by both paths (the flash kernel streams it
    blockwise; the dense path broadcasts it over heads and queries).

    impl: 'auto' (flash on TPU, dense elsewhere) | 'pallas' (force flash,
    interpret-mode off-TPU — used by tests) | 'xla' (force dense).

    ``window``: causal self-attention over the last ``window`` keys (row
    ``i`` sees ``j`` with ``i - window < j <= i``); ``k``/``v`` may then
    stay at their own (fewer) heads. The banded flash forward skips what
    lies outside the band; the dense path masks it.

    ``mask_block``: causal SELF-attention (``q_len == kv_len``) under the
    block mask of a block-diffusion model: row ``i`` sees ``j`` iff ``j //
    mask_block <= i // mask_block``. The flash forward masks its diagonal
    tiles so (forward only); the dense path takes the mask whole.
    """
    if impl not in ("auto", "pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if window is not None:
        return _window_attention(q, k, v, window, scale, kv_mask, impl)
    t, tk = q.shape[-2], k.shape[-2]
    # largest block dividing the length, else the MXU default — the flash
    # wrapper pads-and-masks non-multiples internally (r5; the old dense
    # fallback cost the [T, T] HBM round-trip exactly on the odd-length
    # masked-prefill shapes that need flash most)
    bq = block_q or _pick_block(t) or 128
    bk = block_k or _pick_block(tk) or 128
    # the one genuinely ineligible shape: causal q_len > kv_len (the
    # wrapper rejects it — top rows would attend nothing)
    eligible = not (causal and t > tk)
    if impl == "pallas" and not eligible:
        raise ValueError(
            f"impl='pallas' forced but causal q_len {t} > kv_len {tk} "
            f"is not a meaningful attention shape")
    if mask_block and not (causal and t == tk):
        raise ValueError(f"mask_block is causal self-attention's: causal "
                         f"{causal}, q_len {t}, kv_len {tk}")
    if impl == "pallas" or (impl == "auto" and eligible
                            and jax.default_backend() == "tpu"):
        return _flash_per_shard(
            q, k, v, kv_mask, causal=causal, scale=scale, block_q=bq,
            block_k=bk, **({"mask_block": mask_block} if mask_block else {}))
    mask = None if kv_mask is None else kv_mask[:, None, None, :].astype(bool)
    if mask_block:
        seen = (jnp.arange(tk)[None, :] // mask_block
                <= jnp.arange(t)[:, None] // mask_block)[None, None]
        return dot_product_attention(
            q, k, v, scale=scale, mask=seen if mask is None else seen & mask)
    return dot_product_attention(q, k, v, causal=causal, scale=scale,
                                 mask=mask)


def _window_attention(q, k, v, window, scale, kv_mask, impl):
    """The two engines of a window layer's prefill (see
    :func:`attention`). No mesh: a window layer is served off-mesh."""
    if impl == "pallas" or (impl == "auto"
                            and jax.default_backend() == "tpu"):
        from distributed_compute_pytorch_tpu.ops.pallas.flash_attention \
            import flash_attention_band
        return flash_attention_band(q, k, v, window=window, scale=scale,
                                    kv_mask=kv_mask)
    t = q.shape[-2]
    rep = q.shape[1] // k.shape[1]
    if rep > 1:
        k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    row = jnp.arange(t)[:, None]
    col = jnp.arange(t)[None, :]
    mask = ((col <= row) & (col > row - window))[None, None]
    if kv_mask is not None:
        mask = mask & kv_mask[:, None, None, :].astype(bool)
    return dot_product_attention(q, k, v, scale=scale, mask=mask)


def _flash_per_shard(q, k, v, kv_mask, **kw):
    """The flash kernel where the operands live. A Mosaic call cannot be
    partitioned — jax refuses to lower one into a multi-device program —
    so under a mesh the kernel runs inside a ``shard_map`` manual over
    every mesh axis: batch split over the batch axes, heads over
    ``tensor``, each chip's kernel grid covering its LOCAL batch and
    heads (no gather of q/k/v; forward and backward alike, the backward
    kernels are the transpose of the same region). Off-mesh, on a
    one-device mesh, or already inside a manual region (the pipeline's)
    the kernel is called directly."""
    from jax.sharding import PartitionSpec as P

    from distributed_compute_pytorch_tpu.core.mesh import (
        BATCH_AXES, _manual_axis_names, current_mesh)
    from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
        flash_attention)
    mesh = current_mesh()
    if mesh is None or mesh.size == 1 or _manual_axis_names()[0]:
        return flash_attention(q, k, v, kv_mask=kv_mask, **kw)

    def split(dim, axes):
        # a dim the axes do not divide stays whole (every shard computes
        # all of it): correct, and never an all-gather into the kernel
        axes = tuple(a for a in axes
                     if a in mesh.axis_names and mesh.shape[a] > 1)
        world = 1
        for a in axes:
            world *= mesh.shape[a]
        return axes if axes and dim % world == 0 else None

    batch = split(q.shape[0], BATCH_AXES)
    spec = P(batch, split(q.shape[1], ("tensor",)), None, None)
    masked = () if kv_mask is None else (kv_mask,)
    return jax.shard_map(
        lambda q, k, v, *m: flash_attention(
            q, k, v, kv_mask=m[0] if m else None, **kw),
        mesh=mesh, out_specs=spec,
        in_specs=(spec, spec, spec) + (P(batch, None),) * len(masked),
    )(q, k, v, *masked)


def _pos_valid_mask(pos, t_max: int):
    """``[B or 1, 1, 1, T]`` bool mask of cache slots at-or-before
    ``pos`` — scalar ``pos`` (lockstep decode, one shared write position)
    or ``[B]`` vector (per-row decode, every row at its own position —
    the serving loop's contract, ``serve.ContinuousBatcher``)."""
    pos = jnp.asarray(pos)
    slots = jnp.arange(t_max)
    if pos.ndim:
        return slots[None, None, None, :] <= pos[:, None, None, None]
    return (slots <= pos)[None, None, None, :]


def _multi_pos_valid_mask(pos, t_max: int):
    """``[B, 1, W, T]`` bool mask for a verify WINDOW of queries: query
    ``w`` of row ``b`` sits at position ``pos[b, w]`` and may attend cache
    slots at-or-before it — the per-query generalisation of
    :func:`_pos_valid_mask` (which this reduces to at ``W == 1``). This
    is exactly the bottom-right-causal shape speculative verify needs:
    window queries are consecutive positions, so the staircase mask IS
    the causal rule over (prefix + window)."""
    pos = jnp.asarray(pos)
    slots = jnp.arange(t_max)
    return slots[None, None, None, :] <= pos[:, None, :, None]


def cached_attention(q, k_cache, v_cache, pos, *, scale: float | None = None,
                     slot_mask=None):
    """Single-position decode attention over a preallocated K/V cache.

    Args:
      q: this step's query, ``[B, H, 1, hd]``.
      k_cache, v_cache: ``[B, Hk, T_max, hd]`` caches already holding
        positions ``0..pos`` (``pos`` included). ``Hk`` may be smaller than
        ``H`` (GQA) — heads are repeated here, on the read path, so the
        cache itself stays at kv-head width (the whole point of GQA:
        cache memory and bandwidth scale with ``Hk``).
      pos: position of ``q`` — a scalar (lockstep: all rows share one
        position), an int32 ``[B]`` vector (per-row decode), or an int32
        ``[B, q_len]`` matrix (multi-position verify window: query ``w``
        attends slots ``<= pos[b, w]``); each row's cache slots beyond
        its position are masked.
      slot_mask: optional ``[B, T_max]`` per-row slot validity (0/1 or
        bool) — left-padded variable-length prompts leave pad slots in
        the cache, which must never be attended.

    GQA reads the NARROW cache directly: the query's group dim folds into
    its (length-1) sequence dim, so no ``[B, H, T_max, hd]`` repeat is
    ever materialised — per-tick HBM traffic stays proportional to
    ``Hk``, which is the point of grouped-query attention.

    Returns ``[B, H, 1, hd]``.
    """
    B, H, q_len, hd = q.shape
    hk = k_cache.shape[1]
    grouped = H != hk
    pos_nd = jnp.ndim(pos)
    if grouped:
        assert q_len == 1 or pos_nd == 2, (
            "GQA multi-position cache read needs per-query [B, q_len] pos")
        # fold the group dim into the (short) query dim: row (g, w) of the
        # folded query is head g*q_len + w — per-query masks below must
        # follow the same (g, w) order
        q = q.reshape(B, hk, (H // hk) * q_len, hd)
    # NOTE (measured v5e, 2026-07-30): padding the 1-row query up to a
    # sublane tile speeds the ISOLATED cache read (0.611 -> 0.466 ms for
    # 12 MHA layers) but REGRESSES the full decode tick (gpt2 1.07 ->
    # 1.14 ms; the 8x f32 score intermediates break fusion elsewhere) —
    # measured and rejected, don't re-add without end-to-end numbers.
    # NOTE (measured v5e, r5): DEFERRED-write attention (cache holds
    # slots < pos, current K/V inline as an appended softmax column, all
    # layers' rows committed in one end-of-tick stacked launch) was
    # built and measured-REJECTED: reads preceding the aliased write
    # cost XLA the in-place update (full cache copy; llama tick 0.559 ->
    # 0.804 ms). Write-then-attend with the kv-pair kernel is the
    # measured-fast form (ops/pallas/cache_update.py).
    valid = (_multi_pos_valid_mask(pos, k_cache.shape[2]) if pos_nd == 2
             else _pos_valid_mask(pos, k_cache.shape[2]))
    if slot_mask is not None:
        valid = jnp.logical_and(valid,
                                slot_mask[:, None, None, :].astype(bool))
    if grouped and q_len > 1:
        # [B, 1, W, T] -> [B, 1, G*W, T]: folded query row g*W + w needs
        # mask row w, i.e. the window mask tiled over groups
        valid = jnp.tile(valid, (1, 1, H // hk, 1))
    out = dot_product_attention(q, k_cache, v_cache, mask=valid,
                                scale=scale)
    return out.reshape(B, H, q_len, hd) if grouped else out


def split_heads(x, num_heads: int):
    """``[b, t, d]`` -> ``[b, h, t, d/h]``."""
    b, t, d = x.shape
    return x.reshape(b, t, num_heads, d // num_heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    """``[b, h, t, hd]`` -> ``[b, t, h*hd]``."""
    b, h, t, hd = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * hd)


def cached_attention_q8(q, cache, pos, *, scale: float | None = None,
                        slot_mask=None):
    """:func:`cached_attention` over an INT8-quantized K/V cache.

    ``cache``: ``{"k","v": int8 [B, Hk, T_max, hd],
    "k_scale","v_scale": f32 [B, Hk, T_max, 1]}`` — per-row symmetric
    scales (``utils/quantize.py::quantize_kv``). The scales commute out
    of both contractions, so the int8 arrays enter the dots DIRECTLY
    (the weight-quantization lesson, ``ops/int8_matmul.py``: a dequant
    first would materialise a bf16 copy and lose the bandwidth):

    - score_t = (q . k_q_t) * k_scale_t — the K scale is per cache ROW,
      which is the score's last axis, a plain broadcast multiply;
    - out = sum_t p_t * v_t = sum_t (p_t * v_scale_t) * v_q_t — the V
      scale folds into the probability before the value contraction.

    Probabilities are computed in f32 and cast to ``q.dtype`` for the
    value dot (the measured-fast mixed-dtype pairing is bf16 x int8);
    that cast is the one extra rounding vs the bf16-cache path and is
    far below the int8 quantization error itself.

    MEASURED (v5e, 2026-07-31) and NOT the default: unlike the 2-D
    weight matmuls (``ops/int8_matmul.py``), the BATCHED 4-D mixed
    dots here do not stream the int8 cache — the full decode tick
    regresses (llama 0.52 -> 0.99 ms, gpt2 0.97 -> 2.34 with int8
    weights on). ``--quantize int8-kv`` therefore buys cache MEMORY
    (half the bytes resident — longer contexts per chip), not speed,
    on current XLA:TPU; revisit if batched mixed-dot lowering improves.
    """
    B, H, q_len, hd = q.shape
    k_q, v_q = cache["k"], cache["v"]
    hk = k_q.shape[1]
    grouped = H != hk
    pos_nd = jnp.ndim(pos)
    if grouped:
        assert q_len == 1 or pos_nd == 2, (
            "GQA multi-position cache read needs per-query [B, q_len] pos")
        q = q.reshape(B, hk, (H // hk) * q_len, hd)
    sc = (hd ** -0.5) if scale is None else scale
    # [B, hk, g, T]: mixed bf16 x int8 dot over hd, batched over (B, hk)
    scores = lax.dot_general(
        q, k_q, dimension_numbers=(((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * sc
    scores = scores * cache["k_scale"][:, :, None, :, 0]
    valid = (_multi_pos_valid_mask(pos, k_q.shape[2]) if pos_nd == 2
             else _pos_valid_mask(pos, k_q.shape[2]))
    if slot_mask is not None:
        valid = jnp.logical_and(valid,
                                slot_mask[:, None, None, :].astype(bool))
    if grouped and q_len > 1:
        # folded query row g*W + w takes window-mask row w (see
        # cached_attention)
        valid = jnp.tile(valid, (1, 1, H // hk, 1))
    # finite fill, not -inf: a fully-masked row (padded query) must give
    # finite garbage downstream masking absorbs, never NaN — same
    # convention as dot_product_attention above
    probs = jax.nn.softmax(jnp.where(valid, scores, -1e30), axis=-1)
    pv = (probs * cache["v_scale"][:, :, None, :, 0]).astype(q.dtype)
    out = lax.dot_general(
        pv, v_q, dimension_numbers=(((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32).astype(q.dtype)
    return out.reshape(B, H, q_len, hd) if grouped else out


class CacheLeaf(NamedTuple):
    """One leaf of a layer's serving cache, as the layer declares it
    (``models/hybrid.py::HybridBlock.cache_leaves``) and as ``serve.py``
    allocates, writes, copies and accounts it. A leaf is keyed BY BLOCK
    (``slot_axis`` None): ``[s, P, heads, rows, width]`` with axis 1 the
    pool's block axis, so on the block table and the free list, written
    by admission in whole blocks, copied by copy-on-write and pinned to
    the pool's sharding. Or BY SLOT (``slot_axis``: the axis with one
    entry a slot): never on the table, written by admission at the
    wave's slots, never copied. ``tokens``: how many tokens one entry (a
    block, a slot) holds, which is what bytes a cached token are counted
    from; 0 for what is a function of the whole prefix (a state, a
    tail), counted by the slot."""

    shape: tuple
    dtype: object
    slot_axis: int | None = None
    tokens: int = 0

    @property
    def by_block(self) -> bool:
        return self.slot_axis is None


def kv_pool_leaves(blocks: int, heads: int, block_tokens: int,
                   head_dim: int, dtype, kv_dtype: str = "bf16") -> dict:
    """The paged K/V pool of one layer as declared leaves: ``{"kv": [2,
    P, hk, bt, hd]}`` in the compute ``dtype``, or for ``kv_dtype``
    ``"int8"`` int8 blocks with a ``"scale"`` leaf ``[2, P, hk, bt, 1]``
    (float32, one scale a position and head) beside them, sharded alike
    (the last two axes are unsharded in ``infer._POOL_SPEC``)."""
    int8 = kv_dtype == "int8"
    shape = (2, blocks, heads, block_tokens)
    leaves = {"kv": CacheLeaf(shape + (head_dim,),
                              jnp.int8 if int8 else dtype,
                              tokens=block_tokens)}
    if int8:
        leaves["scale"] = CacheLeaf(shape + (1,), jnp.float32,
                                    tokens=block_tokens)
    return leaves


def gather_kv_blocks(pool_leaf, table):
    """Materialise the LOGICAL per-row cache view of a paged pool leaf:
    ``pool_leaf [s, P, hk, bt, hd]`` gathered through ``table [B, nb]``
    -> ``[s, B, hk, nb * bt, hd]`` — row ``b``'s logical slot ``t`` is
    ``pool_leaf[:, table[b, t // bt], :, t % bt]``.

    This is the portable-XLA paged read: what every pool reads through
    that :func:`paged_read_path` does not hand to the block-table kernel
    (CPU, meshes, hd 64, the int8 pool, verify windows). Its traffic is
    set ENTIRELY by the table argument: ``O(B * nb * bt)`` bytes per
    layer per tick for whatever ``nb`` the caller ships — every row at
    the width of the widest. The serve scheduler slices the host tables
    to the smallest bucket-ladder rung covering the live working set
    (``serve.py``, ISSUE 19), so a tick's gather moves bytes
    proportional to the LONGEST live row, not to ``t_max``; the
    fixed-horizon cost model (every tick gathering ``t_max`` slots)
    only returns when bucketing is off (``decode_width_buckets=1``) or
    a session actually fills the horizon. The gather is three copies on
    current XLA:TPU (the gather, the transpose, the K/V split): 60% of
    a Mistral-7B decode tick on the v5e (PERF.md, PR 24), which is why
    eligible pools are read in place instead
    (``ops/pallas/decode_attention.py::paged_decode_attention_pallas``).
    Under a mesh the gather's OUTPUT is constrained to the row-sharded
    decode layout by the caller, so attached blocks reshard into it
    via whatever collective the two layouts imply (the
    arXiv:2112.01075 redistribution move) — a sliced table just
    narrows the unsharded slot axis of that move."""
    g = pool_leaf[:, table]                    # [s, B, nb, hk, bt, hd]
    s, B, nb, hk, bt, hd = g.shape
    return g.transpose(0, 1, 3, 2, 4, 5).reshape(s, B, hk, nb * bt, hd)


def _paged_view(pool, table) -> dict:
    """A paged pool's logical per-row view as attention reads it:
    ``{"k", "v"}`` (plus ``"k_scale"``/``"v_scale"`` for the int8 form),
    each ``[B, hk, nb * bt, hd]``. The K/V split is a copy of the whole
    gathered view on this backend, so it counts with the gather."""
    with scope("kv_gather"):
        kv = gather_kv_blocks(pool["kv"], table)
        view = {"k": kv[0], "v": kv[1]}
        if "scale" in pool:
            sc = gather_kv_blocks(pool["scale"], table)
            view.update(k_scale=sc[0], v_scale=sc[1])
        return view


def paged_read_path(pool: dict, q_len: int, slot_mask=None,
                    one_range: bool = False) -> str:
    """Which engine reads a paged pool for attention: ``"kernel"`` (the
    block-table Pallas kernel, the pool read in place) or ``"gather"``
    (:func:`_paged_view` + the dense cached attention). Decided from the
    operands alone: the repo's one policy for Pallas dispatchers
    (``cache_update._pallas_ok``: TPU backend, no mesh context, window-
    aligned blocks), a float pool (no int8 ``scale`` leaf), ONE query
    position (or ``one_range``: a row's ``q_len`` queries all attend the
    same slots, as the positions of a block-diffusion block do), no
    ``slot_mask``, heads of whole 128-lane tiles, and a
    block of all KV heads small enough for the kernel's VMEM scratch.
    Everything else (CPU, a mesh, hd 64, the int8 pool, verify windows)
    reads through the gather, exactly as before the kernel existed."""
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        _pallas_ok)
    from distributed_compute_pytorch_tpu.ops.pallas.decode_attention import (
        _chunk_blocks)
    kv = pool["kv"]
    eligible = ((q_len == 1 or one_range) and slot_mask is None
                and "scale" not in pool
                and kv.shape[-1] % 128 == 0
                and _chunk_blocks(kv.shape, kv.dtype.itemsize, 1) == 1
                and _pallas_ok(pool, axis=3))
    return "kernel" if eligible else "gather"


def _paged_write_and_attend(q, k, v, cache, pos, *, slot_mask=None):
    """One decode tick against the PAGED pool cache format
    ``{"kv": [2, P, hk, bt, hd], "table": int32 [B, nb]}`` (plus
    ``"scale"`` for the int8 form): row ``b`` writes its K/V at the
    physical (block, offset) its table maps logical slot ``pos[b]`` to,
    then attends over its logical slots ``0 .. pos[b]``: through the
    block table inside ``dcp_paged_decode_attn`` where
    :func:`paged_read_path` says so, over the gathered logical view
    otherwise. The caller (the serve
    scheduler) guarantees the written block is exclusively owned —
    shared prefix blocks are copy-on-write BEFORE a row may write into
    their span, so the write never mutates another row's reads.

    The working-set WIDTH flows from the table: a ``[B, nb_w]`` slice
    makes the gathered views, the position-validity masks, and the
    ``slot_mask`` plumbing all ``nb_w * bt`` wide (including the int8
    ``scale`` leaf, gathered through the same table); the kernel's cost
    follows ``pos``, not the slice. The caller must
    ship a table covering ``max(pos) // bt`` — the write's
    ``take_along_axis`` clamps, which is only correct for parked rows
    whose table is all-trash. A PARKED row (its table's first entry the
    trash block: a live row's never is) still writes, into trash; what it
    attends is nobody's to read: the kernel passes it by and returns
    zeros for it, the gather attends the trash block."""
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        kv_pool_insert_all)
    from distributed_compute_pytorch_tpu.utils.quantize import quantize_kv
    table = cache["table"]
    pool = {n: leaf for n, leaf in cache.items() if n != "table"}
    bt = pool["kv"].shape[3]
    pos = jnp.broadcast_to(jnp.atleast_1d(pos), (q.shape[0],))
    blk = jnp.take_along_axis(table, (pos // bt)[:, None], axis=1)[:, 0]
    off = pos % bt
    if "scale" in pool:
        with scope("kv_write"):
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            pool = kv_pool_insert_all(
                pool, {"kv": jnp.stack([kq, vq]),
                       "scale": jnp.stack([ks, vs])}, blk, off)
        view = _paged_view(pool, table)
        out = cached_attention_q8(q, view, pos, slot_mask=slot_mask)
    else:
        with scope("kv_write"):
            pool = kv_pool_insert_all(pool, {"kv": jnp.stack([k, v])},
                                      blk, off)
        if paged_read_path(pool, q.shape[2], slot_mask) == "kernel":
            from distributed_compute_pytorch_tpu.ops.pallas import (
                decode_attention)
            # write-then-attend, as everywhere: a read placed before the
            # aliased pool write costs XLA the in-place update
            out = decode_attention.paged_decode_attention_pallas(
                q, pool["kv"], table, pos)
        else:
            view = _paged_view(pool, table)
            out = cached_attention(q, view["k"], view["v"], pos,
                                   slot_mask=slot_mask)
    return out, {**pool, "table": table}


def block_write_and_attend(q, k, v, cache, pos0):
    """One pass over a BLOCK of a block-diffusion model against the paged
    pool (``cache`` as :func:`_paged_write_and_attend` takes it, float
    pools only): ``q [B, H, L, hd]``, ``k``/``v`` ``[B, hk, L, hd]`` are
    the ``L`` positions ``pos0[b] .. pos0[b] + L - 1`` of each row, ``pos0``
    and the pool's block size multiples of ``L``, so a block lies in ONE
    pool block. The block's K/V are written first (one window a row:
    ``cache_update.kv_pool_insert_span_all``), then every query attends
    slots ``0 .. pos0 + L - 1``: ONE range a row for all its queries, so
    where :func:`paged_read_path` allows the pool is read in place by the
    block-table kernel with the ``L`` queries beside the head group (``L x
    G`` query rows to a KV head), else through the gathered view. A parked
    row (an all-trash table) writes into trash and attends nothing worth
    reading, as in a tick."""
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        kv_pool_insert_span_all)
    table = cache["table"]
    pool = {n: leaf for n, leaf in cache.items() if n != "table"}
    assert "scale" not in pool, "a block pass has no int8 form"
    bt, L = pool["kv"].shape[3], q.shape[2]
    assert bt % L == 0, (bt, L)
    blk = jnp.take_along_axis(table, (pos0 // bt)[:, None], axis=1)[:, 0]
    with scope("kv_write"):
        pool = kv_pool_insert_span_all(pool, {"kv": jnp.stack([k, v])},
                                       blk, pos0 % bt)
    end = pos0 + L - 1
    if paged_read_path(pool, L, one_range=True) == "kernel":
        from distributed_compute_pytorch_tpu.ops.pallas import (
            decode_attention)
        out = decode_attention.paged_decode_attention_pallas(
            q, pool["kv"], table, end)
    else:
        view = _paged_view(pool, table)
        out = cached_attention(
            q, view["k"], view["v"],
            jnp.broadcast_to(end[:, None], (q.shape[0], L)))
    return out, {**pool, "table": table}


def latent_read_path(pool: dict) -> str:
    """Which engine reads a LATENT pool (``{"kv": [1, P, 1, bt, W]}``, a
    token one vector: ``models/hybrid.py``, mixer ``latent_attention``) in
    a decode tick: ``"kernel"`` (``dcp_paged_latent_decode_attn``, the
    pool in place through the block table) or ``"gather"`` (the gathered
    logical view and a dense softmax). The one policy for Pallas
    dispatchers (``cache_update._pallas_ok``: TPU backend, no mesh
    context, window-aligned blocks) and a float pool."""
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        _pallas_ok)
    return ("kernel" if "scale" not in pool and _pallas_ok(pool, axis=3)
            else "gather")


def latent_pool_width(width: int) -> int:
    """Channels a latent pool gives a token of ``width`` channels: whole
    128-lane tiles (576 -> 640). A TPU lays an array's minor axis out in
    such tiles whatever its length, so the padding costs no byte the chip
    would not spend anyway, and a copy of a block in or out of the pool is
    then whole tiles (Mosaic refuses to slice 576 of 640 lanes)."""
    return -(-width // 128) * 128


def pad_channels(x, width: int):
    """``x [..., w]`` zero-padded on its last axis to ``width``."""
    if x.shape[-1] == width:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])])


def latent_write(latent, cache, pos):
    """The write half of a latent layer's decode tick: row ``b``'s token
    vector ``latent [B, W]`` (zero-padded to the pool's width) goes to the
    (block, offset) its table maps slot ``pos[b]`` to. ``cache`` holds the
    pool's ``"kv"`` leaf and the ``"table"`` (other leaves pass through
    untouched). Returns the pool's new ``"kv"`` leaf."""
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        kv_pool_insert_all)
    table = cache["table"]
    bt, Wp = cache["kv"].shape[3:]
    latent = pad_channels(latent, Wp)
    blk = jnp.take_along_axis(table, (pos // bt)[:, None], axis=1)[:, 0]
    with scope("kv_write"):
        return kv_pool_insert_all(
            {"kv": cache["kv"]}, {"kv": latent[None, :, None, None, :]},
            blk, pos % bt)["kv"]


def latent_write_and_attend(q, latent, cache, pos, *, v_width: int,
                            scale: float):
    """One decode tick of a LATENT-attention layer against its paged pool
    ``{"kv": [1, P, 1, bt, Wp], "table": int32 [B, nb]}``: row ``b`` writes
    its token's vector ``latent [B, W]`` (compressed K/V, then the rotary
    key; zero-padded to the pool's ``Wp = latent_pool_width(W)``, as the
    queries are) at the (block, offset) its table maps slot ``pos[b]`` to
    (:func:`latent_write`), then its ``H`` absorbed queries ``q [B, H, W]``
    attend slots ``0 .. pos[b]``: the stored vector is every head's key and
    its first ``v_width`` channels are the value. In place through the
    table where :func:`latent_read_path` says ``kernel``; otherwise (CPU, a
    mesh) over the gathered view, which is also the kernel's test oracle.
    The same block table, write kernel, width rules and parked rows as
    :func:`_paged_write_and_attend`. Returns ``(o [B, H, v_width],
    new_cache)``."""
    table = cache["table"]
    Wp = cache["kv"].shape[-1]
    q, latent = pad_channels(q, Wp), pad_channels(latent, Wp)
    pos = jnp.broadcast_to(jnp.atleast_1d(pos), (q.shape[0],))
    pool = {"kv": latent_write(latent, cache, pos)}
    if latent_read_path(pool) == "kernel":
        from distributed_compute_pytorch_tpu.ops.pallas import (
            decode_attention)
        out = decode_attention.paged_latent_decode_attention_pallas(
            q, pool["kv"], table, pos, v_width=v_width, scale=scale)
    else:
        out = latent_attention_gathered(q, pool["kv"], table, pos,
                                        v_width=v_width, scale=scale)
    return out, {**pool, "table": table}


def latent_attention_gathered(q, pool_kv, table, pos, *, v_width: int,
                              scale: float):
    """The portable read of a latent pool: the rows' logical views
    gathered through ``table``, one dense masked softmax over them (all
    heads share the one key, so the heads are the query axis)."""
    with scope("kv_gather"):
        lat = gather_kv_blocks(pool_kv, table)[0]        # [B, 1, T, W]
    valid = _pos_valid_mask(pos, lat.shape[2])
    out = dot_product_attention(q[:, None], lat, lat[..., :v_width],
                                mask=valid, scale=scale)
    return out[:, 0]


def masked_attention(q, k, v, mask, scale: float):
    """Softmax attention of a block of queries under an arbitrary mask:
    ``q [B, H, Tq, d]``, ``k [B, H, Tk, d]``, ``v [B, H, Tk, dv]``, ``mask``
    broadcastable to ``[B, H, Tq, Tk]`` (True = attend; a row that attends
    nothing gives finite garbage, as :func:`dot_product_attention`'s
    does). The row maximum is a reduction of its own behind a barrier: left
    to fuse with the subtraction that broadcasts it back, the v5e's
    compiler turns the pair into a window reduction of ``2 Tk - 1`` taps an
    element for ``Tk`` of 4096 and 8192 (11.7 ms a block of 256 queries
    where its products take 0.6: PERF.md, PR 41). The weights are
    normalised after the product with ``v``, on ``dv`` channels and not
    ``Tk``."""
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    logits = jnp.where(mask, logits, -1e30)
    top = lax.optimization_barrier(jnp.max(logits, -1, keepdims=True))
    p = jnp.exp(logits - top)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return (out / jnp.sum(p, -1, keepdims=True)).astype(q.dtype)


def topk_mask(score, k: int):
    """``score [..., n]`` -> bool ``[..., n]``: the ``k`` largest entries
    of each row, ties to the lower index (what ``lax.top_k`` picks, as a
    mask and without a scatter): everything above the ``k``-th value, and
    of the entries equal to it the first that fill the count."""
    k = min(k, score.shape[-1])
    kth = lax.top_k(score, k)[0][..., -1:]
    above = score > kth
    tie = score == kth
    room = k - jnp.sum(above, -1, keepdims=True)
    return above | (tie & (jnp.cumsum(tie, -1) <= room))


def index_scores(qi, w, pooled):
    """The indexer's score of every pooled key for every query: ``qi [B,
    T, Hi, di]``, head weights ``w [B, T, Hi]`` (float32), ``pooled [B, G,
    di]`` -> ``sum_j w_j ReLU(q_j . K_g)`` ``[B, T, G]`` float32."""
    s = jnp.einsum("btjd,bgd->btjg", qi, pooled.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btj,btjg->btg", w, jax.nn.relu(s))


def selected_latent_attention(q, pool_kv, table, pos, groups, groups_ok, *,
                              group_tokens: int, v_width: int, scale: float):
    """The read of a latent pool that touches only what was CHOSEN: row
    ``b``'s ``H`` absorbed queries ``q [B, H, Wp]`` attend the tokens of
    its chosen groups ``groups [B, k]`` (``groups_ok``: which entries count;
    group ``g`` is tokens ``g * group_tokens ..``) and its own group as far
    as it has come (``group_tokens * (pos // group_tokens) .. pos``). The
    tokens' vectors are gathered from ``pool_kv [1, P, 1, bt, Wp]`` by
    (block, offset) through ``table``; what was not chosen is never read.
    Returns ``(o [B, H, v_width], attended [B])``: the tokens each row
    attended."""
    P_ = group_tokens
    bt, Wp = pool_kv.shape[3:]
    B = q.shape[0]
    own = (pos // P_ * P_)[:, None] + jnp.arange(P_)[None, :]       # [B, P]
    tok = jnp.concatenate(
        [(groups[:, :, None] * P_ + jnp.arange(P_)).reshape(B, -1), own], 1)
    ok = jnp.concatenate(
        [jnp.repeat(groups_ok, P_, axis=1), own <= pos[:, None]], 1)
    tok = jnp.where(ok, tok, 0)
    with scope("kv_gather"):
        blk = jnp.take_along_axis(table, tok // bt, axis=1)
        lat = pool_kv.reshape(-1, Wp)[blk * bt + tok % bt]     # [B, S, Wp]
    out = dot_product_attention(q[:, None], lat[:, None],
                                lat[:, None, :, :v_width],
                                mask=ok[:, None, None, :], scale=scale)
    return out[:, 0], jnp.sum(ok, axis=1)


def _decay_gram(x, y, b, sub: int, floor: bool = True):
    """``G[t, j] = sum_c x[t, c] y[j, c] exp(b[t, c] - b[j, c])`` for ``j <=
    t`` within one chunk (entries with ``j > t`` are unspecified but
    finite): ``x, y, b [B, C, H, d]`` float32, ``b`` the running sum of the
    log-decays (non-increasing along ``C``) -> ``[B, H, C, C]``. The
    exponent is split at the start of ``t``'s SUB-CHUNK of ``sub`` tokens,
    so that one factor never exceeds 1 and the other ``exp(sub * max
    |log-decay|)``: both stay in float32's range where the plain split
    ``(x e^b) (y e^-b)`` overflows after a few dozen tokens. That holds
    while the log-decay has a ``floor``. Without one no split inside a
    sub-chunk is safe (the secondary chunking of arXiv:2312.06635, section
    4): the split then serves the ``j`` of EARLIER sub-chunks alone, where
    both factors, ``e^(b_t - ref)`` and ``e^(ref - b_j)``, are at most 1,
    and INSIDE a sub-chunk the exponent is the difference ``b_t - b_j <=
    0`` itself, a channel at a time: no ``g <= 0`` can overflow, and what
    underflows to zero is zero to float32."""
    B, C, H, d = x.shape
    P_ = C // sub
    hi = lax.Precision.HIGHEST
    subs = lambda t: t.reshape(B, P_, sub, H, d)
    # b at the token before each sub-chunk's first (0 before the chunk's)
    ref = jnp.concatenate([jnp.zeros_like(b[:, :1]),
                           b[:, sub - 1:C - 1:sub]], 1)         # [B, P, H, d]
    xl = subs(x * jnp.exp(b - jnp.repeat(ref, sub, axis=1)))
    # for the sub-chunk p of t: y[j] e^(ref_p - b_j), nothing for a j of a
    # later sub-chunk (its exponent has no bound) nor, without a floor, of
    # p itself
    sub_of, p = jnp.arange(C)[None, :] // sub, jnp.arange(P_)[:, None]
    unseen = sub_of > p if floor else sub_of >= p
    expo = jnp.where(unseen[None, :, :, None, None], -jnp.inf,
                     ref[:, :, None] - b[:, None])            # [B, P, C, H, d]
    yr = y[:, None] * jnp.exp(expo)
    g = jnp.einsum("bpihc,bpjhc->bhpij", xl, yr, precision=hi)
    if not floor:
        bs = subs(b)
        within = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
        e = jnp.exp(jnp.where(within[:, :, None, None],
                              bs[:, :, :, None] - bs[:, :, None], -jnp.inf))
        own = jnp.einsum("bpihc,bpjhc,bpijhc->bhpij", subs(x), subs(y), e,
                         precision=hi)                     # [B, H, P, sub, sub]
        # each sub-chunk's own block onto the diagonal
        g = g + (own[:, :, :, :, None, :] * jnp.eye(P_, dtype=own.dtype)[
            :, None, :, None]).reshape(g.shape)
    return g.reshape(B, H, C, C)


def kda_heads(aq, ak, av, f, a, lower_bound: float | None):
    """A KDA layer's heads from their convolved projections, ``[..., dk]``
    a head: ``q`` (unit length times ``dk ** -0.5``), ``k`` (unit length),
    ``v`` and the log-decay ``g`` (``f`` the decay gate's logits ``[...,
    dk]``, ``a`` the head's rate, broadcast against it), all float32. The
    gate has two forms, the layer's configuration says which: BOUNDED by a
    floor, ``g = lower_bound * sigmoid(a * f)`` in ``[lower_bound, 0)``, or
    (``lower_bound`` None) Kimi Linear's own with no floor, ``g = -a *
    softplus(f)``. The one definition: the models call it on a window or a
    token, ``ops/pallas/kda_scan.py`` on a block."""
    f32 = lambda t: t.astype(jnp.float32)
    unit = lambda t: t * lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)
    if lower_bound is None:
        g = -f32(a) * jax.nn.softplus(f32(f))
    else:
        g = lower_bound * jax.nn.sigmoid(f32(a) * f32(f))
    return unit(f32(aq)) * aq.shape[-1] ** -0.5, unit(f32(ak)), f32(av), g


def kda_chunk(S, q, k, v, g, beta, sub: int, floor: bool = True):
    """One chunk of the delta rule with a per-channel decay, all its tokens
    at once: state ``S [B, H, dk, dv]`` before the chunk, ``q, k, g [B, C,
    H, dk]`` (``g`` the tokens' log-decays, ``<= 0``; ``floor``: no lower
    than ``-80 / sub``, else the decay grams take the form that needs no
    floor), ``v [B, C, H, dv]``, ``beta [B, C, H]`` (in ``[0, 2)``), all
    float32 -> ``(o [B, C, H, dv], S')``. Equal to
    ``C`` steps of :func:`kda_step` in exact arithmetic: with ``b`` the
    running sum of ``g``, the tokens' corrections ``Delta`` solve the unit
    lower-triangular system ``(I + Diag(beta) A) Delta = Diag(beta) (V - (K
    e^b) S)``, ``A[t, j] = (k_t e^{b_t}) . (k_j e^{-b_j})`` for ``j < t``;
    its inverse is the product ``(I + M)(I + M^2)(I + M^4)...`` of the
    nilpotent ``M = -Diag(beta) A``. A token with ``beta = 0`` and ``g = 0``
    leaves the state as it was."""
    hi = lax.Precision.HIGHEST
    C = q.shape[1]
    b = jnp.cumsum(g, axis=1)
    A = jnp.tril(_decay_gram(k, k, b, sub, floor), -1)
    Bm = jnp.tril(_decay_gram(q, k, b, sub, floor))
    bh = beta.transpose(0, 2, 1)[..., None]                   # [B, H, C, 1]
    M = -bh * A
    inv = jnp.eye(C, dtype=M.dtype) + M
    for _ in range((C - 1).bit_length() - 1):
        M = jnp.matmul(M, M, precision=hi)
        inv = inv + jnp.matmul(inv, M, precision=hi)
    heads = lambda t: t.transpose(0, 2, 1, 3)                 # [B, H, C, .]
    eb = jnp.exp(b)
    rhs = jnp.matmul(inv, bh * jnp.concatenate(
        [heads(v), heads(k * eb)], -1), precision=hi)
    dv = v.shape[-1]
    delta = rhs[..., :dv] - jnp.matmul(rhs[..., dv:], S, precision=hi)
    o = (jnp.matmul(heads(q * eb), S, precision=hi)
         + jnp.matmul(Bm, delta, precision=hi))
    k_end = heads(k * jnp.exp(b[:, -1:] - b))
    S = (heads(eb[:, -1:])[:, :, 0, :, None] * S
         + jnp.einsum("bhck,bhcv->bhkv", k_end, delta, precision=hi))
    return o.transpose(0, 2, 1, 3), S


def whole_chunks(t, chunk: int):
    """``t [B, T, ...]`` zero-padded along ``T`` to whole chunks."""
    short = -t.shape[1] % chunk
    return t if not short else jnp.pad(
        t, ((0, 0), (0, short)) + ((0, 0),) * (t.ndim - 2))


def _kda_kernel_ok(head_dim: int) -> bool:
    """Whether a window's recurrence (and a token's step:
    :func:`kda_step_live`) runs as the Pallas kernel: one TPU
    (no mesh context, as the pool's kernels: ``cache_update._pallas_ok``)
    and heads a whole number of lane tiles wide."""
    from distributed_compute_pytorch_tpu.core.mesh import current_mesh
    return (jax.default_backend() == "tpu" and current_mesh() is None
            and head_dim % 128 == 0)


def kda_window(aq, ak, av, f_low, f_up, dt_bias, rate, beta, real, *,
               lower_bound: float | None, chunk: int, sub: int):
    """The delta rule with a per-channel decay over whole windows, from the
    zero state, ``chunk`` tokens at a time: ``aq, ak, av [B, T, H * dk]``
    the CONVOLVED projections, ``f_low [B, T, R]`` and ``f_up [R, H * dk]``
    the decay gate's two factors, ``dt_bias [H * dk]``, ``rate [H]``
    (``exp(A_log)``), ``beta [B, T, H]`` float32, ``real [B, T]`` (1 at a
    token, 0 at a pad: a pad gets ``beta = 0`` and no decay),
    ``lower_bound`` the gate's floor or None (:func:`kda_heads`) -> ``(o [B, T,
    H, dk] float32, S [B, H, dk, dk] float32)``, ``S`` the state after each
    row's last real token. One algorithm, two executions chosen by what
    can be observed (:func:`_kda_kernel_ok`): ONE kernel with a head's
    state in VMEM (``ops/pallas/kda_scan.py``), or a ``lax.scan`` of
    :func:`kda_chunk`, the portable form the kernel is tested against."""
    B, T, _ = aq.shape
    H = beta.shape[-1]
    dk = aq.shape[-1] // H
    if _kda_kernel_ok(dk):
        from distributed_compute_pytorch_tpu.ops.pallas.kda_scan import (
            kda_chunk_scan)
        return kda_chunk_scan(aq, ak, av, f_low, f_up, dt_bias, rate, beta,
                              real, lower_bound=lower_bound, chunk=chunk,
                              sub=sub)
    nC = -(-T // chunk)
    chunks = lambda t: whole_chunks(t, chunk).reshape(
        (B, nC, chunk) + t.shape[2:]).swapaxes(0, 1)
    heads = lambda t: t.reshape(t.shape[:-1] + (H, dk))

    def step(S, xs):
        aq_c, ak_c, av_c, f_c, beta_c, real_c = xs
        f = jnp.dot(f_c, f_up.astype(f_c.dtype),
                    preferred_element_type=jnp.float32) + dt_bias
        q, k, v, g = kda_heads(heads(aq_c), heads(ak_c), heads(av_c),
                               heads(f), rate[:, None], lower_bound)
        o, S = kda_chunk(S, q, k, v, g * real_c[..., None, None],
                         beta_c * real_c[..., None], sub,
                         floor=lower_bound is not None)
        return S, o

    S, o = lax.scan(step, jnp.zeros((B, H, dk, dk), jnp.float32),
                    tuple(chunks(t) for t in (aq, ak, av, f_low, beta, real)))
    return o.swapaxes(0, 1).reshape(B, nC * chunk, H, dk)[:, :T], S


def kda_step(S, q, k, v, g, beta):
    """One token of the delta rule with a per-channel decay: ``S [B, H, dk,
    dv]``, ``q, k, g [B, H, dk]``, ``v [B, H, dv]``, ``beta [B, H]``, all
    float32 -> ``(o [B, H, dv], S')``: ``S' = (I - beta k k^T) Diag(e^g) S +
    beta k v^T``, ``o = S'^T q``."""
    hi = lax.Precision.HIGHEST
    S = S * jnp.exp(g)[..., None]
    w = jnp.einsum("bhk,bhkv->bhv", k, S, precision=hi)
    S = S + k[..., None] * (beta[..., None] * (v - w))[:, :, None, :]
    return jnp.einsum("bhk,bhkv->bhv", q, S, precision=hi), S


def kda_step_live(S, q, k, v, g, beta, live=None):
    """:func:`kda_step` for the rows in the plan: ``live [B]`` (above 0.5:
    the row advances; None: every row does) -> ``(o [B, H, dv], S')``, a
    parked row's state as it was and its ``o`` of no use to anyone. One
    algorithm, two executions chosen by what can be observed
    (:func:`_kda_kernel_ok`): ONE kernel that moves a live row's state in
    and out once and nothing of a parked row's
    (``ops/pallas/kda_step.py``), or :func:`kda_step` and a select, the
    portable form the kernel is tested against."""
    if _kda_kernel_ok(S.shape[-2]) and S.shape[-2] == S.shape[-1]:
        from distributed_compute_pytorch_tpu.ops.pallas.kda_step import (
            kda_step_rows)
        return kda_step_rows(S, q, k, v, g, beta, live)
    o, new = kda_step(S, q, k, v, g, beta)
    if live is not None:
        new = jnp.where((live > 0.5)[:, None, None, None], new, S)
    return o, new


def cache_verify_and_attend(q, k, v, cache, positions, *, slot_mask=None):
    """One speculative VERIFY step against the paged pool cache: all ``W``
    window positions of every row written and attended in a single pass.

    Args:
      q, k, v: ``[B, H(k), W, hd]`` — the verify window's projections
        (position ``w`` of row ``b`` is logical slot ``positions[b, w]``).
      cache: the paged pool format ``{"kv": [2, P, hk, bt, hd],
        "table": int32 [B, nb]}`` (plus ``"scale"`` for the int8 pool).
      positions: int32 ``[B, W]`` — consecutive per-row logical slots.
      slot_mask: optional ``[B, nb * bt]`` per-row slot validity.

    The write is the portable-XLA scatter (the admission idiom): window
    K/V land at the physical (block, offset) each row's table maps its
    slots to, with positions at-or-beyond the logical horizon routed to
    an out-of-range sentinel block id and DROPPED (``mode="drop"``) —
    drafted tokens can thus never write past a row's allocated extent.
    Attention then reads the gathered logical view under the per-query
    staircase mask (:func:`_multi_pos_valid_mask`): query ``w`` sees
    ``slots <= positions[b, w]``, i.e. the prefix plus the window's own
    bottom-right-causal triangle — the SAME kv_len/mask semantics as
    ``W`` sequential decode ticks, which is what makes verify outputs
    bit-comparable to plain decode. Speculation is a pure read-side
    rollback: rejecting tokens only rewinds the host's per-row position,
    stale K/V beyond it is never attended and is overwritten by the next
    verify. Returns ``(o [B, H, W, hd], new_cache)``.

    As everywhere in the paged path, the logical horizon is the
    TABLE's: ``t_max = table.shape[1] * bt``. A width-bucketed caller
    (serve.py, ISSUE 19) shipping a ``[B, nb_w]`` slice must pick a
    rung covering ``max(positions) + 1`` slots, or an in-horizon write
    would be sentinel-dropped as if it were past the row's extent."""
    from distributed_compute_pytorch_tpu.utils.quantize import quantize_kv
    table = cache["table"]
    pool = {n: leaf for n, leaf in cache.items() if n != "table"}
    num_blocks = pool["kv"].shape[1]
    bt = pool["kv"].shape[3]
    nb = table.shape[1]
    t_max = nb * bt
    # clipped gather THEN sentinel: take_along_axis clamps out-of-range
    # lookups, so the horizon test must re-route them explicitly
    blk = jnp.take_along_axis(table, jnp.clip(positions // bt, 0, nb - 1),
                              axis=1)
    blk = jnp.where(positions < t_max, blk, num_blocks)   # dropped below
    off = positions % bt

    def scatter(leaf, upd):
        # upd [2, B, hk, W, x] -> [B, W, 2, hk, x]: advanced indices at
        # axes (1, 3) land broadcast-first (the admission scatter idiom)
        upd = upd.astype(leaf.dtype).transpose(1, 3, 0, 2, 4)
        return leaf.at[:, blk, :, off, :].set(upd, mode="drop")

    if "scale" in pool:
        with scope("kv_write"):
            kq, ks = quantize_kv(k)
            vq, vs = quantize_kv(v)
            pool = {"kv": scatter(pool["kv"], jnp.stack([kq, vq])),
                    "scale": scatter(pool["scale"], jnp.stack([ks, vs]))}
        view = _paged_view(pool, table)
        out = cached_attention_q8(q, view, positions, slot_mask=slot_mask)
    else:
        with scope("kv_write"):
            pool = {"kv": scatter(pool["kv"], jnp.stack([k, v]))}
        view = _paged_view(pool, table)
        out = cached_attention(q, view["k"], view["v"], positions,
                               slot_mask=slot_mask)
    return out, {**pool, "table": table}


def cache_write_and_attend(q, k, v, cache, pos, *, slot_mask=None):
    """One decode tick's cache write + attention, for BOTH cache formats.

    ``pos`` is a scalar (lockstep decode) or an int32 ``[B]`` vector
    (per-row decode — ``serve.ContinuousBatcher``): each row writes its
    K/V at, and attends up to, its OWN slot
    (``ops/pallas/cache_update.py::kv_insert_rows_pallas``).

    ``cache`` holds this layer's K/V STACKED as one array —
    ``{"kv": [2, B, Hk, T_max, hd]}`` (dim 0 = k/v) or the int8 form
    ``{"kv": int8, "scale": f32 [2, B, Hk, T_max, 1]}`` (``--quantize
    …+kv``; new rows quantized per row first,
    ``utils/quantize.py::quantize_kv``). The pair layout is a measured
    r5 decision: the slot write costs one window DMA instead of two
    (insert+attend 0.101 vs 0.303 ms/tick at the 12-layer Llama decode
    shapes — ops/pallas/cache_update.py has the full A/B, including the
    rejected whole-model-stacked deferred variant). Returns
    ``(o, new_cache)``. The shared entry point keeps the block
    families' ``decode_step``s format-agnostic.
    """
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        kv_insert_all)
    if "table" in cache:
        # PAGED pool format ({"kv": [2, P, hk, bt, hd], "table": [B, nb]},
        # serve.ContinuousBatcher): the write resolves through the block
        # table and attention reads the gathered logical view
        return _paged_write_and_attend(q, k, v, cache, pos,
                                       slot_mask=slot_mask)
    if "scale" in cache:
        from distributed_compute_pytorch_tpu.utils.quantize import (
            quantize_kv)
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        cache = kv_insert_all(
            cache, {"kv": jnp.stack([kq, vq]),
                    "scale": jnp.stack([ks, vs])}, pos)
        view = {"k": cache["kv"][0], "v": cache["kv"][1],
                "k_scale": cache["scale"][0], "v_scale": cache["scale"][1]}
        return cached_attention_q8(q, view, pos, slot_mask=slot_mask), cache
    cache = kv_insert_all(cache, {"kv": jnp.stack([k, v])}, pos)
    return cached_attention(q, cache["kv"][0], cache["kv"][1], pos,
                            slot_mask=slot_mask), cache


def ring_positions(pos, ring: int):
    """The logical position each slot of a ring holds once position
    ``pos [B]`` is written at slot ``pos % ring``: ``[B, ring]``, the
    newest position at or before ``pos`` congruent to the slot; negative
    where the row has not reached the slot yet."""
    slots = jnp.arange(ring)[None, :]
    return pos[:, None] - (pos[:, None] - slots) % ring


def ring_write_and_attend(q, k, v, cache, pos, window: int):
    """One decode tick of a WINDOW layer against its ring
    ``{"kv": [2, B, hk, R, hd]}`` (``R >= window`` slots a row, whatever
    the horizon): row ``b`` writes its K/V at slot ``pos[b] % R`` (the
    kv-pair window write of the contiguous cache, in place), then attends
    the whole ring under a position mask: slot ``s`` holds position
    ``ring_positions(pos)[b, s]`` and counts if that is a position of the
    row (``>= 0``) within the window (``> pos - window``). What a slot
    held before (an older position, another request) is overwritten or
    masked, never attended. Returns ``(o [B, H, 1, hd], new_cache)``."""
    from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
        kv_insert_all)
    ring = cache["kv"]
    R = ring.shape[3]
    pos = jnp.broadcast_to(jnp.atleast_1d(pos), (q.shape[0],))
    with scope("kv_write"):
        ring = kv_insert_all({"kv": ring}, {"kv": jnp.stack([k, v])},
                             pos % R)["kv"]
    held = ring_positions(pos, R)
    valid = (held >= 0) & (held > (pos[:, None] - window))
    B, H, q_len, hd = q.shape
    hk = ring.shape[2]
    # GQA reads the narrow ring: the group folds into the query dim
    qg = q.reshape(B, hk, (H // hk) * q_len, hd)
    out = dot_product_attention(qg, ring[0], ring[1],
                                mask=valid[:, None, None, :])
    return out.reshape(B, H, q_len, hd), {"kv": ring}


def ring_from_prefill(k, v, n_tokens, ring: int):
    """The ring rows a prefill leaves behind: ``k``/``v [K, hk, T, hd]``
    hold positions ``0..T-1`` of which the first ``n_tokens [K]`` are real;
    slot ``s`` takes the newest real position congruent to it (the last
    ``min(n, ring)`` tokens), as :func:`ring_positions` will read them.
    Slots the row has not reached hold a clamped copy that the position
    mask hides. Returns ``[2, K, hk, ring, hd]``."""
    src = ring_positions(n_tokens - 1, ring)              # [K, ring]
    idx = jnp.clip(src, 0, k.shape[2] - 1)[:, None, :, None]
    take = lambda a: jnp.take_along_axis(a, idx, axis=2)
    return jnp.stack([take(k), take(v)])
