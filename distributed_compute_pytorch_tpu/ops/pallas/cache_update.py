"""In-place KV-cache slot write — the decode-loop Pallas kernel.

Why this exists (measured on TPU v5 lite, 2026-07-30, decode-tick probe):
``lax.dynamic_update_slice`` on a scan-carried KV cache is NOT lowered
in place by XLA here — every tick copies the whole cache to a fresh
buffer. For the 124M-param Llama decode rung (12 layers x [16, 4, 384,
64] bf16 k+v = 75 MB) that copy costs **0.33 ms/tick**, 44% of the
0.75 ms tick; donation, ``fori_loop`` vs ``scan``, stacked-vs-split
caches and time-minor layouts were all probed and all copy. This kernel
writes ONLY the 8-slot block containing ``pos`` and aliases the cache
buffer through ``input_output_aliases`` — measured **0.074 ms/tick**
for the same 24-cache update pattern, 4.5x less, taking the whole tick
from ~0.79 to ~0.53 ms.

Mechanics: TPU block shapes need the last two dims (sublane x lane)
divisible by (8, 128) or equal to the array dims, so the minimal
writable window on the time axis is 8 slots. The kernel DMAs that
8-slot block in, overwrites row ``pos % 8`` with the update via a
vectorized select (Mosaic rejects dynamic vector stores on that axis),
and DMAs it back — 8 KB of traffic instead of 75 MB. Aliasing keeps
every other block of the cache untouched in the SAME buffer, which XLA
honours through scan carries.

SPMD caveat (same as ``fused_adamw``): a Mosaic call cannot be
partitioned — jax refuses to lower one into a multi-device program
outside ``shard_map``. The dispatchers below therefore take the kernel
only when no mesh context is active (the caches then live whole on one
device, whichever device of the host that is) and the XLA
``dynamic_update_slice``/scatter forms under a mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_WINDOW = 8    # minimal sublane-aligned window on the time axis (f32/bf16)


def _window(dtype) -> int:
    """int8 tiles need 32 sublanes (pallas_guide tiling table); the
    bf16/f32 caches keep the measured 8-slot window."""
    return 32 if dtype == jnp.int8 else _WINDOW


def _insert_kernel(pos_ref, upd_ref, cache_ref, out_ref):
    r = pos_ref[0] % cache_ref.shape[2]
    blk = cache_ref[...]
    slot = lax.broadcasted_iota(jnp.int32, blk.shape, 2)
    out_ref[...] = jnp.where(slot == r, upd_ref[...], blk)


def cache_insert_pallas(cache, upd, pos, *, interpret: bool = False):
    """``cache [B, Hk, T, hd]`` with ``upd [B, Hk, 1, hd]`` written at
    time slot ``pos`` (traced scalar), in place. Requires ``T % 8 == 0``
    (cache lengths here are multiples of 128 anyway). ``interpret``
    runs the kernel in the Pallas interpreter (CPU correctness tests)."""
    b, hk, t, hd = cache.shape
    W = _window(cache.dtype)
    assert t % W == 0, (t, W)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((b, hk, 1, hd), lambda i, pos_ref: (0, 0, 0, 0)),
            pl.BlockSpec((b, hk, W, hd),
                         lambda i, pos_ref, W=W: (0, 0, pos_ref[0] // W, 0)),
        ],
        out_specs=pl.BlockSpec((b, hk, W, hd),
                               lambda i, pos_ref, W=W:
                               (0, 0, pos_ref[0] // W, 0)),
    )
    return pl.pallas_call(
        _insert_kernel,
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        grid_spec=grid_spec,
        # alias the CACHE operand (index counts the scalar-prefetch arg:
        # 0=pos, 1=upd, 2=cache) onto the output: the kernel touches one
        # 8-slot block; every other block stays in place, no copy
        input_output_aliases={2: 0},
        name="dcp_cache_write",
        interpret=interpret,
    )(jnp.atleast_1d(pos).astype(jnp.int32), upd.astype(cache.dtype), cache)


def _pallas_ok(caches: dict, axis: int = 2) -> bool:
    """TPU, no mesh context (so the caches are unsharded — every sharded
    caller traces under ``use_mesh``), and every array's time-axis
    length window-aligned. Decided from where the operands live, never
    from how many chips the host has: an unsharded server on one chip
    of a four-chip host takes the kernel like any other. ``axis``: the
    time axis — 2 for the plain [B, hk, T, w] form, 3 for the kv-pair
    [2, B, hk, T, w] form. ONE policy for every dispatcher."""
    from distributed_compute_pytorch_tpu.core.mesh import current_mesh
    return (jax.default_backend() == "tpu" and current_mesh() is None
            and all(c.shape[axis] % _window(c.dtype) == 0
                    for c in caches.values()))


def cache_insert(cache, upd, pos):
    """Single-array dispatcher (kept for callers outside the decode tick;
    the tick itself uses :func:`kv_insert_all` — one window DMA for a
    layer's whole K/V pair)."""
    if _pallas_ok({"c": cache}):
        return cache_insert_pallas(cache, upd, pos)
    return lax.dynamic_update_slice_in_dim(
        cache, upd.astype(cache.dtype), pos, axis=2)


# ---------------------------------------------------------------------------
# KV-PAIR insert — one window DMA per layer per tick (r5).
#
# Measured on v5e (r5 decomposition + in-situ A/B, 12-layer Llama decode
# shapes, write-then-attend tick):
#   - 24 single-array launches (k and v separately): 0.266 ms/tick;
#   - 12 two-ref launches (k+v fused, two windows):  0.270 ms (no win —
#     the cost is per WINDOW pipeline, not per launch);
#   - per-layer K/V stacked as ONE [2, B, hk, T, hd] array, 12 launches
#     of ONE window each: insert+attend 0.101 ms vs 0.303 for the old
#     per-array form — the win that actually survives in situ;
#   - a whole-model [L, 2, ...] stack with ONE deferred end-of-tick
#     launch measured 0.036 ms in isolation but REGRESSED in situ
#     (llama tick 0.559 -> 0.804): attention must then read the cache
#     BEFORE the write (current K/V inline), and with reads preceding
#     the aliased custom call XLA copies the whole cache — measured-
#     rejected; write-then-attend with per-layer pairs keeps the alias.
# ---------------------------------------------------------------------------


def _pair_kernel(n: int):
    """Kernel for ``n`` kv-pair cache arrays ([2, B, hk, W, w] blocks,
    window axis 3)."""
    def kernel(pos_ref, *refs):
        upds, caches, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
        for u, c, o in zip(upds, caches, outs):
            r = pos_ref[0] % c.shape[3]
            blk = c[...]
            slot = lax.broadcasted_iota(jnp.int32, blk.shape, 3)
            o[...] = jnp.where(slot == r, u[...], blk)
    return kernel


def kv_insert_pallas(cache: dict, upd: dict, pos, *,
                     interpret: bool = False) -> dict:
    """One-launch slot write for one layer's kv-pair cache.

    ``cache``: ``{"kv": [2, B, hk, T, hd]}`` (dim 0 = k/v) or the int8
    form ``{"kv": int8, "scale": f32 [2, B, hk, T, 1]}`` — mixed dtypes
    each keep their own window (8 sublanes bf16/f32, 32 int8).
    ``upd``: same trees with ``T == 1``."""
    names = sorted(cache)
    n = len(names)
    in_specs = [None] * (2 * n)
    out_specs, out_shapes, aliases = [], [], {}
    for i, name in enumerate(names):
        c = cache[name]
        s, b, hk, t, w = c.shape
        W = _window(c.dtype)
        assert t % W == 0, (name, t, W)
        in_specs[i] = pl.BlockSpec(
            (s, b, hk, 1, w), lambda g, pos_ref: (0, 0, 0, 0, 0))
        in_specs[n + i] = pl.BlockSpec(
            (s, b, hk, W, w),
            lambda g, pos_ref, W=W: (0, 0, 0, pos_ref[0] // W, 0))
        out_specs.append(pl.BlockSpec(
            (s, b, hk, W, w),
            lambda g, pos_ref, W=W: (0, 0, 0, pos_ref[0] // W, 0)))
        out_shapes.append(jax.ShapeDtypeStruct(c.shape, c.dtype))
        aliases[1 + n + i] = i
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(1,),
        in_specs=in_specs, out_specs=out_specs)
    outs = pl.pallas_call(
        _pair_kernel(n),
        out_shape=out_shapes,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        name="dcp_kv_write",
        interpret=interpret,
    )(jnp.atleast_1d(pos).astype(jnp.int32),
      *[upd[k].astype(cache[k].dtype) for k in names],
      *[cache[k] for k in names])
    return dict(zip(names, outs))


def kv_insert_all(cache: dict, upd: dict, pos) -> dict:
    """Dispatcher for one layer's kv-pair write.

    ``pos`` is either a scalar (lockstep decode: every row writes the
    same slot — ``infer.py``) or a ``[B]`` int32 vector (per-row decode:
    each row writes its OWN slot — ``serve.ContinuousBatcher``). Both
    forms use a one-window-per-row Pallas kernel on an unsharded TPU
    cache and per-array ``dynamic_update_slice`` (scalar) / a masked
    select (vector) elsewhere (CPU tests; sharded generation, where a
    Mosaic call cannot be partitioned)."""
    if jnp.ndim(pos) == 0:
        if _pallas_ok(cache, axis=3):
            return kv_insert_pallas(cache, upd, pos)
        return {k: lax.dynamic_update_slice_in_dim(
            cache[k], upd[k].astype(cache[k].dtype), pos, axis=3)
            for k in cache}
    if _pallas_ok(cache, axis=3):
        return kv_insert_rows_pallas(cache, upd, pos)
    return {k: _rowwise_select(cache[k], upd[k], pos) for k in cache}


def _rowwise_select(cache, upd, pos):
    """Vector-position fallback: ``cache [s, B, hk, T, w]`` takes
    ``upd [s, B, hk, 1, w]`` at per-row slot ``pos [B]``. A full-array
    select — same cost class as the scalar path's DUS fallback (XLA
    copies the cache either way off the Pallas path)."""
    hit = jnp.arange(cache.shape[3])[None, :] == pos[:, None]   # [B, T]
    return jnp.where(hit[None, :, None, :, None],
                     upd.astype(cache.dtype), cache)


# ---------------------------------------------------------------------------
# PAGED-POOL insert — the block-table serving cache (serve.ContinuousBatcher
# with the paged KV pool). The cache is a pool [2, P, hk, bt, hd] of
# fixed-size blocks; row b's write lands at PHYSICAL (block[b], offset[b])
# resolved by the host/table instead of at batch row b. Same one-window-DMA
# discipline as the per-row kernel: the grid runs one step per decode row,
# scalar-prefetched (block, offset) pairs pick the pool block and the
# W-slot window inside it.
# ---------------------------------------------------------------------------


def _pool_rows_kernel(n: int):
    """Per-decode-row pool write: grid step ``g`` owns update row ``g``
    and writes it into pool block ``blk[g]`` at slot ``off[g]``
    ([2, 1, hk, W, w] window blocks, window axis 3). Distinct decode
    rows always target distinct pool blocks (a row's tail block is
    exclusively owned — serve's copy-on-write invariant) EXCEPT the
    shared trash block parked rows write garbage into; TPU grid steps
    run sequentially on the core, so overlapping trash writes are
    merely garbage, never a data race."""
    def kernel(blk_ref, off_ref, *refs):
        del blk_ref                    # consumed by the index maps
        g = pl.program_id(0)
        upds, caches, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
        for u, c, o in zip(upds, caches, outs):
            r = off_ref[g] % c.shape[3]
            blk = c[...]
            slot = lax.broadcasted_iota(jnp.int32, blk.shape, 3)
            o[...] = jnp.where(slot == r, u[...], blk)
    return kernel


def kv_pool_insert_rows_pallas(cache: dict, upd: dict, blocks, offsets, *,
                               interpret: bool = False) -> dict:
    """Per-row slot write into a PAGED block pool.

    ``cache``: ``{"kv": [2, P, hk, bt, hd]}`` (or the int8
    ``{"kv", "scale"}`` form) — ``P`` physical blocks of ``bt`` slots.
    ``upd``: same trees with the pool axis replaced by the decode batch
    ``B`` and ``bt == 1``. ``blocks``/``offsets``: int32 ``[B]`` — row
    ``b``'s K/V lands at ``cache[:, blocks[b], :, offsets[b], :]``.
    ``bt`` must be a multiple of the dtype's window (8 bf16/f32, 32
    int8). All block ids must be in range (serve points parked rows at
    the reserved trash block, never out of bounds)."""
    names = sorted(cache)
    n = len(names)
    B = upd[names[0]].shape[1]
    in_specs = [None] * (2 * n)
    out_specs, out_shapes, aliases = [], [], {}
    for i, name in enumerate(names):
        c = cache[name]
        s, p, hk, bt, w = c.shape
        W = _window(c.dtype)
        assert bt % W == 0, (name, bt, W)
        in_specs[i] = pl.BlockSpec(
            (s, 1, hk, 1, w), lambda g, blk_ref, off_ref: (0, g, 0, 0, 0))
        in_specs[n + i] = pl.BlockSpec(
            (s, 1, hk, W, w),
            lambda g, blk_ref, off_ref, W=W:
            (0, blk_ref[g], 0, off_ref[g] // W, 0))
        out_specs.append(pl.BlockSpec(
            (s, 1, hk, W, w),
            lambda g, blk_ref, off_ref, W=W:
            (0, blk_ref[g], 0, off_ref[g] // W, 0)))
        out_shapes.append(jax.ShapeDtypeStruct(c.shape, c.dtype))
        aliases[2 + n + i] = i         # 2 scalar-prefetch args lead
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=in_specs, out_specs=out_specs)
    outs = pl.pallas_call(
        _pool_rows_kernel(n),
        out_shape=out_shapes,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        name="dcp_kv_pool_write",
        interpret=interpret,
    )(blocks.astype(jnp.int32), offsets.astype(jnp.int32),
      *[upd[k].astype(cache[k].dtype) for k in names],
      *[cache[k] for k in names])
    return dict(zip(names, outs))


def _pool_scatter(cache, upd, blocks, offsets):
    """XLA fallback for the pool write: one scatter at the per-row
    (block, offset) pairs. ``mode="drop"`` discards out-of-range block
    ids, which the serve layer uses for admission pad rows."""
    # advanced indices at axes (1, 3) land broadcast-first: the target
    # region is [B, s, hk, w]
    u = jnp.moveaxis(upd[:, :, :, 0, :], 1, 0).astype(cache.dtype)
    return cache.at[:, blocks, :, offsets, :].set(u, mode="drop")


def kv_pool_insert_all(cache: dict, upd: dict, blocks, offsets) -> dict:
    """Dispatcher for the paged pool write: the per-row Pallas kernel on
    an unsharded TPU pool (one window DMA per decode row), an XLA
    scatter elsewhere (CPU tests; sharded pools, where a Mosaic call
    cannot be partitioned)."""
    if _pallas_ok(cache, axis=3):
        return kv_pool_insert_rows_pallas(cache, upd, blocks, offsets)
    return {k: _pool_scatter(cache[k], upd[k], blocks, offsets)
            for k in cache}


# ---------------------------------------------------------------------------
# SPAN insert: ``L`` consecutive slots a row in one write (a block of a
# block-diffusion model: serve.py's block pass). ``L`` divides the window, so
# a row's span lies in ONE window of one pool block and the write is the
# per-row kernel's: one window in, the span's rows replaced, one window out.
# ---------------------------------------------------------------------------


def _pool_span_kernel(n: int, L: int):
    """As :func:`_pool_rows_kernel`, for a span: update row ``g`` is a whole
    WINDOW whose slot ``j`` holds the span's token ``j % L`` (the caller
    tiles it: no sub-tile vector is cut inside the kernel), of which slots
    ``off[g] % W .. + L - 1`` are taken."""
    def kernel(blk_ref, off_ref, *refs):
        del blk_ref                    # consumed by the index maps
        g = pl.program_id(0)
        upds, caches, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
        for u, c, o in zip(upds, caches, outs):
            r = off_ref[g] % c.shape[3]
            blk = c[...]
            slot = lax.broadcasted_iota(jnp.int32, blk.shape, 3)
            o[...] = jnp.where((slot >= r) & (slot < r + L), u[...], blk)
    return kernel


def kv_pool_insert_span_pallas(cache: dict, upd: dict, blocks, offsets, *,
                               interpret: bool = False) -> dict:
    """``L`` consecutive slots a row into a PAGED block pool: ``upd``
    leaves ``[s, B, hk, L, w]`` land at ``cache[:, blocks[b], :, offsets[b]
    .. offsets[b] + L - 1, :]``. ``L`` divides the dtype's window and
    ``offsets`` are multiples of ``L``. Everything else as
    :func:`kv_pool_insert_rows_pallas`."""
    names = sorted(cache)
    n = len(names)
    B, L = upd[names[0]].shape[1], upd[names[0]].shape[3]
    in_specs = [None] * (2 * n)
    out_specs, out_shapes, aliases, tiled = [], [], {}, []
    for i, name in enumerate(names):
        c = cache[name]
        s, p, hk, bt, w = c.shape
        W = _window(c.dtype)
        assert bt % W == 0 and W % L == 0, (name, bt, W, L)
        tiled.append(jnp.tile(upd[name].astype(c.dtype),
                              (1, 1, 1, W // L, 1)))
        in_specs[i] = pl.BlockSpec(
            (s, 1, hk, W, w), lambda g, blk_ref, off_ref: (0, g, 0, 0, 0))
        window = pl.BlockSpec(
            (s, 1, hk, W, w),
            lambda g, blk_ref, off_ref, W=W:
            (0, blk_ref[g], 0, off_ref[g] // W, 0))
        in_specs[n + i] = window
        out_specs.append(window)
        out_shapes.append(jax.ShapeDtypeStruct(c.shape, c.dtype))
        aliases[2 + n + i] = i         # 2 scalar-prefetch args lead
    outs = pl.pallas_call(
        _pool_span_kernel(n, L),
        out_shape=out_shapes,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=in_specs, out_specs=out_specs),
        input_output_aliases=aliases,
        name="dcp_kv_pool_write_span",
        interpret=interpret,
    )(blocks.astype(jnp.int32), offsets.astype(jnp.int32), *tiled,
      *[cache[k] for k in names])
    return dict(zip(names, outs))


def kv_pool_insert_span_all(cache: dict, upd: dict, blocks, offsets) -> dict:
    """Dispatcher for the span write: the Pallas kernel on an unsharded TPU
    pool, an XLA scatter of the span's ``L`` slots elsewhere."""
    if _pallas_ok(cache, axis=3):
        return kv_pool_insert_span_pallas(cache, upd, blocks, offsets)
    L = next(iter(upd.values())).shape[3]
    at = offsets[:, None] + jnp.arange(L)[None, :]             # [B, L]
    # advanced indices at axes (1, 3) land broadcast-first: [B, L, s, hk, w]
    return {k: cache[k].at[:, blocks[:, None], :, at, :].set(
        upd[k].transpose(1, 3, 0, 2, 4).astype(cache[k].dtype), mode="drop")
        for k in cache}


def _pair_rows_kernel(n: int):
    """Per-row variant of :func:`_pair_kernel`: grid step ``b`` owns
    batch row ``b``'s window block ([2, 1, hk, W, w], window axis 3) at
    that row's own position."""
    def kernel(pos_ref, *refs):
        b = pl.program_id(0)
        upds, caches, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
        for u, c, o in zip(upds, caches, outs):
            r = pos_ref[b] % c.shape[3]
            blk = c[...]
            slot = lax.broadcasted_iota(jnp.int32, blk.shape, 3)
            o[...] = jnp.where(slot == r, u[...], blk)
    return kernel


def kv_insert_rows_pallas(cache: dict, upd: dict, pos, *,
                          interpret: bool = False) -> dict:
    """Per-row slot write for one layer's kv-pair cache: row ``b`` takes
    its update at ITS OWN slot ``pos[b]`` — the kernel that frees the
    serving loop from the lockstep-horizon invariant.

    Same trees as :func:`kv_insert_pallas` (``{"kv": [2, B, hk, T, hd]}``
    or the int8 ``{"kv", "scale"}`` form), ``pos`` an int32 ``[B]``
    vector. The grid runs one step per batch row; each step DMAs only
    that row's W-slot window (scalar-prefetched ``pos[b]`` picks the
    block), overwrites slot ``pos[b] % W`` and DMAs it back — the same
    total window traffic as the lockstep kernel, split into per-row
    blocks, with every untouched block aliased in place."""
    names = sorted(cache)
    n = len(names)
    B = cache[names[0]].shape[1]
    in_specs = [None] * (2 * n)
    out_specs, out_shapes, aliases = [], [], {}
    for i, name in enumerate(names):
        c = cache[name]
        s, b, hk, t, w = c.shape
        W = _window(c.dtype)
        assert t % W == 0, (name, t, W)
        in_specs[i] = pl.BlockSpec(
            (s, 1, hk, 1, w), lambda g, pos_ref: (0, g, 0, 0, 0))
        in_specs[n + i] = pl.BlockSpec(
            (s, 1, hk, W, w),
            lambda g, pos_ref, W=W: (0, g, 0, pos_ref[g] // W, 0))
        out_specs.append(pl.BlockSpec(
            (s, 1, hk, W, w),
            lambda g, pos_ref, W=W: (0, g, 0, pos_ref[g] // W, 0)))
        out_shapes.append(jax.ShapeDtypeStruct(c.shape, c.dtype))
        aliases[1 + n + i] = i
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(B,),
        in_specs=in_specs, out_specs=out_specs)
    outs = pl.pallas_call(
        _pair_rows_kernel(n),
        out_shape=out_shapes,
        grid_spec=grid_spec,
        input_output_aliases=aliases,
        name="dcp_kv_rows_write",
        interpret=interpret,
    )(pos.astype(jnp.int32),
      *[upd[k].astype(cache[k].dtype) for k in names],
      *[cache[k] for k in names])
    return dict(zip(names, outs))
