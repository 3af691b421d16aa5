"""Flash-decode: single-position cached attention as Pallas kernels.

Three kernels live here. ONE is on the serving path:

- :func:`paged_decode_attention_pallas` (``dcp_paged_decode_attn``, at
  the end of the file): the decode tick's read of the PAGED pool
  through the block table, for heads of whole 128-lane tiles. It is
  what ``ops/attention.py::_paged_write_and_attend`` calls for every
  eligible pool (``paged_read_path``), in place of a per-tick gathered
  copy of every row's K and V.

The other two are REFERENCES, measured and rejected as the default
decode path for the CONTIGUOUS cache at hd=64, kept correct and
test-covered (``tests/test_decode_attention.py``, chip only):

- :func:`decode_attention_pallas`: the dense ``[B, Hk, T, hd]`` cache;
- :func:`decode_attention_paged_pallas`: the same mathematics over a
  block pool, ONE pool block per DMA step: the recipe for the table
  lookup inside the stream, latency-bound at small blocks.

What the references were built for (measured v5e, 2026-07-30,
GPT-2-small decode shapes): XLA's dense
masked attention streams the KV cache at ~45% of HBM bandwidth when the
query is a single row (12 MHA layers x [16, 12, 384, 64] bf16 read in
0.611 ms vs the 0.28 ms full-bandwidth floor), and it always reads the
FULL ``t_max`` window even though only slots ``0..pos`` are valid (67%
on the bench's average tick). The kernels fix both:

- **Explicit DMA streaming**: K/V stay in HBM (``memory_space=ANY``);
  the kernel double-buffers block-sized chunks into VMEM scratch with
  ``make_async_copy``, so the stream runs at DMA bandwidth regardless
  of the 1-row query shape that starves XLA's tiling.
- **Dynamic length**: the block loop bound is ``pos // block_k + 1`` —
  a traced scalar (scalar-prefetched), so slots beyond ``pos`` are
  never fetched at all. XLA cannot express this with static shapes.
- **Online softmax** (the flash recipe) in f32.

**The packed-lane trick** (the two hd=64 references only): Mosaic only
slices VMEM memrefs at 128-lane
granularity, and ``head_dim`` is 64 — so the caches are viewed (free,
contiguous reshape) as ``[B, Hk, T/2, 128]``: each row packs slot pair
``(2i, 2i+1)``. Scores come from two matmuls with half-zero queries
(``[q|0]`` hits the even slots, ``[0|q]`` the odd), and the packed V
block multiplies against the interleaved probability row — producing
``[sum p*v_even | sum p*v_odd]`` in the two lane halves, which one
final 128-lane dot against ``[I|I]`` folds back to 64. Everything is
MXU-shaped; no lane-slicing anywhere.

**Status of the two hd=64 kernels: measured and rejected for the
CONTIGUOUS cache** (kept as
reference + test-covered for future hardware/compiler revisions).
Correct to bf16 round-off, but on v5e the 12-layer GPT-2-shaped read
loop measures 1.73 ms/tick vs 0.45-0.60 for XLA's dense path. Why: the
per-(batch, head) work is a 1-row GEMV against that pair's private K/V
— there is nothing to batch into the MXU's 8-sublane minimum, so the
per-head compute (not the DMA stream) dominates; a per-(b,h) grid was
6.5x slower still (192 serial DMA latencies). The dynamic-length DMA
saving (~33% of bytes on the bench's average tick) cannot pay for
~8x-underutilised compute tiles. Lesson recorded: XLA's fused masked
attention is already within ~2x of the bandwidth floor for decode over
a contiguous cache. That record never timed the PAGED read, whose XLA
form first gathers a dense copy of every row's view: there the
comparison is a kernel against the gather, and the kernel wins
(PERF.md section 6, PR 25).

Scope of the references: ``slot_mask`` unsupported; even ``T``;
``hd == 64``. Numerics:
f32 scores/accumulator like the dense path; parity pinned in
``tests/test_decode_attention.py`` (references) and
``tests/test_paged_decode_attention.py`` (the serving kernel).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(pos_ref, q_ref, k_hbm, v_hbm, out_ref, *, block_pairs: int,
            scale: float, num_heads: int):
    b = pl.program_id(0)
    # clamp: ``pos`` is traced, so a caller off-by-one (pos == T) must
    # degrade like the dense path's mask instead of DMA-reading past the
    # cache buffer. pos_ref is per-row [B]: grid step b streams only up
    # to ITS row's valid length (scalar pos broadcasts in the wrapper).
    total_pairs = k_hbm.shape[2]
    pos = jnp.minimum(pos_ref[b], total_pairs * 2 - 1)
    # pairs-per-block loop bound: block covering slot ``pos`` included
    nb = (pos // 2) // block_pairs + 1
    G = q_ref.shape[2]
    hd = q_ref.shape[3]
    zeros = jnp.zeros((G, hd), jnp.float32)
    q_all = q_ref[0].astype(jnp.float32) * scale           # [Hk, G, hd]
    q_even = [jnp.concatenate([q_all[h], zeros], axis=1)
              for h in range(num_heads)]                   # each [G, 2hd]
    q_odd = [jnp.concatenate([zeros, q_all[h]], axis=1)
             for h in range(num_heads)]
    # lane-fold matrix [2hd, hd]: [I | I]^T — collapses the two packed
    # halves of the accumulated PV row back to head_dim lanes
    eye = jnp.eye(hd, dtype=jnp.float32)
    fold = jnp.concatenate([eye, eye], axis=0)             # [2hd, hd]

    def body(scratch_k, scratch_v, sem_k, sem_v):
        # ONE DMA per (pair-block, k/v) covers every head: [Hk, BP, 2hd]
        # chunks are ~190 KB, big enough to hit DMA bandwidth; the
        # per-head compute below runs while the next chunk streams
        def dma(slot, kb, which):
            hbm, scr, sem = ((k_hbm, scratch_k, sem_k) if which == 0
                             else (v_hbm, scratch_v, sem_v))
            return pltpu.make_async_copy(
                hbm.at[b, :, pl.ds(kb * block_pairs, block_pairs), :],
                scr.at[slot], sem.at[slot])

        dma(0, 0, 0).start()
        dma(0, 0, 1).start()

        def block_step(kb, carry):
            ms, ls, accs = carry       # each [Hk, G, 1] / [Hk, G, 2hd]
            slot = kb % 2
            nxt = (kb + 1) % 2

            @pl.when(kb + 1 < nb)
            def _():
                dma(nxt, kb + 1, 0).start()
                dma(nxt, kb + 1, 1).start()

            dma(slot, kb, 0).wait()
            dma(slot, kb, 1).wait()

            base = kb * block_pairs * 2
            new_m, new_l, new_acc = [], [], []
            for h in range(num_heads):
                kp = scratch_k[slot][h].astype(jnp.float32)  # [BP, 2hd]
                vp = scratch_v[slot][h].astype(jnp.float32)
                s_even = jax.lax.dot_general(                # [G, BP]
                    q_even[h], kp, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s_odd = jax.lax.dot_general(
                    q_odd[h], kp, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ids = base + 2 * lax.broadcasted_iota(jnp.int32,
                                                      s_even.shape, 1)
                s_even = jnp.where(ids <= pos, s_even, -1e30)
                s_odd = jnp.where(ids + 1 <= pos, s_odd, -1e30)

                m, l, acc = ms[h], ls[h], accs[h]
                blk_max = jnp.maximum(
                    jnp.max(s_even, axis=1, keepdims=True),
                    jnp.max(s_odd, axis=1, keepdims=True))
                m_new = jnp.maximum(m, blk_max)              # [G, 1]
                alpha = jnp.exp(m - m_new)
                p_even = jnp.exp(s_even - m_new)             # [G, BP]
                p_odd = jnp.exp(s_odd - m_new)
                l_new = (l * alpha
                         + jnp.sum(p_even, axis=1, keepdims=True)
                         + jnp.sum(p_odd, axis=1, keepdims=True))
                # vp rows pack [v_{2i} | v_{2i+1}]: p_even @ vp holds the
                # wanted sum in its LEFT lane half, p_odd @ vp in its
                # RIGHT; merge halves with a lane select
                pv_e = jax.lax.dot_general(
                    p_even, vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [G, 2hd]
                pv_o = jax.lax.dot_general(
                    p_odd, vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                lane = lax.broadcasted_iota(jnp.int32, pv_e.shape, 1)
                contrib = jnp.where(lane < hd, pv_e, pv_o)
                new_m.append(m_new)
                new_l.append(l_new)
                new_acc.append(acc * alpha + contrib)
            return (tuple(new_m), tuple(new_l), tuple(new_acc))

        m0 = tuple(jnp.full((G, 1), -jnp.inf, jnp.float32)
                   for _ in range(num_heads))
        l0 = tuple(jnp.zeros((G, 1), jnp.float32)
                   for _ in range(num_heads))
        acc0 = tuple(jnp.zeros((G, 2 * hd), jnp.float32)
                     for _ in range(num_heads))
        _, ls, accs = lax.fori_loop(0, nb, block_step, (m0, l0, acc0))
        for h in range(num_heads):
            out = jax.lax.dot_general(accs[h] / ls[h], fold,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            out_ref[0, h] = out.astype(out_ref.dtype)

    pl.run_scoped(
        body,
        scratch_k=pltpu.VMEM((2, num_heads, block_pairs, 2 * hd),
                             k_hbm.dtype),
        scratch_v=pltpu.VMEM((2, num_heads, block_pairs, 2 * hd),
                             v_hbm.dtype),
        sem_k=pltpu.SemaphoreType.DMA((2,)),
        sem_v=pltpu.SemaphoreType.DMA((2,)),
    )


def _paged_kernel(pos_ref, tbl_ref, q_ref, k_hbm, v_hbm, out_ref, *,
                  block_pairs: int, scale: float, num_heads: int,
                  nb: int):
    """Block-table variant of :func:`_kernel`: the caches are a POOL of
    fixed-size blocks ``[P, Hk, bt/2, 2hd]`` (packed-lane pair view) and
    row ``b``'s logical block ``j`` streams from physical block
    ``tbl_ref[b * nb + j]`` — the paged-attention read, where the
    per-row DMA source is a table lookup instead of a contiguous slice.
    One pool block == one DMA chunk, so the dynamic length bound
    (``pos[b] // bt + 1`` blocks) never fetches past a row's live
    prefix. Same online-softmax/packed-lane math as the dense kernel."""
    b = pl.program_id(0)
    total_pairs = block_pairs * nb
    pos = jnp.minimum(pos_ref[b], total_pairs * 2 - 1)
    nblk = (pos // 2) // block_pairs + 1
    G = q_ref.shape[2]
    hd = q_ref.shape[3]
    zeros = jnp.zeros((G, hd), jnp.float32)
    q_all = q_ref[0].astype(jnp.float32) * scale
    q_even = [jnp.concatenate([q_all[h], zeros], axis=1)
              for h in range(num_heads)]
    q_odd = [jnp.concatenate([zeros, q_all[h]], axis=1)
             for h in range(num_heads)]
    eye = jnp.eye(hd, dtype=jnp.float32)
    fold = jnp.concatenate([eye, eye], axis=0)

    def body(scratch_k, scratch_v, sem_k, sem_v):
        def dma(slot, kb, which):
            hbm, scr, sem = ((k_hbm, scratch_k, sem_k) if which == 0
                             else (v_hbm, scratch_v, sem_v))
            phys = tbl_ref[b * nb + kb]        # the table lookup
            return pltpu.make_async_copy(
                hbm.at[phys], scr.at[slot], sem.at[slot])

        dma(0, 0, 0).start()
        dma(0, 0, 1).start()

        def block_step(kb, carry):
            ms, ls, accs = carry
            slot = kb % 2
            nxt = (kb + 1) % 2

            @pl.when(kb + 1 < nblk)
            def _():
                dma(nxt, kb + 1, 0).start()
                dma(nxt, kb + 1, 1).start()

            dma(slot, kb, 0).wait()
            dma(slot, kb, 1).wait()

            base = kb * block_pairs * 2
            new_m, new_l, new_acc = [], [], []
            for h in range(num_heads):
                kp = scratch_k[slot][h].astype(jnp.float32)
                vp = scratch_v[slot][h].astype(jnp.float32)
                s_even = jax.lax.dot_general(
                    q_even[h], kp, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s_odd = jax.lax.dot_general(
                    q_odd[h], kp, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ids = base + 2 * lax.broadcasted_iota(jnp.int32,
                                                      s_even.shape, 1)
                s_even = jnp.where(ids <= pos, s_even, -1e30)
                s_odd = jnp.where(ids + 1 <= pos, s_odd, -1e30)

                m, l, acc = ms[h], ls[h], accs[h]
                blk_max = jnp.maximum(
                    jnp.max(s_even, axis=1, keepdims=True),
                    jnp.max(s_odd, axis=1, keepdims=True))
                m_new = jnp.maximum(m, blk_max)
                alpha = jnp.exp(m - m_new)
                p_even = jnp.exp(s_even - m_new)
                p_odd = jnp.exp(s_odd - m_new)
                l_new = (l * alpha
                         + jnp.sum(p_even, axis=1, keepdims=True)
                         + jnp.sum(p_odd, axis=1, keepdims=True))
                pv_e = jax.lax.dot_general(
                    p_even, vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                pv_o = jax.lax.dot_general(
                    p_odd, vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                lane = lax.broadcasted_iota(jnp.int32, pv_e.shape, 1)
                contrib = jnp.where(lane < hd, pv_e, pv_o)
                new_m.append(m_new)
                new_l.append(l_new)
                new_acc.append(acc * alpha + contrib)
            return (tuple(new_m), tuple(new_l), tuple(new_acc))

        m0 = tuple(jnp.full((G, 1), -jnp.inf, jnp.float32)
                   for _ in range(num_heads))
        l0 = tuple(jnp.zeros((G, 1), jnp.float32)
                   for _ in range(num_heads))
        acc0 = tuple(jnp.zeros((G, 2 * hd), jnp.float32)
                     for _ in range(num_heads))
        _, ls, accs = lax.fori_loop(0, nblk, block_step, (m0, l0, acc0))
        for h in range(num_heads):
            out = jax.lax.dot_general(accs[h] / ls[h], fold,
                                      (((1,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            out_ref[0, h] = out.astype(out_ref.dtype)

    pl.run_scoped(
        body,
        scratch_k=pltpu.VMEM((2, num_heads, block_pairs, 2 * hd),
                             k_hbm.dtype),
        scratch_v=pltpu.VMEM((2, num_heads, block_pairs, 2 * hd),
                             v_hbm.dtype),
        sem_k=pltpu.SemaphoreType.DMA((2,)),
        sem_v=pltpu.SemaphoreType.DMA((2,)),
    )


def decode_attention_paged_pallas(q, k_pool, v_pool, tables, pos, *,
                                  scale: float | None = None):
    """Paged flash-decode: ``q [B, Hk, G, hd]`` against a BLOCK POOL
    ``k_pool/v_pool [P, Hk, bt, hd]`` addressed through ``tables
    [B, nb]`` (row ``b``'s logical slot ``t`` lives in pool block
    ``tables[b, t // bt]`` at offset ``t % bt``); attends logical slots
    ``0..pos[b]``. The pool block is the DMA unit, so the stream
    touches exactly the blocks a row's live prefix occupies — the
    block-table analogue of the dense kernel's dynamic length bound.

    Reference status, like the dense kernel above (measured-rejected as
    the default decode path on v5e): the per-(batch,head) GEMV shape
    underuses the MXU regardless of how K/V are addressed; kept
    correct + covered for future hardware/compiler revisions, and as
    the recipe for fusing the table lookup into the stream. ``hd`` must
    be 64 and ``bt`` even (the packed-lane layout)."""
    B, Hk, G, hd = q.shape
    P, _, bt, _ = k_pool.shape
    nb = tables.shape[1]
    assert hd == 64, hd
    assert bt % 2 == 0, bt
    scale = (hd ** -0.5) if scale is None else scale
    block_pairs = bt // 2
    kp = k_pool.reshape(P, Hk, bt // 2, 2 * hd)
    vp = v_pool.reshape(P, Hk, bt // 2, 2 * hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hk, G, hd), lambda b, p, t: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hk, G, hd), lambda b, p, t: (b, 0, 0, 0)),
    )
    pos = jnp.broadcast_to(jnp.atleast_1d(pos).astype(jnp.int32), (B,))
    return pl.pallas_call(
        functools.partial(_paged_kernel, block_pairs=block_pairs,
                          scale=scale, num_heads=Hk, nb=nb),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
    )(pos, tables.reshape(-1).astype(jnp.int32), q, kp, vp)


def decode_attention_pallas(q, k_cache, v_cache, pos, *,
                            scale: float | None = None,
                            block_k: int = 128):
    """``q [B, Hk, G, hd]`` (grouped query rows), caches
    ``[B, Hk, T, hd]``; attends slots ``0..pos``. ``pos`` is a scalar
    (every row at the same position) or an int32 ``[B]`` vector (per-row
    valid lengths — the serving loop's per-row decode positions); each
    grid step streams only its row's ``pos[b] // block_k + 1`` blocks.
    Returns ``[B, Hk, G, hd]`` in q's dtype. ``hd`` must be 64 (the
    packed-lane layout; the framework's decode models all use 64) and
    ``T`` must be divisible by ``block_k`` (cache lengths are multiples
    of 128)."""
    B, Hk, G, hd = q.shape
    T = k_cache.shape[2]
    assert hd == 64, hd
    assert T % block_k == 0 and block_k % 2 == 0, (T, block_k)
    scale = (hd ** -0.5) if scale is None else scale
    block_pairs = block_k // 2
    kp = k_cache.reshape(B, Hk, T // 2, 2 * hd)
    vp = v_cache.reshape(B, Hk, T // 2, 2 * hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hk, G, hd), lambda b, p: (b, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, Hk, G, hd), lambda b, p: (b, 0, 0, 0)),
    )
    pos = jnp.broadcast_to(jnp.atleast_1d(pos).astype(jnp.int32), (B,))
    return pl.pallas_call(
        functools.partial(_kernel, block_pairs=block_pairs, scale=scale,
                          num_heads=Hk),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid_spec=grid_spec,
    )(pos, q, kp, vp)


# ---------------------------------------------------------------------------
# PAGED DECODE READ through the block table — the serving path's kernel
# (``ops/attention.py::_paged_write_and_attend`` for eligible pools).
#
# The portable paged read materialises, per layer per tick, a dense
# ``[B, hk, nb_w * bt, hd]`` copy of every row's K and V at the width
# rung of the LONGEST live row (``gather_kv_blocks``: a gather, a
# transpose XLA makes a real copy, a K/V split that is a third) and only
# then attends: 60% of the Mistral-7B decode tick on the v5e (PERF.md
# section 5, PR 24). This kernel reads the pool IN PLACE: its traffic is
# each row's live blocks, once.
# ---------------------------------------------------------------------------

# A DMA chunk, in tokens (divided by the pool's block size: C blocks), is
# copied into one of two VMEM buffers (one async copy a block, K and V
# planes together: 32 KB at Mistral's shape) while the other is attended.
# The two buffers take at most ``_SCRATCH_BYTES`` of VMEM, a quarter of
# the 16 MiB a v5e kernel may use without asking for more: exactly the
# 512-token chunk at Mistral's shape (8 KV heads of 128, bf16), 128
# tokens at Llama-2-7B's 32 KV heads or for an f32 pool of 8 x 256.
# Measured on the v5e at the steady cell's shape
# (PERF.md, PR 25): at bt = 8 ONE block in flight is latency-bound (the
# hd=64 reference above); 512-token chunks beat 256 by 13-26%; issuing
# copies four to a loop step and waiting for a chunk in at most log2(C)
# waits another 18%; attending a chunk in steps of 128 live tokens
# instead of whole was 20-50% slower.
_CHUNK_TOKENS = 512
_SCRATCH_BYTES = 4 << 20
_ISSUE_GROUP = 4


def _paged_decode_kernel(pos_ref, tbl_ref, q_ref, pool_hbm, out_ref,
                         buf, sem, slot0_ref, *, chunk_blocks: int,
                         nb_w: int):
    """Grid step ``b`` attends row ``b``'s query over logical slots
    ``0 .. pos[b]`` of ``pool_hbm [2, P, hk, bt, hd]`` (left in HBM)
    through ``tbl_ref`` (the row-major ``[B, nb_w]`` table). The row's
    live blocks stream in chunks of ``chunk_blocks``: every live block of
    a chunk is one async copy ``pool[:, table[b, j]] -> buf[slot, j]``
    (``[2, hk, bt, hd]``: both planes), all on one semaphore; the next
    chunk's copies — the next ROW's first chunk after a row's last — are
    started before the current chunk is waited for, so no row pays a
    DMA latency of its own. ``slot0_ref`` (SMEM) carries the buffer
    parity over the grid steps, which therefore run in order
    (``arbitrary``). Blocks past ``pos[b] // bt`` are neither fetched
    nor waited for (copies go out ``_ISSUE_GROUP`` to a loop step, the
    last group filled up with the row's last live block again); buffer
    slots no copy filled hold an earlier chunk's (finite) data, masked
    to probability 0. Online softmax per KV head: f32 scores, running
    max, sum and accumulator; the probabilities meet V in the pool's
    dtype, as in ``cached_attention``."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    _, _, hk, bt, hd = pool_hbm.shape
    C = chunk_blocks
    group = math.gcd(C, _ISSUE_GROUP)
    G = q_ref.shape[2]
    cdt = buf.dtype
    scale = hd ** -0.5

    def live_blocks(row):
        # never past the shipped table (a parked row's position means
        # nothing; its table is all-trash and any block of it will do)
        return jnp.clip(pos_ref[row] // bt + 1, 1, nb_w)

    def issue_steps(row, c):
        return pl.cdiv(jnp.minimum(live_blocks(row) - c * C, C), group)

    def start(row, c, slot):
        live, lo = live_blocks(row), c * C

        def issue(g, carry):
            for u in range(group):
                j = g * group + u
                phys = tbl_ref[row * nb_w + jnp.minimum(lo + j, live - 1)]
                pltpu.make_async_copy(pool_hbm.at[:, phys], buf.at[slot, j],
                                      sem.at[slot]).start()
            return carry
        lax.fori_loop(0, issue_steps(row, c), issue, 0)

    def wait(row, c, slot):
        # a DMA semaphore counts bytes: one wait sized k blocks takes up
        # k copies' worth, so a chunk costs at most log2(C) + 1 waits
        n = issue_steps(row, c) * group
        k = 1 << (C.bit_length() - 1)
        while k >= group:
            @pl.when(n & k != 0)
            def _(k=k):
                part = buf.at[slot, pl.ds(0, k)]
                pltpu.make_async_copy(part, part, sem.at[slot]).wait()
            k //= 2

    @pl.when(b == 0)
    def _():
        # whatever the scratch held (NaN patterns included) must never
        # meet a zero probability: from here on it only holds pool data
        buf[...] = jnp.zeros(buf.shape, cdt)
        slot0_ref[0] = 0
        start(0, 0, 0)

    slot0 = slot0_ref[0]
    pos = pos_ref[b]
    n_chunks = pl.cdiv(live_blocks(b), C)
    q = q_ref[0].astype(cdt)                         # [hk, G, hd]

    def chunk_step(c, carry):
        ms, ls, accs = carry
        slot = (slot0 + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _():
            start(b, c + 1, 1 - slot)

        @pl.when(jnp.logical_and(c + 1 == n_chunks, b + 1 < n_rows))
        def _():
            start(b + 1, 0, 1 - slot)

        wait(b, c, slot)
        ids = c * (C * bt) + lax.broadcasted_iota(jnp.int32, (G, C * bt), 1)
        valid = ids <= pos
        new_m, new_l, new_acc = [], [], []
        for h in range(hk):
            k = buf[slot, :, 0, h].reshape(C * bt, hd)
            v = buf[slot, :, 1, h].reshape(C * bt, hd)
            s = lax.dot_general(q[h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            s = jnp.where(valid, s, -1e30)
            m_new = jnp.maximum(ms[h], jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(ms[h] - m_new)
            p = jnp.exp(s - m_new)
            new_l.append(ls[h] * alpha + jnp.sum(p, axis=1, keepdims=True))
            new_acc.append(accs[h] * alpha + lax.dot_general(
                p.astype(cdt), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            new_m.append(m_new)
        return tuple(new_m), tuple(new_l), tuple(new_acc)

    init = (tuple(jnp.full((G, 1), -jnp.inf, jnp.float32) for _ in range(hk)),
            tuple(jnp.zeros((G, 1), jnp.float32) for _ in range(hk)),
            tuple(jnp.zeros((G, hd), jnp.float32) for _ in range(hk)))
    _, ls, accs = lax.fori_loop(0, n_chunks, chunk_step, init)
    slot0_ref[0] = (slot0 + n_chunks) % 2
    for h in range(hk):
        out_ref[0, h] = (accs[h] / ls[h]).astype(out_ref.dtype)


def _chunk_blocks(pool_shape, itemsize: int, nb_w: int) -> int:
    """Blocks to a DMA chunk: ``_CHUNK_TOKENS`` of them, fewer where the
    table slice is narrower or two chunks (K and V planes of every KV
    head) would pass ``_SCRATCH_BYTES``. 0 where not even one block a
    buffer fits: such a pool is not the kernel's
    (``ops/attention.py::paged_read_path``)."""
    _, _, hk, bt, hd = pool_shape
    block_bytes = 2 * hk * bt * hd * itemsize
    return min(max(1, _CHUNK_TOKENS // bt), nb_w,
               _SCRATCH_BYTES // (2 * block_bytes))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, pool_kv, table, pos, *,
                                  interpret: bool = False):
    """Single-position decode attention over a paged pool, read in place.

    ``q [B, H, 1, hd]`` (this tick's queries); ``pool_kv [2, P, hk, bt,
    hd]`` (dim 0 = k/v: the serving pool leaf as it is, never copied or
    re-laid); ``table`` int32 ``[B, nb_w]`` (row ``b``'s logical slot
    ``t`` lives in pool block ``table[b, t // bt]`` at offset ``t %
    bt``; a width-rung slice is fine, the cost follows ``pos``); ``pos``
    int32 ``[B]`` (row ``b`` attends slots ``0 .. pos[b]``, its own
    just-written one included). Returns ``[B, H, 1, hd]`` in ``q``'s
    dtype: the same mathematics as ``cached_attention`` over
    ``gather_kv_blocks`` (GQA: query head ``h`` reads KV head
    ``h // (H // hk)``). Needs ``hd % 128 == 0`` (one lane tile per
    head; no packed-lane trick), a float pool, and a block of all KV
    heads that fits the scratch twice (``_chunk_blocks``).

    Jitted on its own so that a program calling it once a layer traces
    the kernel and lowers it to Mosaic ONCE (an inner jit is one function
    of the module, called per layer) instead of once a call: a process
    pays that at every start, compile cache or not (16 layers x 10 width
    rungs cost the steady cell 65 s of set-up before this)."""
    B, H, q_len, hd = q.shape
    _, _, hk, bt, _ = pool_kv.shape
    assert q_len == 1 and hd % 128 == 0 and H % hk == 0, (q.shape, hk)
    nb_w = table.shape[1]
    G = H // hk
    C = _chunk_blocks(pool_kv.shape, pool_kv.dtype.itemsize, nb_w)
    assert C >= 1, ("a block pair does not fit the scratch", pool_kv.shape)
    row_spec = pl.BlockSpec((1, hk, G, hd), lambda b, p, t: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B,),
        in_specs=[
            row_spec,
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=row_spec,
        scratch_shapes=[
            pltpu.VMEM((2, C, 2, hk, bt, hd), pool_kv.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, chunk_blocks=C, nb_w=nb_w),
        out_shape=jax.ShapeDtypeStruct((B, hk, G, hd), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dcp_paged_decode_attn",
        interpret=interpret,
    )(jnp.broadcast_to(jnp.atleast_1d(pos).astype(jnp.int32), (B,)),
      table.reshape(-1).astype(jnp.int32), q.reshape(B, hk, G, hd), pool_kv)
    return out.reshape(B, H, 1, hd)
