"""Flash-decode over the paged pool: the decode tick's attention as a
Pallas kernel.

:func:`paged_decode_attention_pallas` (``dcp_paged_decode_attn``) reads
the PAGED pool in place through the block table, for heads of whole
128-lane tiles. It is what
``ops/attention.py::_paged_write_and_attend`` calls for every eligible
pool (``paged_read_path``), in place of a per-tick gathered copy of
every row's K and V; every other caller (CPU, hd 64, int8 pool, verify
windows, meshes) keeps ``_paged_view`` + ``cached_attention``, which is
also the reference its tests compare with
(``tests/test_paged_decode_attention.py``).

How it is built:

- **Explicit DMA streaming**: the pool stays in HBM
  (``memory_space=ANY``); the kernel double-buffers chunks of blocks
  into VMEM scratch with ``make_async_copy``, so the stream runs at DMA
  bandwidth regardless of the 1-row query shape that starves XLA's
  tiling.
- **Dynamic length**: the chunk loop bound follows ``pos``, a traced
  scalar (scalar-prefetched), so blocks beyond a row's position are
  never fetched. XLA cannot express this with static shapes.
- **Parked rows are passed by**: a row whose table's first entry is the
  reserved trash block holds no request (``_live_from``: the paged
  format's own fact, read from the table every caller already ships; no
  flag). Its grid step issues no copy and no product and writes ZEROS:
  finite, and nobody reads that row. The stream is chained from live row
  to live row, so the rows between cost a grid step each (0.16 us on the
  v5e against the 2 us of a chunk of masked tokens: PERF.md section 6,
  PR 37). Live rows' outputs are bit for bit what they were.
- **Online softmax** (the flash recipe) in f32.

What it measured against the gather on the chip: PERF.md section 6,
PR 25.

:func:`paged_latent_decode_attention_pallas`
(``dcp_paged_latent_decode_attn``) is the same stream over a LATENT pool
(``models/hybrid.py``, mixer ``latent_attention``): a token is one vector
with no K/V pair and no heads, every query head attends it, and its first
channels are the value, so a block is fetched once and used twice.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_compute_pytorch_tpu.kv_pool import BlockPool


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
# (PERF.md, PR 25): at bt = 8 ONE block in flight is latency-bound;
# 512-token chunks beat 256 by 13-26%; issuing
# copies four to a loop step and waiting for a chunk in at most log2(C)
# waits another 18%; attending a chunk in steps of 128 live tokens
# instead of whole was 20-50% slower.
_CHUNK_TOKENS = 512
_SCRATCH_BYTES = 4 << 20
_ISSUE_GROUP = 4


def _live_from(table):
    """int32 ``[B + 1]``: entry ``b`` is the first LIVE row at or after
    ``b``, ``B`` where there is none (entry ``B`` always). A row is
    PARKED when its table's first entry is the reserved trash block
    (``kv_pool.py::BlockPool.TRASH``: the scheduler hands every slot out
    of a segment's plan an all-trash table, and a live row's first block
    never is it). The one array tells a kernel's grid step all it asks:
    row ``b`` is live iff entry ``b`` is ``b``, the stream opens at entry
    0 and goes on from row ``b`` to entry ``b + 1``."""
    B = table.shape[0]
    rows = jnp.where(table[:, 0] != BlockPool.TRASH,
                     jnp.arange(B, dtype=jnp.int32), B)
    return jnp.concatenate([lax.cummin(rows, reverse=True),
                            jnp.full((1,), B, jnp.int32)])


def _stream_rows(pos_ref, tbl_ref, nxt_ref, block_at, out_ref, buf, sem,
                 slot0_ref, *, C: int, nb_w: int, bt: int, live_row):
    """The grid step both decode kernels run, step ``b`` for row ``b``.

    A LIVE row (``nxt_ref[b] == b``, :func:`_live_from`) streams its live
    blocks (those its position reaches, never past the shipped table) in
    chunks of ``C`` into ``buf[slot]``: every block of a chunk is one
    async copy ``block_at(table[b, j]) -> buf[slot, j]``, all on
    ``sem[slot]``, issued ``group`` to a loop step (the last group filled
    up with the row's last live block again) and taken up in at most
    ``log2(C) + 1`` waits. The next chunk's copies are started before the
    current chunk is waited for, and after a row's last chunk they are the
    next LIVE row's first (``nxt_ref[b + 1]``; the first live row's are
    started by step 0), so no row pays a DMA latency of its own and a
    parked last row leaves no copy in flight. ``live_row()`` gives the
    row's ``(init, attend, finish)``: ``attend(c, slot, carry)`` folds
    chunk ``c`` into the online-softmax ``carry`` (from ``init``),
    ``finish(carry)`` writes the row's output. ``slot0_ref`` (SMEM)
    carries the buffer parity from live row to live row, so the grid
    steps run in order (``arbitrary``).

    A PARKED row issues no copy, waits for none and multiplies nothing:
    its block of ``out_ref`` is zeros and the stream passes it by."""
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    live = nxt_ref[b] == b
    group = math.gcd(C, _ISSUE_GROUP)

    def live_blocks(row):
        return jnp.clip(pos_ref[row] // bt + 1, 1, nb_w)

    def issue_steps(row, c):
        return pl.cdiv(jnp.minimum(live_blocks(row) - c * C, C), group)

    def start(row, c, slot):
        live, lo = live_blocks(row), c * C

        def issue(g, carry):
            for u in range(group):
                j = g * group + u
                phys = tbl_ref[row * nb_w + jnp.minimum(lo + j, live - 1)]
                pltpu.make_async_copy(block_at(phys), buf.at[slot, j],
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
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        slot0_ref[0] = 0

        @pl.when(nxt_ref[0] < n_rows)
        def _():
            start(nxt_ref[0], 0, 0)

    @pl.when(live)
    def _():
        slot0 = slot0_ref[0]
        n_chunks = pl.cdiv(live_blocks(b), C)
        nxt = nxt_ref[b + 1]
        init, attend, finish = live_row()

        def chunk_step(c, carry):
            slot = (slot0 + c) % 2

            @pl.when(c + 1 < n_chunks)
            def _():
                start(b, c + 1, 1 - slot)

            @pl.when(jnp.logical_and(c + 1 == n_chunks, nxt < n_rows))
            def _():
                start(nxt, 0, 1 - slot)

            wait(b, c, slot)
            return attend(c, slot, carry)

        carry = lax.fori_loop(0, n_chunks, chunk_step, init)
        slot0_ref[0] = (slot0 + n_chunks) % 2
        finish(carry)

    @pl.when(jnp.logical_not(live))
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)


def _paged_decode_kernel(pos_ref, tbl_ref, nxt_ref, q_ref, pool_hbm,
                         out_ref, buf, sem, slot0_ref, *, chunk_blocks: int,
                         nb_w: int):
    """Grid step ``b`` attends row ``b``'s query over logical slots
    ``0 .. pos[b]`` of ``pool_hbm [2, P, hk, bt, hd]`` (left in HBM)
    through ``tbl_ref`` (the row-major ``[B, nb_w]`` table), streamed by
    :func:`_stream_rows` (a block's copy is ``[2, hk, bt, hd]``: both
    planes); a parked row's output is zeros. Buffer
    slots no copy filled hold an earlier chunk's (finite) data, masked
    to probability 0. Online softmax per KV head: f32 scores, running
    max, sum and accumulator; the probabilities meet V in the pool's
    dtype, as in ``cached_attention``."""
    b = pl.program_id(0)
    _, _, hk, bt, hd = pool_hbm.shape
    C = chunk_blocks
    G = q_ref.shape[2]
    cdt = buf.dtype
    scale = hd ** -0.5

    def live_row():
        q = q_ref[0].astype(cdt)                     # [hk, G, hd]

        def attend(c, slot, carry):
            ms, ls, accs = carry
            ids = c * (C * bt) + lax.broadcasted_iota(
                jnp.int32, (G, C * bt), 1)
            valid = ids <= pos_ref[b]
            new_m, new_l, new_acc = [], [], []
            for h in range(hk):
                k = buf[slot, :, 0, h].reshape(C * bt, hd)
                v = buf[slot, :, 1, h].reshape(C * bt, hd)
                s = lax.dot_general(
                    q[h], k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(valid, s, -1e30)
                m_new = jnp.maximum(ms[h], jnp.max(s, axis=1, keepdims=True))
                alpha = jnp.exp(ms[h] - m_new)
                p = jnp.exp(s - m_new)
                new_l.append(ls[h] * alpha
                             + jnp.sum(p, axis=1, keepdims=True))
                new_acc.append(accs[h] * alpha + lax.dot_general(
                    p.astype(cdt), v, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
                new_m.append(m_new)
            return tuple(new_m), tuple(new_l), tuple(new_acc)

        def finish(carry):
            _, ls, accs = carry
            for h in range(hk):
                out_ref[0, h] = (accs[h] / ls[h]).astype(out_ref.dtype)

        return (
            (tuple(jnp.full((G, 1), -jnp.inf, jnp.float32)
                   for _ in range(hk)),
             tuple(jnp.zeros((G, 1), jnp.float32) for _ in range(hk)),
             tuple(jnp.zeros((G, hd), jnp.float32) for _ in range(hk))),
            attend, finish)

    _stream_rows(pos_ref, tbl_ref, nxt_ref,
                 lambda phys: pool_hbm.at[:, phys], out_ref, buf, sem,
                 slot0_ref, C=C, nb_w=nb_w, bt=bt, live_row=live_row)


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
    """Decode attention over a paged pool, read in place: every query of a
    row over ONE range of its slots.

    ``q [B, H, L, hd]`` (this tick's queries, ``L`` 1; or the ``L``
    positions of a block-diffusion block, which all attend the same slots:
    they ride beside the head group, ``L x G`` query rows to a KV head,
    and the stream of the row's blocks is the one a single query would
    cost); ``pool_kv [2, P, hk, bt,
    hd]`` (dim 0 = k/v: the serving pool leaf as it is, never copied or
    re-laid); ``table`` int32 ``[B, nb_w]`` (row ``b``'s logical slot
    ``t`` lives in pool block ``table[b, t // bt]`` at offset ``t %
    bt``; a width-rung slice is fine, the cost follows ``pos``); ``pos``
    int32 ``[B]`` (row ``b`` attends slots ``0 .. pos[b]``, its own
    just-written ones included). Returns ``[B, H, L, hd]`` in ``q``'s
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
    assert hd % 128 == 0 and H % hk == 0, (q.shape, hk)
    nb_w = table.shape[1]
    G = H // hk * q_len       # query rows to a KV head: (group, position)
    C = _chunk_blocks(pool_kv.shape, pool_kv.dtype.itemsize, nb_w)
    assert C >= 1, ("a block pair does not fit the scratch", pool_kv.shape)
    row_spec = pl.BlockSpec((1, hk, G, hd), lambda b, p, t, n: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
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
      table.reshape(-1).astype(jnp.int32), _live_from(table),
      q.reshape(B, hk, G, hd), pool_kv)
    return out.reshape(B, H, q_len, hd)


# ---------------------------------------------------------------------------
# LATENT pool: ``[1, P, 1, bt, W]``, a token ONE vector of ``W`` channels
# (the compressed K/V of a latent-attention layer followed by its shared
# rotary key, zero-padded to whole 128-lane tiles:
# ``ops/attention.py::latent_pool_width``). All ``H`` absorbed queries of a
# row attend that one vector as key; its first ``V`` channels are the value.
# ---------------------------------------------------------------------------

# A chunk in tokens, as ``_CHUNK_TOKENS`` above. A token is W x 2 bytes
# (1280 at 576 channels in 640 lanes), so two buffers of 1024 tokens are
# 2.6 MB. Measured on the v5e at the long-document cell's shape (64 rows
# of ~8.6k live tokens, 32 heads; PERF.md, PR 32): chunks of 256 / 512 /
# 1024 / 2048 tokens took 1.63 / 1.17 / 1.02 / 1.03 ms a call at blocks
# of 32 tokens (1.88 / 1.43 / 1.25 / 1.17 at 16, 1.56 / 1.14 / 1.01 /
# 1.02 at 64); issuing copies eight to a loop step instead of four
# changed nothing at 32 and 64.
_LATENT_CHUNK_TOKENS = 1024


def _latent_decode_kernel(pos_ref, tbl_ref, nxt_ref, q_ref, pool_hbm,
                          out_ref, buf, sem, slot0_ref, *, chunk_blocks: int,
                          nb_w: int, v_width: int, scale: float):
    """Grid step ``b``: row ``b``'s ``H`` queries ``q_ref [1, H, W]`` over
    logical slots ``0 .. pos[b]`` of ``pool_hbm [1, P, 1, bt, W]`` through
    the table, streamed as :func:`_paged_decode_kernel` streams its pool
    (:func:`_stream_rows`; one async copy a block, ``[bt, W]``; a parked
    row's output is zeros). A chunk is the key of every
    head (scores over its ``V`` compressed channels and over its ``W - V``
    rotary channels, two products whose operands start on a lane tile) and,
    in its first ``V`` channels, the value. Online softmax in f32; output
    ``[1, H, V]``."""
    b = pl.program_id(0)
    _, _, _, bt, W = pool_hbm.shape
    C, V = chunk_blocks, v_width
    H = q_ref.shape[1]
    cdt = buf.dtype
    nt = (((1,), (1,)), ((), ()))

    def live_row():
        q = q_ref[0].astype(cdt)                     # [H, W]
        q_c, q_r = q[:, :V], q[:, V:]

        def attend(c, slot, carry):
            m, l, acc = carry
            lat = buf[slot].reshape(C * bt, W)
            val = lat[:, :V]
            s = (lax.dot_general(q_c, val, nt,
                                 preferred_element_type=jnp.float32)
                 + lax.dot_general(q_r, lat[:, V:], nt,
                                   preferred_element_type=jnp.float32)
                 ) * scale
            ids = c * (C * bt) + lax.broadcasted_iota(
                jnp.int32, (H, C * bt), 1)
            s = jnp.where(ids <= pos_ref[b], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + lax.dot_general(
                p.astype(cdt), val, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        def finish(carry):
            _, l, acc = carry
            out_ref[0] = (acc / l).astype(out_ref.dtype)

        return ((jnp.full((H, 1), -jnp.inf, jnp.float32),
                 jnp.zeros((H, 1), jnp.float32),
                 jnp.zeros((H, V), jnp.float32)), attend, finish)

    _stream_rows(pos_ref, tbl_ref, nxt_ref,
                 lambda phys: pool_hbm.at[0, phys, 0], out_ref, buf, sem,
                 slot0_ref, C=C, nb_w=nb_w, bt=bt, live_row=live_row)


@functools.partial(jax.jit,
                   static_argnames=("v_width", "scale", "interpret"))
def paged_latent_decode_attention_pallas(q, pool_kv, table, pos, *,
                                         v_width: int, scale: float,
                                         interpret: bool = False):
    """Single-position decode attention over a LATENT paged pool, read in
    place. ``q [B, H, W]`` (this tick's absorbed queries: ``v_width``
    channels against the compressed part, the rest against the rotary
    key); ``pool_kv [1, P, 1, bt, W]`` (the serving pool leaf as it is);
    ``table`` int32 ``[B, nb_w]``; ``pos`` int32 ``[B]`` (row ``b`` attends
    slots ``0 .. pos[b]``). Returns ``[B, H, v_width]`` in ``q``'s dtype:
    ``softmax(scale * q . latent) @ latent[..., :v_width]``, the same
    mathematics as the gather + dense form of
    ``ops/attention.py::latent_write_and_attend``. Jitted on its own for
    the reason :func:`paged_decode_attention_pallas` gives."""
    B, H, W = q.shape
    s, _, hk, bt, Wp = pool_kv.shape
    assert (s == 1 and hk == 1 and Wp == W and 0 < v_width < W
            and W % 128 == 0 and v_width % 128 == 0), (
        q.shape, pool_kv.shape, v_width)
    nb_w = table.shape[1]
    C = min(max(1, _LATENT_CHUNK_TOKENS // bt), nb_w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda b, p, t, n: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, v_width),
                               lambda b, p, t, n: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, C, bt, W), pool_kv.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_latent_decode_kernel, chunk_blocks=C, nb_w=nb_w,
                          v_width=v_width, scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="dcp_paged_latent_decode_attn",
        interpret=interpret,
    )(jnp.broadcast_to(jnp.atleast_1d(pos).astype(jnp.int32), (B,)),
      table.reshape(-1).astype(jnp.int32), _live_from(table), q, pool_kv)
