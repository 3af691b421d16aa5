"""Flash attention as Pallas TPU kernels (forward + backward).

Why a hand kernel when XLA fuses well: dense attention materialises the
[T, T] logits tensor in HBM; on TPU the HBM round-trip dominates once T is
a few thousand. The flash schedule streams K/V blocks through VMEM with an
online softmax, so logits never leave VMEM and memory is O(T) — the standard
FlashAttention recurrence mapped onto the Pallas TPU grid model:

- grid = (batch*heads, q_blocks, kv_blocks), innermost kv axis sequential,
  accumulators (o, m, l) in VMEM scratch persisting across kv steps
  (`@pl.when(kv==0)` init / `@pl.when(kv==last)` write, guide §Grid);
- MXU matmuls via jnp.dot with preferred_element_type=float32 (guide §Math);
- causal runs skip fully-masked kv blocks with `@pl.when` and mask the
  blocks the diagonal can cross with broadcasted_iota (guide: 2D iota). In
  the BACKWARD kernels the block ON the diagonal (square blocks whose first
  row and first column meet: one block a head at the train cell's T = 1024)
  is WALKED inside its grid step in sub-tiles (`_accumulate`): tiles above
  the diagonal hold no pair and are dropped (28 of 64), the tile on it is
  masked, the rest run without the iotas and the select. The walk is two
  `fori_loop`s of static trip count over ONE tile body, unrolled only when
  the kernel is lowered: the jaxpr holds the whole block's body and one
  tile's whatever the block is, and Mosaic gets straight-line code with
  constant strip and tile indices. Written as loops that Mosaic cannot
  unroll (bounds from `program_id`) the same walk pays every tile's latency
  in turn and ran 2-5 times SLOWER than the block whole; written out tile
  by tile in Python it cost a serve cell 10-33 s of set-up (PERF.md PR 35).
  Blocks off the diagonal run whole: their geometry hangs on `program_id`,
  so a walk would be those loops. The FORWARD runs every block whole: at
  head width 64 it is not bound by its pairs (walked, it read 1.04 ms a
  call for 0.80), and a square tile pays an online-softmax rescale a tile.
  The BLOCK stays large (`ops/attention.py::_pick_block`): a block body
  pays its latency chain once for 1024 x 1024 pairs (the same kernels at
  blocks of 512 / 256: the train step 191 -> 211 / 272 ms), and the walk
  does inside it what the grid's own skip would do with a step each;
- backward is the two-kernel split (dQ; dK/dV) using the saved logsumexp
  and the precomputed row term delta = rowsum(dO * O). A one-pass fused
  backward (sharing the recomputed score block between dQ and dK/dV) was
  built and REJECTED: the side whose accumulator is keyed by the inner
  grid axis must read-modify-write a revisited HBM block, and Pallas's
  pipelined prefetch fetches the next visit's input block while the
  previous write is still in flight — a race that corrupted dQ in
  testing. The split costs 2 extra block matmuls of 7 but every
  accumulator lives in VMEM scratch across consecutive grid steps,
  which is the sound TPU schedule (the reference TPU kernels make the
  same choice).

Block sizes default to 128 (MXU tile). The public wrapper accepts ANY
sequence lengths: non-block-multiples are zero-padded and masked (padded
keys through the kv_mask path, padded query rows sliced off), and causal
cross-length attention (q_len < kv_len, bottom-right aligned — masked
long-prompt prefill) runs natively via a static kernel offset.
On CPU (tests) kernels run in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK = 128
_NEG_INF = -1e30


def _dot_tt(a, b):
    """``a @ b.T`` via dot_general contracting the trailing dims — the MXU
    contracts either operand's layout natively; an explicit ``b.T`` inside a
    kernel costs a VPU relayout per grid step."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b):
    """``a.T @ b`` via dot_general contracting the leading dims."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


def _out_struct(shape, dtype, like):
    """Output aval that varies over the same manual mesh axes as the
    operand ``like`` — inside a ``shard_map`` (how the kernels run under
    a mesh, ``ops/attention.py``) the varying-axes check needs it said;
    outside one the set is empty."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------------
# the walk of the diagonal block (backward kernels)
# ---------------------------------------------------------------------------

# Sub-tiles a side of the diagonal block, fewer where a tile would be under
# 128 wide: 10 of 16 tiles hold a pair at 4, 36 of 64 at 8. Chosen on the
# chip at the train cell's shape (PERF.md PR 35): the dQ kernel took 0.60 /
# 0.70 ms a call at 4 / 8 and 0.77 whole, the dK/dV kernel 1.01 / 0.85 and
# 1.10 (three products a tile against four: the more a tile multiplies, the
# smaller it may be before its latency shows).
_DQ_TILES = 4
_DKV_TILES = 8


def _diagonal_tiles(causal, block_q, block_k, most) -> int:
    """Sub-tiles a side in which a kernel walks the block on the diagonal
    (1 = the block runs whole): the most, up to ``most``, whose edge is a
    multiple of 128 (a lane tile: the key mask is cut along lanes)."""
    if not causal or block_q != block_k:
        return 1
    return max((n for n in range(2, most + 1) if block_q % (128 * n) == 0),
               default=1)


def _masked(s, keep):
    """``s`` where ``keep``, else the finite stand-in for minus infinity."""
    return jax.lax.select(keep, s, jnp.full_like(s, _NEG_INF))


def _edge_mask(s, lead, window=None, mask_block=0):
    """Mask a score tile that the diagonal (or the band's low edge) crosses.
    ``lead`` = absolute position of the tile's first row less that of its
    first column: row ``i`` sees column ``j`` iff ``j - i <= lead`` (and,
    in a band, ``i - j + lead < window``). ``mask_block`` (a power of two
    dividing both tile origins): the BLOCK mask in place of the causal one,
    row ``i`` sees every column of the blocks up to its own: ``j`` counts
    as the first column of its block."""
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if mask_block:
        col = col & ~(mask_block - 1)
    rel = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) - col
    keep = rel >= -lead
    if window is not None:
        keep = keep & (rel < window - lead)
    return _masked(s, keep)


def _accumulate(step, load, keep, *, run, lead, causal, tiles, block,
                by_rows):
    """A backward kernel's grid step: ``keep(at, step(load(at), rows, cols,
    mask))`` over the pairs of the block that can be seen. A block off the
    diagonal runs whole (masked where causal: its place hangs on
    ``program_id``). The block ON the diagonal (``lead`` 0) is walked in
    ``tiles`` x ``tiles`` sub-tiles: a strip of the accumulator (``by_rows``:
    of query rows, else of keys) is carried over the tiles across it, tiles
    above the diagonal are dropped, only the tile on it is masked.

    The walk is two ``fori_loop``s of static trip count over ONE tile body,
    unrolled when the kernel is lowered: the jaxpr, traced anew in every
    process, holds the whole block's body and one tile's whatever the block
    is, and Mosaic gets straight-line code whose strip and tile indices are
    constants, so its canonicaliser folds the ``lax.cond``s away, the
    dropped tiles with them. (No ``pl.multiple_of`` on a strip's start: the
    hint would keep it from folding into a static slice.)"""
    whole = slice(None)
    on_diagonal = (lead == 0) if tiles > 1 else False

    @pl.when(run & jnp.logical_not(on_diagonal))
    def _whole():
        keep(whole, step(load(whole), whole, whole,
                         (lambda s: _edge_mask(s, lead)) if causal
                         else (lambda s: s)))

    if tiles == 1:
        return
    sub = block // tiles

    @pl.when(run & on_diagonal)
    def _diagonal():
        def strip(i, _):
            mine = pl.ds(i * sub, sub)

            def tile(j, carry):
                gap = (i - j) if by_rows else (j - i)   # row less column
                other = pl.ds(j * sub, sub)
                rows, cols = (mine, other) if by_rows else (other, mine)
                return jax.lax.cond(
                    gap >= 0,
                    lambda carry: step(carry, rows, cols, lambda s: jax.lax.cond(
                        gap == 0, lambda s: _edge_mask(s, 0), lambda s: s, s)),
                    lambda carry: carry, carry)

            keep(mine, jax.lax.fori_loop(0, tiles, tile, load(mine),
                                         unroll=True))

        jax.lax.fori_loop(0, tiles, strip, None, unroll=True)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, *rest,
                scale, causal, offset, masked, block_q, block_k,
                window=None, nband=None, mask_block=0):
    if masked:
        mask_ref, o_ref, lse_ref, acc, m_s, l_s = rest
    else:
        mask_ref, (o_ref, lse_ref, acc, m_s, l_s) = None, rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_s[:] = jnp.full_like(m_s, _NEG_INF)
        l_s[:] = jnp.zeros_like(l_s)

    # `offset` = kv_len - q_len (static): bottom-right-aligned causal for
    # cross-length attention (masked long-prompt prefill) — query row i
    # sits at absolute kv position i + offset. offset=0 is self-attention.
    # BANDED (`window`, self-attention with block_q == block_k): the kv
    # grid axis walks only the `nband` blocks that can hold a key of the
    # band, ending on the diagonal; `kb` is the block a step really holds
    # (the index map clamps a block before the first to 0, and the step
    # is skipped).
    kb = ki if window is None else qi - (nband - 1) + ki
    if window is not None:
        run = kb >= 0
    else:
        run = (ki * block_k < (qi + 1) * block_q + offset) if causal \
            else (ki == ki)

    @pl.when(run)
    def _compute():
        # matmul inputs stay in their native dtype (bf16 in the mixed-
        # precision path): the MXU multiplies bf16 natively with f32
        # accumulation via preferred_element_type — pre-casting to f32
        # forces multi-pass f32 matmuls at a fraction of peak
        q = q_ref[0]                                  # [Bq, D]
        k = k_ref[0]                                  # [Bk, D]
        v = v_ref[0]                                  # [Bk, Dv]
        s = _dot_tt(q, k) * scale
        if causal:
            s = _edge_mask(s, offset + qi * block_q - kb * block_k, window,
                           mask_block)
        if masked:
            # [1, Bk] f32 0/1 key-validity row broadcast down the q rows.
            # _NEG_INF (not -inf) keeps fully-masked rows NaN-free: their
            # p degenerates to uniform but their upstream do is zero, so no
            # garbage reaches the gradients (padded positions are excluded
            # from every loss).
            s = _masked(s, jnp.broadcast_to(mask_ref[0] > 0.5, s.shape))
        m_prev = m_s[:, :1]                           # [Bq, 1]
        l_prev = l_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, -1, keepdims=True)
        acc[:] = acc[:] * corr + jnp.dot(p.astype(v.dtype), v,
                                         preferred_element_type=jnp.float32)
        m_s[:] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[:] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(ki == last_k)
    def _write():
        l = l_s[:, :1]
        o_ref[0] = (acc[:] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        # single-lane output: a lane dim equal to the full array dim (1)
        # satisfies the tiling rule without broadcasting to 128 lanes —
        # 128x less lse traffic than the lane-broadcast layout
        lse_ref[0] = m_s[:, :1] + jnp.log(jnp.maximum(l, 1e-30))


def _mask_spec(heads, block_k):
    """BlockSpec for the [B, 1, Tk] f32 key-validity mask: the grid's bh
    axis maps to batch row bh // heads (every head shares its batch row).
    Rank-3 with a singleton middle dim because Mosaic requires a rank-2
    block's sublane dim to be 8-divisible or the full array dim."""
    return pl.BlockSpec((1, 1, block_k),
                        lambda b, i, j, h=heads: (b // h, 0, j))


def _flash_fwd(q, k, v, kv_mask, heads, scale, causal, offset,
               block_q, block_k, window=None, kv_heads=None, mask_block=0):
    bh, t, d = q.shape
    tk = k.shape[1]
    # v may have another head width than q and k (latent attention: 192
    # against 128): the accumulator and the output take v's
    dv = v.shape[2]
    masked = kv_mask is not None
    v_spec = None
    if window is None:
        grid = (bh, t // block_q, tk // block_k)
        kernel = functools.partial(
            _fwd_kernel, scale=scale, causal=causal, offset=offset,
            masked=masked, block_q=block_q, block_k=block_k,
            # (a keyword only where it is set: every other call's kernel is
            # the partial it always was)
            **({"mask_block": mask_block} if mask_block else {}))
        kv_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
        if dv != d:
            v_spec = pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, j, 0))
        mask_spec = _mask_spec(heads, block_k) if masked else None
        name = "dcp_flash_fwd"
    else:
        # the band: kv blocks qi - (nband - 1) .. qi of each q block, K/V
        # read at THEIR head count (query head h reads kv head h // G:
        # no repeated copy of K/V is made for the kernel)
        assert causal and offset == 0 and block_q == block_k and t == tk
        assert dv == d, (d, dv)
        nband = -(-(window - 1) // block_k) + 1
        grid = (bh, t // block_q, nband)
        kernel = functools.partial(_fwd_kernel, scale=scale, causal=True,
                                   offset=0, masked=masked,
                                   block_q=block_q, block_k=block_k,
                                   window=window, nband=nband)
        hk = kv_heads or heads
        g = heads // hk

        def kv_row(b):
            return (b // heads) * hk + (b % heads) // g

        def kv_blk(i, j):
            return jnp.maximum(i - (nband - 1) + j, 0)

        kv_spec = pl.BlockSpec(
            (1, block_k, d), lambda b, i, j: (kv_row(b), kv_blk(i, j), 0))
        mask_spec = pl.BlockSpec(
            (1, 1, block_k),
            lambda b, i, j: (b // heads, 0, kv_blk(i, j))) if masked else None
        name = "dcp_flash_fwd_band"
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        kv_spec, v_spec or kv_spec,
    ]
    args = [q, k, v]
    if masked:
        in_specs.append(mask_spec)
        args.append(kv_mask)
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((bh, t, dv), q.dtype, q),
            _out_struct((bh, t, 1), jnp.float32, q),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        # batch*heads and q blocks are independent — declaring them parallel
        # lets Mosaic pipeline (double-buffer) block loads across grid steps;
        # only the kv axis carries the accumulator dependency
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
        interpret=_use_interpret(),
    )(*args)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _score_grads(q, k, v, do, lse, delta, valid, mask, scale):
    """``p`` and ``ds`` of one score tile from the saved row terms: the
    softmax weights and their gradient before the scale. Operands stay in
    their native dtype (MXU-native bf16 products into f32)."""
    s = mask(_dot_tt(q, k) * scale)
    if valid is not None:
        s = _masked(s, jnp.broadcast_to(valid > 0.5, s.shape))
    p = jnp.exp(s - lse)
    return p, p * (_dot_tt(do, v) - delta)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   scale, causal, offset, masked, block_q, block_k):
    if masked:
        mask_ref, dq_ref, dq_acc = rest
    else:
        mask_ref, (dq_ref, dq_acc) = None, rest
    qi, ki = pl.program_id(1), pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (ki * block_k < (qi + 1) * block_q + offset) if causal \
        else (ki == ki)

    def step(dq, rows, cols, mask):
        """``dq`` of the query rows plus what the keys of ``cols`` add."""
        k = k_ref[0, cols, :]
        _, ds = _score_grads(
            q_ref[0, rows, :], k, v_ref[0, cols, :], do_ref[0, rows, :],
            lse_ref[0, rows, :], delta_ref[0, rows, :],
            mask_ref[0, :, cols] if masked else None, mask, scale)
        return dq + jnp.dot(ds.astype(k.dtype), k,
                            preferred_element_type=jnp.float32) * scale

    def keep(rows, dq):
        dq_acc[rows, :] = dq

    _accumulate(step, lambda rows: dq_acc[rows, :], keep, run=run,
                lead=offset + qi * block_q - ki * block_k, causal=causal,
                tiles=_diagonal_tiles(causal, block_q, block_k, _DQ_TILES),
                block=block_q, by_rows=True)

    @pl.when(ki == last_k)
    def _write():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    scale, causal, offset, masked, block_q, block_k):
    if masked:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    else:
        mask_ref, (dk_ref, dv_ref, dk_acc, dv_acc) = None, rest
    ki, qi = pl.program_id(1), pl.program_id(2)
    last_q = pl.num_programs(2) - 1

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = ((qi + 1) * block_q + offset > ki * block_k) if causal \
        else (qi == qi)

    def step(dkv, rows, cols, mask):
        """``dk`` and ``dv`` of the keys plus what the query rows add."""
        dk, dv = dkv
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        p, ds = _score_grads(
            q, k_ref[0, cols, :], v_ref[0, cols, :], do,
            lse_ref[0, rows, :], delta_ref[0, rows, :],
            mask_ref[0, :, cols] if masked else None, mask, scale)
        return (dk + _dot_nt(ds.astype(q.dtype), q) * scale,
                dv + _dot_nt(p.astype(do.dtype), do))

    def keep(cols, dkv):
        dk_acc[cols, :], dv_acc[cols, :] = dkv

    _accumulate(step, lambda cols: (dk_acc[cols, :], dv_acc[cols, :]), keep,
                run=run, lead=offset + qi * block_q - ki * block_k,
                causal=causal,
                tiles=_diagonal_tiles(causal, block_q, block_k, _DKV_TILES),
                block=block_q, by_rows=False)

    @pl.when(qi == last_q)
    def _write():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# Under a jit of its own: a model's layers call the backward with the same
# shapes and constants, so it is traced once a process and lowered once a
# program instead of once a layer (the walk's unrolled tiles are lowered one
# by one: 0.2-0.4 s a kernel where the whole block took 0.05).
@functools.partial(jax.jit, static_argnames=(
    "heads", "scale", "causal", "offset", "block_q", "block_k"))
def _flash_bwd(res, g, kv_mask, heads, scale, causal, offset,
               block_q, block_k):
    q, k, v, o, lse = res
    bh, t, d = q.shape
    tk = k.shape[1]
    if v.shape[2] != d:
        raise NotImplementedError(
            f"flash attention with a v head width ({v.shape[2]}) other than "
            f"q's and k's ({d}) has a forward only: latent-attention layers "
            f"are served, not trained")
    do = g.astype(jnp.float32)
    # single-lane rank-3 [bh, t, 1]: a lane dim equal to the full array dim
    # satisfies the tiling rule without a 128-lane broadcast; lse arrives
    # in this layout from the forward
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1)[..., None]
    masked = kv_mask is not None
    extra = (kv_mask,) if masked else ()

    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
    ]
    if masked:
        dq_specs.append(_mask_spec(heads, block_k))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          offset=offset, masked=masked,
                          block_q=block_q, block_k=block_k),
        grid=(bh, t // block_q, tk // block_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct(q.shape, q.dtype, q),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="dcp_flash_bwd_dq",
        interpret=_use_interpret(),
    )(q, k, v, g.astype(q.dtype), lse, delta, *extra)

    dkv_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
        pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0)),
    ]
    if masked:
        # dkv grid is (bh, kv, q): the kv block index is grid arg 1
        dkv_specs.append(
            pl.BlockSpec((1, 1, block_k),
                         lambda b, j, i, h=heads: (b // h, 0, j)))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          offset=offset, masked=masked,
                          block_q=block_q, block_k=block_k),
        grid=(bh, tk // block_k, t // block_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct(k.shape, k.dtype, q),
            _out_struct(v.shape, v.dtype, q),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="dcp_flash_bwd_dkv",
        interpret=_use_interpret(),
    )(q, k, v, g.astype(q.dtype), lse, delta, *extra)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, offset, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, None, 1, scale, causal, offset,
                      block_q, block_k)
    return o


def _flash_vjp_fwd(q, k, v, scale, causal, offset, block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name
    o, lse = _flash_fwd(q, k, v, None, 1, scale, causal, offset,
                        block_q, block_k)
    # the [bh, t, 1] single-lane lse flows to the backward unchanged.
    # Tags: under remat="dots" the RESIDUALS must be the saveable tensors
    # (a tag applied by the caller to the custom_vjp's OUTPUT marks a
    # different equation), so o/lse are named here and the kernel itself
    # is never re-run in the backward.
    o = checkpoint_name(o, "attn_ctx")
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, offset, block_q, block_k, res, g):
    return _flash_bwd(res, g, None, 1, scale, causal, offset,
                      block_q, block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_masked(q, k, v, kv_mask, heads, scale, causal, offset,
                  block_q, block_k):
    o, _ = _flash_fwd(q, k, v, kv_mask, heads, scale, causal, offset,
                      block_q, block_k)
    return o


def _flash_masked_vjp_fwd(q, k, v, kv_mask, heads, scale, causal, offset,
                          block_q, block_k):
    from jax.ad_checkpoint import checkpoint_name
    o, lse = _flash_fwd(q, k, v, kv_mask, heads, scale, causal, offset,
                        block_q, block_k)
    o = checkpoint_name(o, "attn_ctx")       # see _flash_vjp_fwd
    lse = checkpoint_name(lse, "attn_lse")
    return o, (q, k, v, o, lse, kv_mask)


def _flash_masked_vjp_bwd(heads, scale, causal, offset, block_q, block_k,
                          res, g):
    *res5, kv_mask = res
    dq, dk, dv = _flash_bwd(tuple(res5), g, kv_mask, heads, scale, causal,
                            offset, block_q, block_k)
    # the mask is data, not a differentiable input
    return dq, dk, dv, jnp.zeros_like(kv_mask)


_flash_masked.defvjp(_flash_masked_vjp_fwd, _flash_masked_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_band(q, k, v, kv_mask, heads, kv_heads, scale, window, block):
    o, _ = _flash_fwd(q, k, v, kv_mask, heads, scale, True, 0, block, block,
                      window=window, kv_heads=kv_heads)
    return o


def _flash_band_vjp_fwd(q, k, v, kv_mask, heads, kv_heads, scale, window,
                        block):
    return _flash_band(q, k, v, kv_mask, heads, kv_heads, scale, window,
                       block), None


def _flash_band_vjp_bwd(*_):
    raise NotImplementedError(
        "the banded flash forward (window=) has no backward: window "
        "layers are served, not trained")


_flash_band.defvjp(_flash_band_vjp_fwd, _flash_band_vjp_bwd)


def band_block(window: int) -> int:
    """Block of the banded forward: four times the window in a power of
    two, 128..1024. Two blocks a query block hold the band; a smaller
    block multiplies less outside it but the grid grows, and a grid step
    costs more than its small products (window 128 at 2 rows x 64 heads
    x 2048 on a v5e: blocks of 128 / 256 / 512 took 3.08 / 2.76 / 2.29
    ms, PERF.md PR 28)."""
    b = 128
    while b < 4 * window and b < 1024:
        b *= 2
    return b


def flash_attention_band(q, k, v, *, window: int,
                         scale: float | None = None, kv_mask=None,
                         block: int | None = None):
    """Causal self-attention over a WINDOW: ``q [b, h, t, d]``, ``k``/``v``
    ``[b, hk, t, d]`` with ``hk`` dividing ``h`` (grouped queries read
    their KV head in place); row ``i`` sees keys ``j`` with ``i - window <
    j <= i``. KV blocks wholly outside the band are never visited (the
    grid's kv axis is the band's few blocks), blocks on its edge are
    masked. Forward only. ``kv_mask`` as :func:`flash_attention`."""
    b, h, t, d = q.shape
    hk = k.shape[1]
    if k.shape[2] != t or h % hk:
        raise ValueError(
            f"banded flash attention is self-attention over grouped heads: "
            f"q {q.shape}, k {k.shape}")
    scale = (d ** -0.5) if scale is None else scale
    block = block or band_block(window)
    pad = (-t) % block
    if pad:
        # padded keys lie after every real row, out of its causal reach
        q, k, v = (jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0)))
                   for a in (q, k, v))
    tp = t + pad
    mask3 = None
    if kv_mask is not None:
        if kv_mask.shape != (b, t):
            raise ValueError(f"kv_mask shape {kv_mask.shape} != {(b, t)}")
        mask3 = jnp.pad(kv_mask.astype(jnp.float32),
                        ((0, 0), (0, pad))).reshape(b, 1, tp)
    o = _flash_band(q.reshape(b * h, tp, d), k.reshape(b * hk, tp, d),
                    v.reshape(b * hk, tp, d), mask3, h, hk, scale, window,
                    block)
    o = o.reshape(b, h, tp, d)
    return o[:, :, :t, :] if pad else o


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None,
                    kv_mask=None,
                    block_q: int = DEFAULT_BLOCK,
                    block_k: int = DEFAULT_BLOCK,
                    mask_block: int = 0):
    """Fused attention: ``[b, h, t, d]`` in, same out. Differentiable.
    ``v`` may have another head width than ``q`` and ``k`` (``[b, h, tk,
    dv]``: a latent-attention head is 192 wide for q and k, 128 for v);
    the output is then ``[b, h, t, dv]`` and only the forward exists.

    ``kv_mask``: optional ``[b, kv_len]`` key-validity mask (bool or 0/1
    float; True/1 = attend) — the padding mask for variable-length batches.
    Fully-masked query rows produce finite garbage that callers must
    exclude from the loss (they do: padded positions never contribute).

    Any sequence lengths are accepted (VERDICT r4 weak #6): lengths that
    do not divide the blocks are zero-PADDED up to the next multiple —
    padded keys are masked out through the kv_mask path, padded query
    rows are computed-and-sliced — so odd-length masked prefill stays on
    the flash path instead of falling back to the dense [T, T] one.
    Causal with ``q_len != kv_len`` uses bottom-right alignment (query
    row i attends kv positions ``<= i + kv_len - q_len`` — the masked
    decode-prefill convention, matching the dense path); ``q_len >
    kv_len`` causal is rejected (its top rows would attend nothing).

    ``mask_block`` (static; causal self-attention, a power of two dividing
    128): the block mask of a block-diffusion model, row ``i`` sees ``j``
    iff ``j // mask_block <= i // mask_block``. The grid skips the same
    tiles as under the causal mask (a row's own block ends inside its
    tile); only the masking of the diagonal tiles differs. Forward only.
    """
    b, h, t, d = q.shape
    tk = k.shape[2]
    if causal and t > tk:
        raise ValueError(
            f"causal flash attention needs q_len <= kv_len "
            f"(got {t} > {tk}): bottom-right alignment would leave the "
            f"first {t - tk} query rows attending nothing")
    offset = (tk - t) if causal else 0
    scale = (d ** -0.5) if scale is None else scale
    if mask_block and not (causal and offset == 0 and 128 % mask_block == 0):
        raise ValueError(
            f"mask_block {mask_block} needs causal self-attention and a "
            f"power of two dividing 128 (causal {causal}, q {t}, kv {tk})")

    pad_q = (-t) % block_q
    pad_k = (-tk) % block_k
    if pad_k and kv_mask is None and not causal:
        # non-causal padded keys are reachable and must be masked out.
        # Causal needs no synthesized mask: real query row i attends
        # absolute kv positions <= i + offset <= tk - 1, so padded
        # columns are unreachable (and padded query rows — which do
        # reach them — are sliced off with zero upstream cotangents);
        # skipping it keeps the faster unmasked kernel on the common
        # odd-length causal prefill.
        kv_mask = jnp.ones((b, tk), jnp.float32)   # real keys only
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if kv_mask is not None:
        if kv_mask.shape != (b, tk):
            raise ValueError(f"kv_mask shape {kv_mask.shape} != {(b, tk)}")
        if pad_k:
            kv_mask = jnp.pad(kv_mask.astype(jnp.float32),
                              ((0, 0), (0, pad_k)))
    tp, tkp = t + pad_q, tk + pad_k

    qf = q.reshape(b * h, tp, d)
    kf = k.reshape(b * h, tkp, d)
    dv = v.shape[-1]
    vf = v.reshape(b * h, tkp, dv)
    if mask_block:
        # padded query rows reach padded keys under the block mask too, and
        # are sliced off; forward only (served, not trained)
        o, _ = _flash_fwd(
            qf, kf, vf, None if kv_mask is None else kv_mask.astype(
                jnp.float32).reshape(b, 1, tkp), h, scale, True, 0,
            block_q, block_k, mask_block=mask_block)
    elif kv_mask is None:
        o = _flash(qf, kf, vf, scale, causal, offset, block_q, block_k)
    else:
        # rank-3 [B, 1, Tk] so the kernels' (1, 1, block_k) mask blocks
        # satisfy Mosaic's tiling rule (see _mask_spec)
        mask3 = kv_mask.astype(jnp.float32).reshape(b, 1, tkp)
        o = _flash_masked(qf, kf, vf, mask3, h,
                          scale, causal, offset, block_q, block_k)
    o = o.reshape(b, h, tp, dv)
    return o[:, :, :t, :] if pad_q else o
