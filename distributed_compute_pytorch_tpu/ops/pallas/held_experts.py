"""The held experts' decode form as ONE Pallas kernel over the experts some
row chose.

``models/moe.py::HeldExperts._dense`` is a batched product over EVERY held
expert, the token's weight 0 where it did not pick the expert: at a decode
tick's handful of rows it is bound by the stream of the experts' weights, and
it streams them all. Where a tick's rows choose half of the held experts
(GLM-5.3-Flash: 32 rows x 8 of 288, 36 held; ZAYA1: 20 rows x 1 of 17) half
of that stream multiplies by zero. ``dcp_held_experts`` moves the weights of
the experts at least one row chose and of no other:

- the chosen experts go in COMPACTED as scalar prefetch (the chosen first, in
  order, then the last of them again; ``kda_step.py``'s and
  ``decode_attention.py``'s idiom, PR 37 and PR 45): step ``(i, j)`` of the
  grid ``(n, f / f_tile)`` takes the ``j``-th tile of the ``i``-th chosen
  expert's ``gate`` and ``up`` (``[d, f_tile]``) and ``down`` (``[f_tile,
  d]``), and every step past the last chosen expert names the blocks the last
  live step named, so it issues no copy and computes nothing;
- the rows ``x [N, d]`` and the result, a float32 ``[N, d]``, stay in VMEM
  for the whole call; a row's weight for the step's expert arrives as a
  column beside the expert's blocks;
- the arithmetic is ``_dense``'s: the same operands in the rows' type into
  float32, the same clamp, ``h`` cast as ``_dense`` casts it, the sum over
  experts in float32. Only the order of that sum differs (chosen experts in
  order, ``f`` a tile at a time). An expert no row chose is never read:
  whatever its weights hold cannot reach the result.

Build cost is a constraint here too (ROADMAP A4): the entry is under a ``jit``
of its own, so a model's sparse layers share one trace a signature and one
lowering a program, and the body has the same equations at every ``n`` and
``N``.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_compute_pytorch_tpu.models import layers as L
from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
    _use_interpret)
from distributed_compute_pytorch_tpu.ops.pallas.kda_step import compact_rows

# Bytes of ONE of a step's three blocks of weights: a step's fixed cost and
# its products have to hide under its copies. On a v5e, every expert chosen,
# blocks of 1 / 2 / 4 MB: 36 experts of 4096 x 2048 over 32 rows 2.46 / 2.54
# / 2.47 ms, 16 of 2048 x 2048 over 20 rows 0.583 / 0.566 / 0.570 (PERF.md,
# PR 46): they hide at each, within a reading's spread.
BLOCK_BYTES = 2 << 20

# rows are padded to whole sublane tiles of the narrowest type a cell serves
_ROW_TILE = 16


def f_tile(d: int, f: int, itemsize: int) -> int:
    """Columns of ``gate`` / ``up`` (rows of ``down``) a grid step takes: the
    most whole lane tiles that divide ``f`` within ``BLOCK_BYTES`` a block
    (one lane tile at least; all of ``f`` where it is no whole number of
    lane tiles: interpret mode at rehearsal widths)."""
    if f % 128:
        return f
    fits = [t for t in range(128, f + 1, 128)
            if f % t == 0 and d * t * itemsize <= BLOCK_BYTES]
    return max(fits, default=128)


def _experts_kernel(order_ref, count_ref, x_ref, w_ref, g_ref, u_ref, d_ref,
                    o_ref, *, limit):
    del order_ref                      # the index maps read it
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _start_from_zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(i < count_ref[0])
    def _a_tile_of_a_chosen_expert():
        x = x_ref[...]
        mm = lambda a, b: jnp.dot(a, b.astype(a.dtype),
                                  preferred_element_type=jnp.float32)
        h = L.clamped_swiglu(mm(x, g_ref[0]), lambda: mm(x, u_ref[0]),
                             limit).astype(x.dtype)
        o_ref[...] += mm(h, d_ref[0]) * w_ref[0]


# Under a jit of its own, as ``kda_step.py::kda_step_rows``: a model's sparse
# layers call it with one signature, so the kernel and the packing round it
# are traced once a signature a process and lowered once a program.
@functools.partial(jax.jit, static_argnames=("swiglu_limit",))
def held_experts_chosen(gate, up, down, x, local, w, *, swiglu_limit=0.0):
    """The held experts' partial sum for a decode tick's rows: ``gate, up
    [n, d, f]``, ``down [n, f, d]``, ``x [N, d]``, ``local [N, k]`` int32
    (``n``: the assignment is not this chip's, or its row is parked), ``w
    [N, k]`` float32 -> ``[N, d]`` in ``x``'s type:
    ``HeldExperts._dense``'s, from the weights of the chosen experts
    alone."""
    n, d, f = gate.shape
    N = x.shape[0]
    ft = f_tile(d, f, gate.dtype.itemsize)
    nF = f // ft
    onehot = local[:, :, None] == jnp.arange(n)[None, None, :]
    # the experts an assignment fell on first, in order, then the last of
    # them again (expert 0 where none was chosen), and how many there are
    order, count = compact_rows(jnp.any(onehot, axis=(0, 1)), n)
    we = jnp.sum(jnp.where(onehot, w[:, :, None], 0.0), axis=1)    # [N, n]
    pad = -N % _ROW_TILE
    xp = jnp.pad(x, ((0, pad), (0, 0)))
    # an expert's weights of the rows as a column a block: [n, N, 1]
    wt = jnp.pad(we, ((0, pad), (0, 0))).T[:, :, None].astype(jnp.float32)

    # the i-th chosen expert's j-th tile, and past the last chosen expert
    # the tile the last live step named
    tile = lambda i, j, count: jnp.where(i < count[0], j, nF - 1)
    cols = pl.BlockSpec(
        (1, d, ft), lambda i, j, order, count: (order[i], 0,
                                                tile(i, j, count)))
    rows = pl.BlockSpec(
        (1, ft, d), lambda i, j, order, count: (order[i],
                                                tile(i, j, count), 0))
    whole = pl.BlockSpec(xp.shape, lambda i, j, order, count: (0, 0))
    block = d * ft * gate.dtype.itemsize
    y = pl.pallas_call(
        functools.partial(_experts_kernel, limit=swiglu_limit),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, nF),
            in_specs=[whole,
                      pl.BlockSpec((1, xp.shape[0], 1),
                                   lambda i, j, order, count: (order[i], 0,
                                                               0)),
                      cols, cols, rows],
            out_specs=whole),
        out_shape=jax.ShapeDtypeStruct(xp.shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # three blocks in two buffers each, the rows, the result and
            # the products' intermediates
            vmem_limit_bytes=6 * block + (16 << 20)),
        name="dcp_held_experts",
        interpret=_use_interpret(),
    )(order, count, xp, wt, gate, up, down)
    return y[:N].astype(x.dtype)
