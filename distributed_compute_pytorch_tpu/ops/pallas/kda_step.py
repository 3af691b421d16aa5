"""The KDA state's one-token step as ONE Pallas kernel.

``ops/attention.py::kda_step`` is one token of the delta rule with a
per-channel decay. XLA compiles it to three passes over the state (the ``k .
S`` reduction must be complete before any element of ``S'`` is written, so it
cannot be fused into the update; PERF.md, PR 44) and rewrites a parked row's
state like a live one's. ``dcp_kda_step`` moves a row's state HBM -> VMEM ->
HBM once and moves nothing for a parked row:

- grid ``(rows, H / HEADS_PER_STEP)``, both in order; a block of the state is
  ``[1, hb, dk, dv]`` float32 and the output ALIASES the input, so a block
  no step visits is what it was, bit for bit;
- the rows in the plan go in COMPACTED as scalar prefetch (the live rows
  first, in order, then the last of them again; ``decode_attention.py``'s
  idiom, PR 37): step ``i`` of the grid advances the ``i``-th live row, and
  every step past the last live row names the block the last live step
  named, so it issues no copy in either direction and computes nothing;
- what a head needs beside its state are four vectors along ``dk`` (``q``,
  ``k``, ``e^g``, ``beta k``) and ``v``; they arrive as rows (``dk`` on the
  lanes) and are turned once a head, so that each is also a column against
  the state's ``[dk, dv]``;
- the arithmetic is ``kda_step``'s in the form whose two reductions read the
  state AS IT ARRIVES: with ``S1 = Diag(e^g) S``, ``w = S1^T k = S^T (e^g
  k)`` and ``o = S'^T q = S^T (e^g q) + (k . q) beta (v - w)``, so ONE
  product of the rows ``[e^g q; e^g k]`` against ``S`` (float32 at
  ``Precision.HIGHEST``, as ``kda_step`` states its own) gives both, and
  the update ``S' = Diag(e^g) S + (beta k)(v - w)^T`` is one element-wise
  pass over the block in VMEM. On a v5e the VPU form of the two reductions
  (seven operations a vector register of state) runs 2.47 ms a layer of
  160 rows where this one runs 2.31 and a copy of the blocks alone 2.30
  (PERF.md, PR 45).

Build cost is a constraint here too (ROADMAP A4): the entry is under a
``jit`` of its own, so a model's layers share one trace a signature and one
lowering a program, and the body is one loop over a step's heads, unrolled
only at lowering (so that the scheduler overlaps the heads: as a loop it
runs 4.75 ms): its jaxpr has the same equations at every ``B``.
"""

import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
    _use_interpret)

# Heads a grid step: a step's fixed cost has to hide under its copies. On a
# v5e, 160 rows x 64 heads of 128 x 128, 4 / 8 / 16 heads a step: 3.22 / 2.77
# / 2.47 ms a layer in the VPU form, 2.49 / 2.31 at 8 / 16 in this one
# (PERF.md, PR 45). 16 heads are 1 MB in and 1 MB out a step.
HEADS_PER_STEP = 16

# rows of a head's block of vectors: q, k, e^g, beta k, then v; padded to a
# whole sublane tile
_Q, _K, _E, _BK, _V, _VECTORS = 0, 1, 2, 3, 4, 8


def _dot(x, S):
    """``x [m, dk]`` against ``S [dk, dv]`` -> ``[m, dv]``: a product of
    float32 operands as ``kda_step`` states it."""
    return lax.dot_general(x, S, (((1,), (0,)), ((), ())),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _step_kernel(rows_ref, n_ref, s_ref, x_ref, o0_ref, s_out, o_out):
    del rows_ref, o0_ref             # the index maps read one; the other is o_out
    i, j = pl.program_id(0), pl.program_id(1)
    hb = s_ref.shape[1]
    n = n_ref[0]

    @pl.when(i < n)
    def _advance_a_live_row():
        def head(h, _):
            x = x_ref[0, h]                                  # [8, dk]
            col = x.T                                        # [dk, 8]
            S = s_ref[0, h]
            # every row times e^g against the state as it arrived: row _K
            # is w, row _Q what o reads of the old state
            r = _dot(x * x[_E:_E + 1], S)
            d = x[_V:_V + 1] - r[_K:_K + 1]                  # v - w
            s_out[0, h] = S * col[:, _E:_E + 1] + col[:, _BK:_BK + 1] * d
            o_out[0, 0, pl.ds(h, 1), :] = r[_Q:_Q + 1] + d * jnp.sum(
                x[_BK:_BK + 1] * x[_Q:_Q + 1], axis=1, keepdims=True)
            return 0

        lax.fori_loop(0, hb, head, 0, unroll=True)

    # no live row at all: every step names block (0, last), which is then
    # copied in once and written back once, so it has to be carried across
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _carry_the_one_block_across():
        s_out[...] = s_ref[...]
        o_out[...] = jnp.zeros_like(o_out)


def compact_rows(live, B: int):
    """``live [B]`` (above 0.5 in the plan; None: every row) -> ``(rows
    int32 [B], n int32 [1])``: the live rows first, in order, then the last
    of them again (row 0 where none is live), and how many are live."""
    if live is None:
        return jnp.arange(B, dtype=jnp.int32), jnp.full((1,), B, jnp.int32)
    on = live > 0.5
    n = jnp.sum(on, dtype=jnp.int32)
    first = jnp.argsort(jnp.logical_not(on), stable=True).astype(jnp.int32)
    at = jnp.minimum(jnp.arange(B, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    return jnp.where(n > 0, first[at], 0), n.reshape(1)


# Under a jit of its own, as ``kda_scan.py::kda_chunk_scan``: a model's KDA
# layers call it with one signature, so the kernel and the packing round it
# are traced once a signature a process and lowered once a program.
@jax.jit
def kda_step_rows(S, q, k, v, g, beta, live):
    """One token of the delta rule with a per-channel decay for the rows in
    the plan: ``S [B, H, dk, dv]``, ``q, k, g [B, H, dk]``, ``v [B, H,
    dv]``, ``beta [B, H]``, all float32, ``live [B]`` (above 0.5: the row
    advances) or None (every row does) -> ``(o [B, H, dv], S')``. A live
    row's are :func:`ops.attention.kda_step`'s; a parked row's state is bit
    for bit what it was and its ``o`` is zeros. ``S'`` takes ``S``'s
    buffer where the caller lets it go."""
    B, H, dk, dv = S.shape
    assert dk == dv, (dk, dv)
    hb = math.gcd(H, HEADS_PER_STEP)
    nH = H // hb
    rows, n = compact_rows(live, B)
    x = jnp.stack([q, k, jnp.exp(g), beta[..., None] * k, v]
                  + [jnp.zeros_like(q)] * (_VECTORS - 5), axis=2)

    # one index map for the state, the vectors and o (o a block of heads at
    # a time, [B, H / hb, hb, dv]: a block's last two dimensions are then
    # the array's whatever hb is): the i-th live row's j-th block of heads,
    # and past the last live row the block the last live step named
    def at(i, j, rows, n):
        return rows[i], jnp.where(i < n[0], j, nH - 1), 0, 0

    state = pl.BlockSpec((1, hb, dk, dv), at)
    out = pl.BlockSpec((1, 1, hb, dv), at)
    S, o = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, nH),
            in_specs=[state,
                      pl.BlockSpec((1, hb, _VECTORS, dk), at), out],
            out_specs=[state, out]),
        out_shape=[jax.ShapeDtypeStruct(S.shape, jnp.float32),
                   jax.ShapeDtypeStruct((B, nH, hb, dv), jnp.float32)],
        # the state in place; o over zeros, which a parked row's stay
        input_output_aliases={2: 0, 4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="dcp_kda_step",
        interpret=_use_interpret(),
    )(rows, n, S, x, jnp.zeros((B, nH, hb, dv), jnp.float32))
    return o.reshape(B, H, dv), S
