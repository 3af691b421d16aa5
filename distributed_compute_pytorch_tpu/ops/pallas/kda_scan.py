"""The KDA recurrence over a whole window as ONE Pallas kernel.

``ops/attention.py::kda_chunk`` is one chunk of the delta rule with a
per-channel decay; a window is a ``lax.scan`` of it, and on a TPU that scan
is a chain of some twenty small fusions a chunk whose cost is their launch
latency, not their arithmetic (PERF.md, PR 41). ``dcp_kda_chunk_scan`` runs
the same chunks with a head's state ``[dv, dk]`` float32 resident in VMEM
from a window's first chunk to its last:

- grid ``(B, H / HEADS_PER_STEP [parallel], chunks [sequential])``; the
  state is the kernel's second output, whose block does not move along the
  chunk axis, so it is written to HBM once, when the row's heads change;
- the operands keep the model's layout (``[B, T, H * dk]``, a head's
  channels contiguous), so nothing is transposed in HBM on the way in or
  out: a block is ``[C, heads * dk]`` and its heads are whole lane tiles;
- a grid step turns the CONVOLVED projections of its heads and one chunk
  (the activations' type) into ``q, k, v, g`` float32
  (:func:`ops.attention.kda_heads`: nothing of a window exists in float32
  outside a block), then does what ``kda_chunk`` does: the running decay,
  the two decay grams a sub-chunk at a time, the inverse of the unit
  triangular system, the products that read the state and its update;
- every product of float32 operands at ``Precision.HIGHEST``, as
  ``kda_chunk`` states it.

A v5e runs such a product in six bf16 passes and is bound by the MXU's
issue slots: a product costs by the ROWS of its left operand (12 slots a
group of 8 rows) and of its right one (6), whatever else (the compiler's
static schedule, PERF.md PR 43). So the forms here are the ones with the
fewest rows: the state is kept TRANSPOSED (its decay is then a row's
broadcast, and ``K e^b`` and ``Q e^b`` go against it in one product), the
inverse is built in two levels (:func:`_scan_kernel`), and ``delta`` comes
from ``inv Diag(beta) (V - (K e^b) S)``, not from ``inv [V, K e^b]``.

Build cost is a constraint here (ROADMAP A4): the entry is under a ``jit``
of its own, so a model's layers share one trace a signature and one
lowering a program, and the body is loops of ONE body (sub-chunks,
doublings) over values batched by head: its jaxpr has the same equations
at every ``(B, T)``.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_compute_pytorch_tpu.ops.attention import (
    kda_heads, whole_chunks)
from distributed_compute_pytorch_tpu.ops.pallas.flash_attention import (
    _use_interpret)

# Heads a grid step: a step's fixed cost is shared and the scheduler packs
# independent heads' products. On a v5e, (1, 16384) x 64 heads of 128, 2 / 4 /
# 8 / 16 heads a step: 32.8 / 24.8 / 21.8 / 20.8 ms (the scan of kda_chunk:
# about 77; PERF.md, PR 43). 8: a head adds eight equations to the body, and
# the last 4% are not worth 64 of them in every set-up.
HEADS_PER_STEP = 8

# contracting and batch dimensions of the products, all batched by head
_NN = (((2,), (1,)), ((0,), (0,)))      # [h, m, k] x [h, k, n] -> [h, m, n]
_NT = (((2,), (2,)), ((0,), (0,)))      # [h, m, k] x [h, n, k] -> [h, m, n]
_TN = (((1,), (1,)), ((0,), (0,)))      # [h, k, m] x [h, k, n] -> [h, m, n]


def _dot(a, b, dims):
    """A product of float32 operands as ``kda_chunk`` states it."""
    return lax.dot_general(a, b, dims, precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)


def _neumann(M, eye, mul, index: int):
    """``(I - M)^-1 = (I + M)(I + M^2)(I + M^4)...`` of an ``M`` whose
    ``index``-th power is zero, under the product ``mul``: a loop of one
    body, two products a doubling."""
    def doubling(_, carry):
        inv, P = carry
        P = mul(P, P)
        return inv + mul(inv, P), P

    return lax.fori_loop(0, (index - 1).bit_length() - 1, doubling,
                         (eye + M, M))[0]


def _scan_kernel(aq_ref, ak_ref, av_ref, fl_ref, fu_ref, bias_ref, rate_ref,
                 side_ref, o_ref, st_ref, q_scr, k_scr, b_scr, a_scr, bm_scr,
                 *, lower_bound, sub):
    hb, C, dk = q_scr.shape
    H = side_ref.shape[2] - 1
    f32 = jnp.float32

    @pl.when(pl.program_id(2) == 0)
    def _a_window_starts_from_no_state():
        st_ref[...] = jnp.zeros_like(st_ref)

    # the heads of this block: q, k, v and the log-decays, float32. The
    # gate's product goes through scratch before its bias: Mosaic (jax
    # 0.9.0) folds a bias broadcast along rows into a batched product's
    # accumulator and then fails to slice it a head at a time
    b_scr[...] = lax.dot_general(
        jnp.broadcast_to(fl_ref[0][None], (hb,) + fl_ref.shape[1:]),
        fu_ref[...], _NN, preferred_element_type=f32)
    f = b_scr[...] + bias_ref[...]
    # a block keeps the model's layout, [C, hb * dk] with a head's channels
    # contiguous (nothing is transposed in HBM on the way in or out): its
    # heads are whole lane tiles, cut out and laid side by side again
    heads = lambda ref: jnp.stack(
        [ref[0, :, i * dk:(i + 1) * dk] for i in range(hb)])
    q, k, v, g = kda_heads(heads(aq_ref), heads(ak_ref), heads(av_ref), f,
                           rate_ref[...], lower_bound)
    # per token: beta of each head (0 at a pad), then whether it is real
    side = side_ref[0]                                          # [C, H + 1]
    head = (pl.program_id(1) * hb
            + lax.broadcasted_iota(jnp.int32, (hb, C, H + 1), 0))
    beta = jnp.sum(jnp.where(
        lax.broadcasted_iota(jnp.int32, (hb, C, H + 1), 2) == head,
        side[None], 0.0), -1, keepdims=True)                    # [hb, C, 1]
    g = g * lax.slice_in_dim(side, H, H + 1, axis=1)[None]

    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    b = _dot(jnp.broadcast_to((row >= col).astype(f32)[None], (hb, C, C)),
             g, _NN)                            # the running sum of g
    q_scr[...], k_scr[...], b_scr[...] = q, k, b

    # the decay grams A[t, j] = (k_t e^{b_t}) . (k_j e^{-b_j}) and B_m (q for
    # the first k), sub-chunk by sub-chunk of t: the exponent is split at
    # the sub-chunk's first token, so e^{b_t - b_0} <= 1 and, for the j it
    # may see, e^{b_0 - b_j} <= e^{sub * |lower_bound|}. A gate with no
    # floor (lower_bound None) has no such bound: the split then serves the
    # j of EARLIER sub-chunks alone (both factors at most 1), and inside
    # the sub-chunk the exponent is b_t - b_j itself, a column j at a time
    # (ops/attention.py::_decay_gram)
    token = lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    floor = lower_bound is not None

    def gram(p, _):
        at = pl.ds(pl.multiple_of(p * sub, sub), sub)
        b_p = b_scr[:, at, :]
        first = b_p[:, :1, :]
        w = jnp.exp(b_p - first)
        seen = jnp.where(token >= (p + int(floor)) * sub, 0.0,
                         k_scr[...] * jnp.exp(first - b_scr[...]))
        G = _dot(jnp.concatenate([k_scr[:, at, :] * w, q_scr[:, at, :] * w],
                                 axis=1), seen, _NT)            # [hb, 2 sub, C]
        Ga, Gb = G[:, :sub], G[:, sub:]
        if not floor:
            k_p, q_p = k_scr[:, at, :], q_scr[:, at, :]
            t_own = lax.broadcasted_iota(jnp.int32, (sub, dk), 0)
            column_of = lax.broadcasted_iota(jnp.int32, (sub, C), 1)

            def column(j, acc):
                one = pl.ds(p * sub + j, 1)
                e = k_scr[:, one, :] * jnp.exp(jnp.where(
                    t_own >= j, b_p - b_scr[:, one, :], -jnp.inf))
                here = column_of == p * sub + j
                return tuple(
                    jnp.where(here, jnp.sum(x * e, -1, keepdims=True), a)
                    for x, a in zip((k_p, q_p), acc))

            Ga, Gb = lax.fori_loop(0, sub, column, (Ga, Gb))
        a_scr[:, at, :], bm_scr[:, at, :] = Ga, Gb
        return 0

    lax.fori_loop(0, C // sub, gram, 0)

    # (I - M)^-1 of the nilpotent M = -Diag(beta) tril(A, -1), in two levels,
    # so that few rows stream through its products (below). D, the sub x sub
    # blocks on M's diagonal, side by side as a strip [sub, C]: against
    # another's blocks on a diagonal, a strip's product is the blocks'
    # products. Then with K = (I - D)^-1 (M - D), nilpotent by blocks,
    # (I - M)^-1 = (I - K)^-1 (I - D)^-1.
    eye = (row == col).astype(f32)
    on_diagonal = row // sub == col // sub
    strip = lambda m: sum(m[..., i * sub:(i + 1) * sub, :]
                          for i in range(C // sub))
    blocks = lambda s: jnp.where(
        on_diagonal, jnp.concatenate([s] * (C // sub), axis=1), 0.0)
    M = -beta * jnp.where(row > col, a_scr[...], 0.0)
    D = jnp.where(on_diagonal, M, 0.0)
    inv_D = blocks(_neumann(
        strip(D), strip(eye), lambda a, b: _dot(a, blocks(b), _NN), sub))
    inv = _dot(_neumann(_dot(inv_D, M - D, _NN), eye,
                        lambda a, b: _dot(a, b, _NN), C // sub), inv_D, _NN)

    # what depends on the state (kept transposed, [dv, dk]: its decay is
    # then a row's broadcast): delta = inv Diag(beta) (V - (K e^b) S), o =
    # (Q e^b) S + tril(B_m) delta, S' = Diag(e^{b_C}) S + (K e^{b_C - b})^T
    # delta
    eb = jnp.exp(b)
    St = st_ref[0]
    X = _dot(jnp.concatenate([k * eb, q * eb], axis=1), St, _NT)
    delta = _dot(inv, beta * (v - X[:, :C]), _NN)
    o = X[:, C:] + _dot(jnp.where(row >= col, bm_scr[...], 0.0), delta, _NN)
    o_ref[0] = jnp.concatenate([o[i] for i in range(hb)], axis=-1)
    b_end = b[:, C - 1:, :]
    st_ref[0] = St * jnp.exp(b_end) + _dot(delta, k * jnp.exp(b_end - b), _TN)


# Under a jit of its own, as ``flash_attention.py::_flash_bwd``: a model's
# KDA layers call it with one signature, so the kernel and the passes round
# it are traced once a signature a process and lowered once a program.
@functools.partial(jax.jit, static_argnames=("lower_bound", "chunk", "sub"))
def kda_chunk_scan(aq, ak, av, f_low, f_up, dt_bias, rate, beta, real, *,
                   lower_bound: float | None, chunk: int, sub: int):
    """The delta rule with a per-channel decay over whole windows, from the
    zero state: ``aq, ak, av [B, T, H * dk]`` the CONVOLVED projections
    (the activations' type), ``f_low [B, T, R]`` and ``f_up [R, H * dk]``
    the decay gate's two factors, ``dt_bias [H * dk]`` and ``rate [H]``
    (``exp(A_log)``) float32, ``beta [B, T, H]`` float32, ``real [B, T]``
    (1 at a token, 0 at a pad: a pad gets ``beta = 0`` and no decay),
    ``lower_bound`` the decay gate's floor or None for the gate that has
    none (:func:`ops.attention.kda_heads`) ->
    ``(o [B, T, H, dk] float32, S [B, H, dk, dk] float32)``, ``S`` the
    state after each row's last real token. Equal to a ``lax.scan`` of
    :func:`ops.attention.kda_chunk` over chunks of ``chunk`` tokens."""
    B, T, R = f_low.shape
    H = beta.shape[-1]
    dk = aq.shape[-1] // H
    hb = math.gcd(H, HEADS_PER_STEP)
    pad = lambda t: whole_chunks(t, chunk)
    Tp = pad(real).shape[1]
    side = pad(jnp.concatenate(
        [beta * real[..., None], real[..., None]], -1).astype(jnp.float32))
    per_head = lambda t: jnp.broadcast_to(
        t.astype(jnp.float32).reshape(H, 1, -1), (H, 1, dk))
    block = pl.BlockSpec((1, chunk, hb * dk), lambda b, h, c: (b, c, h))
    head = lambda *shape: pl.BlockSpec((hb,) + shape,
                                       lambda b, h, c: (h, 0, 0))
    o, St = pl.pallas_call(
        functools.partial(_scan_kernel, lower_bound=lower_bound, sub=sub),
        grid=(B, H // hb, Tp // chunk),
        in_specs=[block, block, block,
                  pl.BlockSpec((1, chunk, R), lambda b, h, c: (b, c, 0)),
                  head(R, dk), head(1, dk), head(1, dk),
                  pl.BlockSpec((1, chunk, H + 1), lambda b, h, c: (b, c, 0))],
        out_specs=[block,
                   pl.BlockSpec((1, hb, dk, dk), lambda b, h, c: (b, h, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, Tp, H * dk), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, dk, dk), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, chunk, dk), jnp.float32)] * 3
        + [pltpu.VMEM((hb, chunk, chunk), jnp.float32)] * 2,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="dcp_kda_chunk_scan",
        interpret=_use_interpret(),
    )(pad(aq), pad(ak), pad(av), pad(f_low),
      f_up.astype(f_low.dtype).reshape(R, H, dk).swapaxes(0, 1),
      per_head(dt_bias), per_head(rate), side)
    return o[:, :T].reshape(B, T, H, dk), St.swapaxes(2, 3)
