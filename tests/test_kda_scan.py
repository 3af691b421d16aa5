"""``ops/pallas/kda_scan.py``: the KDA recurrence over a window as one
kernel (interpret mode on the CPU) against the two forms it replaces on a
TPU and equals everywhere: the ``lax.scan`` of ``kda_chunk``
(``ops/attention.py::kda_window``'s portable path) and ``T`` steps of
``kda_step``, on outputs AND on the state a window ends with; and what
keeps it inside the build-cost gate (ROADMAP A4): a body that does not
grow with the window, one trace and one jitted function for all of a
model's layers, under the scopes the benchmark reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models.hybrid import KDA_CHUNK, KDA_SUB
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.pallas import kda_scan
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request, ladder_shapes)
from tests.test_flash_attention import _kernel_sizes, _sub_jaxprs
from tests.test_tracing_scopes import _has, _locations

H, DK, R = 4, 16, 8
LOW = -5.0
KW = dict(lower_bound=LOW, chunk=KDA_CHUNK, sub=KDA_SUB)

# float32 on both sides from the same inputs: what differs is the order of
# the sums (a chunk's triangular solve against the token's own step). The
# outputs are of size ~0.3 and the state of size ~1; 2e-5 is what
# tests/test_hybrid_glm.py allows the chunked form, and a hundred times
# under what bfloat16 products do (the control below).
TOL = 2e-5


def inputs(B, T, seed=0, at_bound=False, deep=False, beta_max=1.0):
    """Convolved projections, the gate's factors, ``beta``: the arguments
    of ``kda_window`` but ``real``. ``dt_bias`` at the family's std of 3
    (decays from fast to slow), or ``at_bound`` 40 in every channel: every
    decay AT the gate's lower bound, or ``deep`` spread evenly from -6 to
    40: under the gate with no floor, log-decays from -0.002 down to -40 a
    token and past it. ``beta`` in ``(0, beta_max)``."""
    ks = jax.random.split(jax.random.key(seed), 8)
    aq, ak, av = (jax.nn.silu(jax.random.normal(k, (B, T, H * DK)))
                  for k in ks[:3])
    f_low = jax.random.normal(ks[3], (B, T, R))
    f_up = jax.random.normal(ks[4], (R, H * DK)) * R ** -0.5
    dt_bias = (jnp.full((H * DK,), 40.0) if at_bound else
               jax.random.uniform(ks[5], (H * DK,), minval=-6.0, maxval=40.0)
               if deep else 3.0 * jax.random.normal(ks[5], (H * DK,)))
    rate = jnp.exp(0.3 * jax.random.normal(ks[6], (H,)))
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[7], (B, T, H)))
    return aq, ak, av, f_low, f_up, dt_bias, rate, beta


def token_steps(aq, ak, av, f_low, f_up, dt_bias, rate, beta, real,
                lower_bound=LOW):
    """``T`` steps of ``kda_step``; a pad leaves the state as it was."""
    B, T, _ = aq.shape
    heads = lambda t: t.reshape(t.shape[:-1] + (H, DK))
    f = jnp.dot(f_low, f_up, preferred_element_type=jnp.float32) + dt_bias
    q, k, v, g = A.kda_heads(heads(aq), heads(ak), heads(av), heads(f),
                             rate[:, None], lower_bound)

    def step(S, xs):
        q, k, v, g, beta, real = xs
        o, S = A.kda_step(S, q, k, v, g * real[:, None, None],
                          beta * real[:, None])
        return S, o

    S, o = jax.lax.scan(
        step, jnp.zeros((B, H, DK, DK)),
        tuple(t.swapaxes(0, 1) for t in (q, k, v, g, beta, real)))
    return o.swapaxes(0, 1), S


def rows_of(B, T):
    """Real lengths of the rows of one call: the whole window, then rows
    that end inside a chunk, after three tokens, and before the first."""
    lens = np.array([T, max(T - 37, 1), 3, 0])[:B]
    return (jnp.arange(T)[None] < lens[:, None]).astype(jnp.float32), lens


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


@pytest.mark.parametrize("B", [1, 2, 4])
@pytest.mark.parametrize("T", [64, 128, 37, 100])
def test_the_kernel_equals_the_scan_of_chunks_and_the_token_steps(B, T):
    """Windows that are and are not whole chunks, rows of different real
    lengths in one call (trailing pads leave the state at the row's last
    real token): outputs at every real token and the final state."""
    args = inputs(B, T, seed=T + B)
    real, lens = rows_of(B, T)
    o, S = kda_scan.kda_chunk_scan(*args, real, **KW)
    assert o.shape == (B, T, H, DK) and S.shape == (B, H, DK, DK)
    assert o.dtype == S.dtype == jnp.float32
    o_scan, S_scan = A.kda_window(*args, real, **KW)
    o_step, S_step = token_steps(*args, real)
    on = real[..., None, None]
    assert worst(o * on, o_scan * on) < TOL and worst(S, S_scan) < TOL
    assert worst(o * on, o_step * on) < TOL and worst(S, S_step) < TOL
    if (lens == 0).any():
        assert not S[int(np.argmin(lens))].any()


@pytest.mark.parametrize("B,T", [(1, 128), (2, 100)])
def test_decays_at_the_gates_lower_bound_stay_in_range(B, T):
    """Every channel decays by ``e^-5`` a token, ``e^-320`` over a chunk:
    the plain split ``(x e^b)(y e^-b)`` overflows float32 after 18 tokens.
    The kernel splits the exponent at a sub-chunk's FIRST token, so no
    factor is under ``e^-75``: it stands at the token steps' float32. (The
    scan of ``kda_chunk`` splits at the token before, and what it
    multiplies by ``e^-80`` underflows where ``|x| < 6e-4``: at this bound
    it is 3e-4 off outputs of 6e-3, so it is no yardstick here.)"""
    args = inputs(B, T, seed=7, at_bound=True)
    real, _ = rows_of(B, T)
    o, S = kda_scan.kda_chunk_scan(*args, real, **KW)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(S)).all()
    o_step, S_step = token_steps(*args, real)
    on = real[..., None, None]
    assert float(jnp.max(jnp.abs(o_step * on))) > 100 * TOL
    assert worst(o * on, o_step * on) < TOL and worst(S, S_step) < TOL


# the decay gate's two forms (a floor of -5, or the softplus gate with none),
# each at decays it may reach, and both ranges of beta
GATES = {"floor": (LOW, False), "no_floor": (None, False),
         "no_floor_deep": (None, True)}


@pytest.mark.parametrize("B,T", [(1, 128), (3, 100)])
@pytest.mark.parametrize("beta_max", [1.0, 2.0])
@pytest.mark.parametrize("gate", list(GATES))
def test_chunk_form_and_kernel_equal_the_token_steps_whatever_the_gate(
        gate, beta_max, B, T):
    """Both forms of the gate and both ranges of ``beta`` (GLM-5.3-Flash's
    is ``floor`` at 1, Solar-Open2's ``no_floor`` at 2): the scan of
    ``kda_chunk`` and the kernel against ``T`` steps of ``kda_step``,
    finite everywhere, at the tolerance the bounded form has always had.
    ``no_floor_deep``: log-decays down to -40 a token and past it, -640 over
    a sub-chunk, where any form that divides a sub-chunk's decays out
    overflows float32 (the control at the end); the form without a floor
    takes differences only, so a decay that deep just underflows to the
    zero it is. ``beta`` up to 2 doubles the entries of the triangular
    system and changes nothing else."""
    lower_bound, deep = GATES[gate]
    args = inputs(B, T, seed=T + B, deep=deep, beta_max=beta_max)
    real, _ = rows_of(B, T)
    kw = dict(KW, lower_bound=lower_bound)
    o_step, S_step = token_steps(*args, real, lower_bound=lower_bound)
    on = real[..., None, None]
    assert float(jnp.max(jnp.abs(o_step * on))) > 100 * TOL
    for form in (kda_scan.kda_chunk_scan, A.kda_window):
        o, S = form(*args, real, **kw)
        assert np.isfinite(np.asarray(o)).all(), form
        assert np.isfinite(np.asarray(S)).all(), form
        assert worst(o * on, o_step * on) < TOL, form
        assert worst(S, S_step) < TOL, form
    if deep:
        heads = lambda t: t.reshape(t.shape[:-1] + (H, DK))
        f = jnp.dot(args[3], args[4]) + args[5]
        q, k, v, g = A.kda_heads(*(heads(t) for t in args[:3]), heads(f),
                                 args[6][:, None], None)
        assert float(g.min()) < -40 and float(g.max()) > -0.01
        # the control: the form that needs a floor, on these decays
        o_floor, _ = A.kda_chunk(jnp.zeros((B, H, DK, DK)), q[:, :64],
                                 k[:, :64], v[:, :64], g[:, :64],
                                 args[7][:, :64], KDA_SUB, floor=True)
        assert not np.isfinite(np.asarray(o_floor)).all()


def test_products_in_bfloat16_fail_the_tolerance(monkeypatch):
    """The control: the same kernel with the operands of its float32
    products rounded to bfloat16 (one pass of the MXU in place of
    ``Precision.HIGHEST``) is outside the tolerance the sound kernel meets,
    on the outputs and on the state."""
    def one_pass(a, b, dims):
        lo = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
        return jax.lax.dot_general(lo(a), lo(b), dims,
                                   preferred_element_type=jnp.float32)

    args = inputs(2, 128, seed=3)
    real, _ = rows_of(2, 128)
    o_step, S_step = token_steps(*args, real)
    monkeypatch.setattr(kda_scan, "_dot", one_pass)
    # the function under the jit: a trace of its own, not a cached one
    o, S = kda_scan.kda_chunk_scan.__wrapped__(*args, real, **KW)
    on = real[..., None, None]
    assert worst(o * on, o_step * on) > 10 * TOL
    assert worst(S, S_step) > 10 * TOL


# (equations, dot_generals) of the body as it was measured inside the
# set-up budget (PERF.md, PR 43), at 8 heads a grid step (a head's lane
# tiles are cut out of a block and laid back one by one: 8 equations a head).
# Thirteen products: the gate's, the running sum, a sub-chunk's grams, two a
# doubling at each of the inverse's two levels and two between them, the
# state's read, delta, the output's, the state's update.
_BODY_AT_MOST = (286, 13)
# the form for a gate with no floor (PR 44): the same thirteen products and
# one more loop of one body, a sub-chunk's sixteen columns (49 equations)
_BODY_AT_MOST_NO_FLOOR = (335, 13)


@pytest.mark.parametrize("lower_bound,at_most", [
    (LOW, _BODY_AT_MOST), (None, _BODY_AT_MOST_NO_FLOOR)])
def test_kernel_body_does_not_grow_with_rows_or_window(lower_bound, at_most):
    """The build-cost guard (the form of ``tests/test_flash_attention.py::
    test_kernel_body_does_not_grow_with_block_offset_or_window``): the
    kernel is traced and lowered in every set-up, once a program of the
    admission ladder, so its body is loops of one body: the same equations
    at the ladder's smallest, widest and longest programs, and no more
    than what was measured."""
    sizes = set()
    for B, T in ((1, 2048), (4, 2048), (1, 16384)):
        z = lambda *s: jnp.zeros(s, jnp.bfloat16)
        args = (z(B, T, 64 * 128), z(B, T, 64 * 128), z(B, T, 64 * 128),
                z(B, T, 128), z(128, 64 * 128), jnp.zeros((64 * 128,)),
                jnp.ones((64,)), jnp.zeros((B, T, 64)), jnp.ones((B, T)))
        sizes.add(_kernel_sizes(
            lambda *a: kda_scan.kda_chunk_scan(
                *a, **dict(KW, lower_bound=lower_bound)),
            *args)["dcp_kda_chunk_scan"])
    assert len(sizes) == 1, sizes
    (eqns, dots), = sizes
    assert dots == at_most[1] and eqns <= at_most[0], (eqns, dots)


def _pjit_calls(jaxpr, name, out):
    """The ``pjit`` equations called ``name``, nested bodies included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pjit", "jit") and eqn.params["name"] == name:
            out.append(eqn)
        else:
            for sub in _sub_jaxprs(eqn):
                _pjit_calls(sub, name, out)
    return out


def test_four_kda_layers_share_one_trace_under_their_scopes(monkeypatch):
    """A four-layer admission of KDA blocks with the kernel chosen (the
    choice is by backend and shape; the test makes it): four calls of ONE
    jitted function, traced once a program, and the kernel's operations under
    ``attn_linear/linear_scan`` in the lowered program, which is where
    ``linear_scan_share.admit`` and ``kda_scan_kernel_share.admit`` look."""
    monkeypatch.setattr(A, "_kda_kernel_ok", lambda head_dim: True)
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("linear_attention",) * 4 + ("full_attention",),
        mlp_layer_types=("dense",) * 5,
        num_heads=4, d_model=64, d_ff=128, norm_placement="pre",
        qk_norm=False, kda_heads=4, kda_head_dim=16, kda_gate_rank=8)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    # (the entry's own traces of another test's engine at these shapes,
    # in this process, would leave nothing to trace here)
    kda_scan.kda_chunk_scan.clear_cache()
    traced, body = [], kda_scan._scan_kernel
    monkeypatch.setattr(kda_scan, "_scan_kernel", lambda *refs, **kw: (
        traced.append(refs[0].shape), body(*refs, **kw))[1])
    out = cb.serve([Request(tokens=list(range(1, 21)), max_new=2)])
    assert len(out[0]) == 2
    fn, args, kwargs = cb._program_sigs["admit"]
    calls = _pjit_calls(jax.make_jaxpr(
        lambda *a: fn(*a, **kwargs))(*args).jaxpr, "kda_chunk_scan", [])
    assert len(calls) == 4
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    # one trace a program of the admission ladder, not one a layer
    assert len(traced) == len(ladder_shapes(cb._admit_ladder)), traced
    locs = _locations(fn.lower(*args, **kwargs))
    assert _has(locs, "admit", "attn", "attn_linear", "linear_scan",
                "jit(kda_chunk_scan)")
    assert not any("jit(kda_chunk_scan)" in n and "linear_scan" not in n
                   for n in locs)
