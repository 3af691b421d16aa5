"""``ops/pallas/kda_step.py``: the KDA state's one-token step as one kernel
(interpret mode on the CPU) against the form it replaces on a TPU and equals
everywhere, ``ops/attention.py::kda_step`` and a select for parked rows: on
outputs AND on the state, for both forms of the decay gate and ``beta`` up
to 2; that a parked row's state is what it was bit for bit; and what keeps it
inside the build-cost gate (ROADMAP A4): a body that does not grow with the
rows, the state aliased through the call, one trace and one jitted function
for all of a model's layers, under the scope the benchmark reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu import serve as serve_module
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops import attention as A
from distributed_compute_pytorch_tpu.ops.pallas import kda_step
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from tests.test_flash_attention import _size, _sub_jaxprs
from tests.test_kda_scan import _pjit_calls
from tests.test_tracing_scopes import _has, _locations

DK = 128

# float32 on both sides from the same inputs: what differs is the order of a
# reduction's sums (the MXU's passes against XLA's own order), the form of
# o (read from the state as it arrives) and where beta multiplies. The state is of size ~1 and the outputs ~1; 2e-5 is
# what tests/test_kda_scan.py allows the chunked form and a hundred times
# under what bfloat16 products do (the control below).
TOL = 2e-5

# the log-decays of the two forms of the gate (ops/attention.py::kda_heads):
# bounded by a floor, g in [-5, 0); no floor, g = -a softplus(f) from -0.002
# down past where e^g is 0 to float32 (under -104)
GATES = {
    "bounded": lambda z: -5.0 * jax.nn.sigmoid(3.0 * z),
    "no_floor": lambda z: -jax.nn.softplus(40.0 * z + 17.0) * 1.5,
}


def inputs(B, H, gate="bounded", seed=0, beta_max=2.0, dk=DK):
    """A step's arguments as ``models/hybrid.py::_kda_heads`` gives them:
    ``q`` of length ``dk ** -0.5``, ``k`` of unit length, ``beta`` in ``(0,
    beta_max)``; the state of size ~1."""
    ks = jax.random.split(jax.random.key(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    S = jax.random.normal(ks[0], (B, H, dk, dk))
    q = unit(jax.random.normal(ks[1], (B, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[2], (B, H, dk)))
    v = jax.random.normal(ks[3], (B, H, dk))
    g = GATES[gate](jax.random.normal(ks[4], (B, H, dk)))
    beta = beta_max * jax.nn.sigmoid(jax.random.normal(ks[5], (B, H)))
    return S, q, k, v, g, beta


def worst(a, b):
    return float(jnp.max(jnp.abs(a - b)))


@pytest.mark.parametrize("B", [1, 3, 8])
@pytest.mark.parametrize("H", [2, 8])
@pytest.mark.parametrize("gate", list(GATES))
@pytest.mark.parametrize("beta_max", [1.0, 2.0])
def test_the_kernel_equals_the_token_step(B, H, gate, beta_max):
    """Every row live: ``o`` and ``S'`` are ``kda_step``'s, whatever the
    gate's form (the no-floor draw holds decays from -0.002 to under -100,
    where ``e^g`` is 0 to float32) and with ``beta`` up to 2."""
    args = inputs(B, H, gate, seed=B + H, beta_max=beta_max)
    if gate == "no_floor":
        g = args[4]
        assert float(g.max()) > -0.01 and float(jnp.exp(g.min())) == 0.0
    o_ref, S_ref = A.kda_step(*args)
    o, S = kda_step.kda_step_rows(*args, None)
    assert np.isfinite(np.asarray(o)).all()
    assert worst(o, o_ref) < TOL and worst(S, S_ref) < TOL


@pytest.mark.parametrize("live", [
    (1, 0, 1, 1, 0), (0, 1, 1, 1, 1), (1, 1, 1, 0, 0), (0, 0, 0, 0, 1),
    (0, 0, 0, 0, 0), (1, 1, 1, 1, 1), None])
def test_a_parked_rows_state_is_bit_for_bit_what_it_was(live):
    """With a mask: a live row's ``o`` and state are the unmasked run's, a
    parked row's state is its input bit for bit and its ``o`` zero (finite:
    the tail norms it); parked rows first, last, all of them, none."""
    args = inputs(5, 16, seed=11)
    o_all, S_all = kda_step.kda_step_rows(*args, None)
    mask = None if live is None else jnp.asarray(live, jnp.float32)
    o, S = kda_step.kda_step_rows(*args, mask)
    on = np.ones(5, bool) if live is None else np.asarray(live, bool)
    assert np.array_equal(np.asarray(S)[on], np.asarray(S_all)[on])
    assert np.array_equal(np.asarray(o)[on], np.asarray(o_all)[on])
    assert np.array_equal(np.asarray(S)[~on], np.asarray(args[0])[~on])
    assert not np.asarray(o)[~on].any()
    # and through the dispatch, where the kernel is not chosen: the same rows
    o_port, S_port = A.kda_step_live(*args, mask)
    assert worst(S, S_port) < TOL
    assert worst(o * on[:, None, None], o_port * on[:, None, None]) < TOL


def test_the_dispatch_takes_the_kernel_where_it_may(monkeypatch):
    """``kda_step_live`` is one algorithm in two executions: with the
    kernel chosen (the choice is by backend, mesh and width; the test makes
    it) the rows in the plan advance as in the portable form."""
    args = inputs(3, 8, seed=5)
    live = jnp.asarray([1.0, 0.0, 1.0])
    o_port, S_port = A.kda_step_live(*args, live)
    called = []
    monkeypatch.setattr(A, "_kda_kernel_ok",
                        lambda dk: called.append(dk) or True)
    o, S = A.kda_step_live(*args, live)
    assert called == [DK]
    assert worst(S, S_port) < TOL and worst(o[::2], o_port[::2]) < TOL
    assert not np.asarray(o[1]).any()


def test_products_in_bfloat16_fail_the_tolerance(monkeypatch):
    """The control: the same kernel with the operands of its product against
    the state rounded to bfloat16 (one pass of the MXU in place of
    ``Precision.HIGHEST``) is outside the tolerance the sound kernel meets,
    on the outputs and on the state."""
    def one_pass(x, S):
        lo = lambda t: t.astype(jnp.bfloat16).astype(jnp.float32)
        return jnp.dot(lo(x), lo(S), preferred_element_type=jnp.float32)

    args = inputs(3, 8, seed=3)
    o_ref, S_ref = A.kda_step(*args)
    monkeypatch.setattr(kda_step, "_dot", one_pass)
    # the function under the jit: a trace of its own, not a cached one
    o, S = kda_step.kda_step_rows.__wrapped__(*args, None)
    assert worst(o, o_ref) > 10 * TOL
    assert worst(S, S_ref) > 10 * TOL


# (equations, dot_generals) of the kernel's body as it was measured inside
# the set-up budget (PERF.md, PR 45): one loop over a step's heads, unrolled
# only at lowering, and two guards; one product a head
_BODY_AT_MOST = (60, 1)


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn)
        else:
            for sub in _sub_jaxprs(eqn):
                _pallas_calls(sub, out)
    return out


def test_the_state_is_aliased_and_the_entry_does_not_grow_with_rows():
    """The build-cost guard (the form of ``tests/test_kda_scan.py::
    test_kernel_body_does_not_grow_with_rows_or_window``): the entry's jaxpr
    and the kernel's body have the same equations at 2 rows and at the
    long-generation cell's 160; and the state goes through the call in
    place: operand 2 (after the two prefetched scalars) is output 0."""
    entry, body = set(), set()
    for B in (2, 160):
        args = tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in (
            (B, 64, DK, DK), (B, 64, DK), (B, 64, DK), (B, 64, DK),
            (B, 64, DK), (B, 64), (B,)))
        jaxpr = jax.make_jaxpr(kda_step.kda_step_rows)(*args).jaxpr
        inner, = (e.params["jaxpr"].jaxpr for e in jaxpr.eqns)
        entry.add(len(inner.eqns))
        call, = _pallas_calls(inner, [])
        assert call.params["name"] == "dcp_kda_step"
        assert (2, 0) in tuple(call.params["input_output_aliases"])
        assert call.invars[2].aval.shape == (B, 64, DK, DK)
        body.add(_size(call.params["jaxpr"]))
    assert len(entry) == 1 and len(body) == 1, (entry, body)
    (eqns, dots), = body
    assert dots == _BODY_AT_MOST[1] and eqns <= _BODY_AT_MOST[0], (eqns, dots)


def serve_with_slots_parked(build, monkeypatch):
    """Three requests through four slots, one ending early, so a slot is
    parked from the first segment and another from the second, by an engine
    with programs of its own (engines of one configuration share theirs,
    and a choice of execution is read while a program is traced) ->
    ``(tokens, stats)``."""
    rng = np.random.default_rng(8)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, 512, n)],
                    max_new=m) for n, m in ((17, 4), (30, 12), (9, 12))]
    monkeypatch.setattr(serve_module, "_PROGRAM_CACHE", {})
    model, params = build()
    cb = ContinuousBatcher(model, params, slots=4, t_max=128,
                           prompt_buf=64, segment=4)
    out = cb.serve_detailed(reqs)
    assert all(r.status == "ok" for r in out)
    return [list(r.tokens) for r in out], cb.stats_snapshot()["stats"]


def serve_portable_then_with_the_kernel(build, monkeypatch):
    """For the families' engine tests (``tests/test_hybrid_solar.py``,
    ``tests/test_hybrid_glm.py``): :func:`serve_with_slots_parked` first in
    the portable form, then with the kernel chosen (interpreted; the choice
    is by backend and width, the caller's test makes it) -> ``((tokens,
    stats) portable, (tokens, stats) kernel, the state blocks the kernel
    was traced on)``."""
    serve = lambda: serve_with_slots_parked(build, monkeypatch)
    portable = serve()
    monkeypatch.setattr(A, "_kda_kernel_ok", lambda head_dim: True)
    traced, body = [], kda_step._step_kernel
    monkeypatch.setattr(kda_step, "_step_kernel", lambda *refs: (
        traced.append(refs[2].shape), body(*refs))[1])
    kda_step.kda_step_rows.clear_cache()
    kernel = serve()
    kda_step.kda_step_rows.clear_cache()
    return portable, kernel, traced


@pytest.mark.parametrize("layers,kw", [
    (3, dict(kda_gate_lower_bound=None, kda_allow_neg_eigval=True)),
    (4, dict())])
def test_a_models_kda_layers_share_one_trace_under_their_scope(
        monkeypatch, layers, kw):
    """A decode segment of three (Solar's period) or four (GLM's) KDA blocks
    with the kernel chosen: as many calls of ONE jitted function, traced
    once a program, and the kernel's operations under ``attn_linear/
    linear_scan`` in the lowered segment, which is where
    ``linear_scan_share.decode``, ``kda_step_roofline_share.decode`` and
    ``kda_step_kernel_share.decode`` look."""
    monkeypatch.setattr(A, "_kda_kernel_ok", lambda head_dim: True)
    # programs of its own: engines of one configuration share theirs
    monkeypatch.setattr(serve_module, "_PROGRAM_CACHE", {})
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("full_attention",) + ("linear_attention",) * layers,
        mlp_layer_types=("dense",) * (layers + 1),
        num_heads=4, d_model=64, d_ff=128, norm_placement="pre",
        qk_norm=False, kda_heads=4, kda_head_dim=16, kda_gate_rank=8, **kw)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    traced, body = [], kda_step._step_kernel
    monkeypatch.setattr(kda_step, "_step_kernel", lambda *refs: (
        traced.append(refs[2].shape), body(*refs))[1])
    kda_step.kda_step_rows.clear_cache()
    out = cb.serve([Request(tokens=list(range(1, 21)), max_new=6)])
    assert len(out[0]) == 6
    fn, args, kwargs = cb._program_sigs["segment"]
    # one trace a segment program the engine built (a rung of its width
    # ladder), not one a layer or a tick
    assert traced == [(1, 4, 16, 16)] * fn._cache_size(), traced
    calls = _pjit_calls(jax.make_jaxpr(
        lambda *a: fn(*a, **kwargs))(*args).jaxpr, "kda_step_rows", [])
    kda_step.kda_step_rows.clear_cache()
    assert len(calls) == layers
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    locs = _locations(fn.lower(*args, **kwargs))
    assert _has(locs, "decode", "attn", "attn_linear", "linear_scan",
                "jit(kda_step_rows)")
    assert not any("jit(kda_step_rows)" in n and "linear_scan" not in n
                   for n in locs)
