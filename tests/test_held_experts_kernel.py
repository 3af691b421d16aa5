"""``ops/pallas/held_experts.py``: the held experts' decode form as one kernel
over the experts some row chose (interpret mode on the CPU) against the form
it replaces where it runs and equals everywhere, ``models/moe.py::
HeldExperts._dense``: every expert chosen, some, one, none; both routers, a
held sub-range, masked rows, both clamps, both parameter types; that an
expert no row chose is never READ (its weights NaN leave the result finite
and equal, which ``_dense`` cannot do); the rule on shapes that says which
execution a call takes; and what keeps the kernel inside the build-cost gate
(ROADMAP A4): a body that does not grow with the experts or the rows, one
trace and one jitted function for all of a model's layers, under the scope
the benchmark reads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu import serve as serve_module
from distributed_compute_pytorch_tpu.models import moe
from distributed_compute_pytorch_tpu.models.moe import HeldExperts, MLPRouter
from distributed_compute_pytorch_tpu.models.registry import build_model
from distributed_compute_pytorch_tpu.ops.pallas import held_experts as HE
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request
from tests.test_flash_attention import _size
from tests.test_kda_scan import _pjit_calls
from tests.test_kda_step import _pallas_calls, serve_with_slots_parked
from tests.test_tracing_scopes import _has, _locations

# Both sides multiply the same operands into float32 and cast ``h`` alike:
# what differs is the order of the float32 sum over experts and tiles. Of
# outputs of size ~0.2: float32 to 1e-6; bfloat16 results to one step of
# their last place (2^-9 of 0.25).
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-3}

D, F = 64, 256


@pytest.fixture(autouse=True)
def a_trace_of_its_own():
    """``BLOCK_BYTES`` and the kernel's body are read while tracing."""
    HE.held_experts_chosen.clear_cache()
    yield
    HE.held_experts_chosen.clear_cache()


def weights(n, dtype, seed=0):
    """``n`` experts' ``gate``, ``up`` and ``down`` as the layer makes them."""
    return HeldExperts(D, F, n, 1, param_dtype=dtype).init(
        jax.random.key(seed))["experts"]


def assignments(N, k, n, chosen, seed=0):
    """``local [N, k]`` whose assignments fall on exactly the experts in
    ``chosen`` (each at least once) or nowhere (``n``), and their weights."""
    rng = np.random.default_rng(seed)
    chosen = list(chosen)
    assert len(chosen) <= N * k
    local = np.full(N * k, n, np.int32)
    if chosen:
        local[:] = np.where(rng.random(N * k) < 0.6,
                            rng.choice(chosen, N * k), n)
        local[rng.permutation(N * k)[:len(chosen)]] = chosen
    w = rng.uniform(0.05, 0.6, (N, k)).astype(np.float32)
    return jnp.asarray(local.reshape(N, k)), jnp.asarray(w)


def kernel(ex, x, local, w, limit):
    return HE.held_experts_chosen(ex["gate"], ex["up"], ex["down"], x, local,
                                  w, swiglu_limit=limit)


def worst(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


CHOSEN = {"every": range(6), "some": (1, 4, 5), "one": (3,), "none": ()}


@pytest.mark.parametrize("which", list(CHOSEN))
@pytest.mark.parametrize("limit", [0.0, 10.0])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("tiles", [1, 2])
def test_the_kernel_equals_the_dense_form(which, limit, dtype, tiles,
                                          monkeypatch):
    """Every held expert chosen, three of six, exactly one and none (zeros),
    with and without the clamp (inputs scaled so that it bites), float32
    and bfloat16 parameters and rows, ``f`` in one tile and in two; five
    rows, so the kernel pads them to a sublane tile."""
    if tiles == 2:
        monkeypatch.setattr(HE, "BLOCK_BYTES",
                            D * 128 * jnp.dtype(dtype).itemsize)
    assert F // HE.f_tile(D, F, jnp.dtype(dtype).itemsize) == tiles
    layer = HeldExperts(D, F, 12, 2, experts_held=(3, 6), swiglu_limit=limit)
    ex = weights(6, dtype, seed=2)
    x = (30.0 * jax.random.normal(jax.random.key(1), (5, D))).astype(dtype)
    local, w = assignments(5, 2, 6, CHOSEN[which], seed=len(CHOSEN[which]))
    want = layer._dense(ex, x, local, w)
    if limit and which != "none":
        unclamped = HeldExperts(D, F, 12, 2, experts_held=(3, 6))._dense(
            ex, x, local, w)
        assert worst(want, unclamped) > 100 * TOL[dtype]
    got = kernel(ex, x, local, w, limit)
    assert got.dtype == want.dtype and got.shape == want.shape
    scale = max(float(jnp.max(jnp.abs(want.astype(jnp.float32)))), 1.0)
    assert worst(got, want) <= TOL[dtype] * scale
    if which == "none":
        assert not np.asarray(got.astype(jnp.float32)).any()


@pytest.mark.parametrize("which", ["some", "one", "none"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_an_expert_no_row_chose_is_never_read(which, dtype):
    """The weights of the experts no assignment fell on set to NaN: the
    kernel's result is finite and what it was (bit for bit: the same
    blocks in the same order), where the dense form's is NaN all over (0 x
    NaN). This is the test that the weights are passed by."""
    ex = weights(6, dtype, seed=3)
    x = jax.random.normal(jax.random.key(4), (7, D)).astype(dtype)
    local, w = assignments(7, 2, 6, CHOSEN[which], seed=9)
    off = jnp.asarray([e not in CHOSEN[which] for e in range(6)])
    poisoned = {k: jnp.where(off[:, None, None], jnp.nan, a).astype(dtype)
                for k, a in ex.items()}
    want = kernel(ex, x, local, w, 10.0)
    got = kernel(poisoned, x, local, w, 10.0)
    assert np.isfinite(np.asarray(got.astype(jnp.float32))).all()
    assert np.array_equal(np.asarray(got.astype(jnp.float32)),
                          np.asarray(want.astype(jnp.float32)))
    dense = HeldExperts(D, F, 6, 2, swiglu_limit=10.0)._dense(
        poisoned, x, local, w)
    assert np.isnan(np.asarray(dense.astype(jnp.float32))).all()


def _layer(router: str, dtype=jnp.float32):
    """The two routers as the families build them: the sigmoid router's
    top-8 of 32 with experts 8..19 held and a shared expert (GLM's shape,
    clamped), and the MLP router's top-1 of 9 with a skip choice nobody
    holds (ZAYA1's), which threads a state."""
    if router == "top8_of_a_sub_range":
        return HeldExperts(D, F, 32, 8, experts_held=(8, 12), shared_d_ff=32,
                           routed_scale=2.5, swiglu_limit=10.0,
                           param_dtype=dtype)
    return HeldExperts(D, F, 9, 1, experts_held=(0, 8),
                       router=MLPRouter(D, 16, 9, 1e-5), skip_index=8,
                       param_dtype=dtype)


@pytest.mark.parametrize("router", ["top8_of_a_sub_range", "top1_with_a_skip"])
@pytest.mark.parametrize("mask", ["no_mask", "rows_parked", "all_parked"])
def test_through_the_layer_the_chosen_form_is_the_dense_forms(
        router, mask, monkeypatch):
    """``apply_with_state`` with the kernel chosen (the choice is by shapes
    and backend; the test makes it) against the dense form on the same
    call: both routers' ``(local, w)`` as they are, a held sub-range, the
    shared expert where it was, a masked row out of the experts AND out of
    the chosen list (the counts say so), the router's state untouched."""
    layer = _layer(router)
    p = layer.init(jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (6, 1, D))
    state = (None if layer.skip_index is None
             else jax.random.normal(jax.random.key(7), (6, 1, 16)))
    token_mask = {"no_mask": None,
                  "rows_parked": jnp.asarray([1., 0., 1., 1., 0., 0.]),
                  "all_parked": jnp.zeros((6,))}[mask]

    def run():
        sink: list = []
        y, s = layer.apply_with_state(p, x, state, token_mask=token_mask,
                                      counts_sink=sink)
        return y, s, np.asarray(sink[0])

    assert layer.form(6) == "dense"
    want, want_state, want_counts = run()
    monkeypatch.setattr(moe, "_chosen_kernel_ok", lambda d, f: True)
    monkeypatch.setattr(moe, "CHOSEN_STREAM_RATIO", 1.5)
    assert layer.form(6) == "chosen"
    got, got_state, counts = run()
    assert worst(got, want) < 2e-6
    assert (got_state is None) == (want_state is None)
    if got_state is not None:
        assert np.array_equal(np.asarray(got_state), np.asarray(want_state))
    assert np.array_equal(counts, want_counts)
    head = 3 if layer.skip_index is not None else 2
    chosen, held = counts[head:head + 2]
    load = counts[head + 2:]
    assert held == layer.held[1] == len(load)
    assert chosen == (load > 0).sum() <= held
    if mask == "all_parked":
        assert chosen == 0 and counts[0] == 0
    else:
        assert chosen > 0


# the five configurations' decode ticks (rows = serving.slots, top_k, the
# router's width, the widths of an expert) and the admission rungs that go
# through the dense form today; what the rule says where a kernel may run
TICKS = [
    ("zaya1", 20, 1, 17, 2048, 2048, "chosen"),            # s = 0.70
    ("glm53flash", 32, 8, 288, 4096, 2048, "chosen"),      # s = 0.59
    ("joyai", 64, 8, 256, 2048, 768, "chosen"),            # s = 0.87
    ("solaropen2", 160, 8, 320, 4096, 1280, "dense"),      # s = 0.98
    ("kexaone", 128, 8, 128, 6144, 2048, "dense"),         # s = 1.00
    ("kexaone_admits_256", 256, 8, 128, 6144, 2048, "dense"),
    ("solaropen2_admits_512", 512, 8, 320, 4096, 1280, "dense"),
    ("glm53flash_admits_256", 256, 8, 288, 4096, 2048, "dense"),
    ("zaya1_admits_512", 512, 1, 17, 2048, 2048, "dense"),
    ("glm53flash_admits_2048", 2048, 8, 288, 4096, 2048, "sorted"),
]


@pytest.mark.parametrize("name,N,k,E,d,f,form", TICKS,
                         ids=[t[0] for t in TICKS])
def test_the_rule_on_shapes(name, N, k, E, d, f, form, monkeypatch):
    """Which execution a call takes is read off its shapes: the expected
    chosen share ``1 - (1 - k / E) ^ N`` against ``CHOSEN_STREAM_RATIO``.
    On the CPU (and under a mesh: ``_chosen_kernel_ok``) every call of up
    to ``dense_max_tokens`` tokens stays the dense form."""
    layer = HeldExperts(d, f, E, k)
    assert 0.5 < moe.CHOSEN_STREAM_RATIO < 1.0
    assert layer.form(N) == ("sorted" if form == "sorted" else "dense")
    monkeypatch.setattr(moe, "_chosen_kernel_ok",
                        lambda d, f: d % 128 == 0 and f % 128 == 0)
    assert layer.form(N) == form
    share = layer.expected_chosen_share(N)
    if form == "chosen":
        assert share < moe.CHOSEN_STREAM_RATIO
    elif form == "dense":
        assert share >= moe.CHOSEN_STREAM_RATIO
    # a width that is no whole number of lane tiles keeps the dense form
    assert HeldExperts(d + 64, f, E, k).form(N) != "chosen"


def test_the_kernel_may_run_only_on_one_tpu():
    """Here the backend is the CPU: no width is eligible."""
    assert jax.default_backend() == "cpu"
    assert not moe._chosen_kernel_ok(4096, 2048)


# (equations, dot_generals) of the kernel's body: two guards, three products
_BODY_AT_MOST = (45, 3)


def test_the_entry_does_not_grow_with_the_experts_or_the_rows():
    """The build-cost guard (the form of ``tests/test_kda_step.py``'s): the
    entry's jaxpr and the kernel's body have the same equations at 16
    experts over 20 rows and at 36 over 32 (ZAYA1's and GLM's ticks), and
    the call is named for the trace."""
    entry, body = set(), set()
    for n, N, k in ((16, 20, 1), (36, 32, 8), (36, 20, 8), (16, 32, 1)):
        sds = jax.ShapeDtypeStruct
        args = (sds((n, 256, 512), jnp.bfloat16),
                sds((n, 256, 512), jnp.bfloat16),
                sds((n, 512, 256), jnp.bfloat16), sds((N, 256), jnp.bfloat16),
                sds((N, k), jnp.int32), sds((N, k), jnp.float32))
        jaxpr = jax.make_jaxpr(lambda *a: HE.held_experts_chosen(
            *a, swiglu_limit=10.0))(*args).jaxpr
        inner, = (e.params["jaxpr"].jaxpr for e in jaxpr.eqns)
        # rows that are no whole sublane tile are padded: a pad more
        entry.add(len(inner.eqns) - (N % 16 > 0))
        call, = _pallas_calls(inner, [])
        assert call.params["name"] == "dcp_held_experts"
        body.add(_size(call.params["jaxpr"]))
    assert len(entry) == 1 and len(body) == 1, (entry, body)
    (eqns, dots), = body
    assert dots == _BODY_AT_MOST[1] and eqns <= _BODY_AT_MOST[0], (eqns, dots)


def serve_dense_then_chosen(build, monkeypatch):
    """For the families' engine tests (``tests/test_hybrid_glm.py``,
    ``tests/test_hybrid_zaya.py``): ``tests/test_kda_step.py::
    serve_with_slots_parked`` first in the dense form, then with the kernel
    chosen (interpreted; the choice is by shapes and backend, this makes
    it) -> ``((tokens, stats) dense, (tokens, stats) chosen, the row blocks
    the kernel was traced on)``."""
    serve = lambda: serve_with_slots_parked(build, monkeypatch)
    dense = serve()
    monkeypatch.setattr(moe, "_chosen_kernel_ok", lambda d, f: True)
    monkeypatch.setattr(moe, "CHOSEN_STREAM_RATIO", 1.5)
    traced, body = [], HE._experts_kernel
    monkeypatch.setattr(HE, "_experts_kernel", lambda *refs, **kw: (
        traced.append(refs[2].shape), body(*refs, **kw))[1])
    HE.held_experts_chosen.clear_cache()
    chosen = serve()
    return dense, chosen, traced


def test_a_models_sparse_layers_share_one_trace_under_their_scope(monkeypatch):
    """A decode segment of three sparse layers with the kernel chosen: as
    many calls of ONE jitted function, traced once a program, and the
    kernel's operations under ``mlp/experts`` in the lowered segment, which
    is where ``experts_share.decode`` looks; the two counters add up."""
    monkeypatch.setattr(moe, "_chosen_kernel_ok", lambda d, f: True)
    monkeypatch.setattr(moe, "CHOSEN_STREAM_RATIO", 1.5)
    # programs of its own: engines of one configuration share theirs
    monkeypatch.setattr(serve_module, "_PROGRAM_CACHE", {})
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("full_attention",) * 3, mlp_layer_types=("sparse",) * 3,
        num_heads=4, d_model=64, d_ff=128, moe_d_ff=32, num_experts=8,
        top_k=2, experts_held=(2, 4), norm_placement="pre")
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    traced, body = [], HE._experts_kernel
    monkeypatch.setattr(HE, "_experts_kernel", lambda *refs, **kw: (
        traced.append(refs[2].shape), body(*refs, **kw))[1])
    HE.held_experts_chosen.clear_cache()
    out = cb.serve([Request(tokens=list(range(1, 21)), max_new=6)])
    assert len(out[0]) == 6
    fn, args, kwargs = cb._program_sigs["segment"]
    # one trace a signature (the segment program's two rows and each
    # admission window's, padded to sublane tiles), not one a layer
    # (a trace a layer would be three times the programs)
    assert len(traced) <= fn._cache_size() + cb._admit_c._cache_size(), traced
    assert (16, 64) in traced and fn._cache_size() == 1
    calls = _pjit_calls(jax.make_jaxpr(
        lambda *a: fn(*a, **kwargs))(*args).jaxpr, "held_experts_chosen", [])
    assert len(calls) == 3
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1
    locs = _locations(fn.lower(*args, **kwargs))
    assert _has(locs, "decode", "mlp", "experts", "jit(held_experts_chosen)")
    assert not any("jit(held_experts_chosen)" in n and "experts" not in n
                   for n in locs)
    st = cb.stats_snapshot()["stats"]
    assert 0 < st["experts_chosen"] <= st["experts_held_ticks"]
    # one row in the plan of two, top-2: at most two of the four held
    # experts a layer a tick
    assert st["experts_held_ticks"] == 4 * 3 * st["segments"] * 4
    assert st["experts_chosen"] <= st["experts_held_ticks"] // 2


def test_on_the_cpu_no_program_holds_the_kernel():
    """Without the choice made for it the engine's decode segment has no
    call of the kernel's entry: every program here stays the dense form."""
    model = build_model(
        "hybrid", vocab_size=256, max_seq_len=64,
        layer_types=("full_attention",) * 2, mlp_layer_types=("sparse",) * 2,
        num_heads=4, d_model=128, d_ff=128, moe_d_ff=128, num_experts=64,
        top_k=1, experts_held=(0, 8), norm_placement="pre")
    params, _ = model.init(jax.random.key(0))
    assert model.layer_block(0).experts().expected_chosen_share(
        2) < moe.CHOSEN_STREAM_RATIO
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=4)
    cb.serve([Request(tokens=list(range(1, 9)), max_new=3)])
    fn, args, kwargs = cb._program_sigs["segment"]
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args).jaxpr
    assert not _pjit_calls(jaxpr, "held_experts_chosen", [])
