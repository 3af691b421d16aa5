"""Who holds the serving weights, and in which form (serve.py
``ContinuousBatcher._hold`` / ``._cut_weights``): a stacked family's block
weights reach the compiled programs as one tree a layer, cut ONCE from the
stack the caller handed over, so no program slices a weight; the engine
keeps no stacked leaf beside its slices; the caller's tree stays usable;
and ``dcp-serve`` leaves one copy of the weights a device."""

import dataclasses
import functools
import gc
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import Literal
from jax.sharding import NamedSharding

from distributed_compute_pytorch_tpu.core.mesh import make_mesh
from distributed_compute_pytorch_tpu.infer import generate
from distributed_compute_pytorch_tpu.models.gpt2 import GPT2, GPT2Config
from distributed_compute_pytorch_tpu.models.llama import (
    LlamaConfig, LlamaLM)
from distributed_compute_pytorch_tpu.serve import (
    ContinuousBatcher, Request)
from distributed_compute_pytorch_tpu.utils.quantize import (
    quantize_params_int8)

_KW = dict(slots=2, t_max=32, prompt_buf=8, segment=3)
_REQS = [[5, 9, 12, 7], [3, 1, 4]]


def _model(family):
    if family == "llama":
        return LlamaLM(dataclasses.replace(LlamaConfig.tiny(),
                                           max_seq_len=64))
    return GPT2(dataclasses.replace(GPT2Config.tiny(), max_seq_len=64))


def _serve(cb):
    return cb.serve([Request(list(t), 5) for t in _REQS])


@pytest.fixture(scope="module", params=["llama", "gpt2"])
def served(request):
    """(family's model, the caller's stacked tree, an engine that has
    served from it: both programs dispatched once)."""
    model = _model(request.param)
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, **_KW)
    _serve(cb)
    return model, params, cb


def _nbytes(tree) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree))


# ---- no program cuts a weight --------------------------------------------

_CUTS = ("slice", "dynamic_slice", "gather")


def _inner_jaxprs(eqn):
    """The jaxprs an equation calls. The programs hold ``jit`` and ``scan``
    alone, whose operands are their jaxpr's inputs one for one; anything
    else would be a hole in the guard and fails it."""
    for value in eqn.params.values():
        for sub in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                assert len(inner.invars) == len(eqn.invars), eqn.primitive
                yield inner


def _cuts(jaxpr, weights) -> list:
    """Every equation of ``jaxpr``, nested ones included, that slices or
    gathers one of the variables ``weights``."""
    found = []
    for eqn in jaxpr.eqns:
        first = eqn.invars[0] if eqn.invars else None
        if (eqn.primitive.name in _CUTS and not isinstance(first, Literal)
                and first in weights):
            found.append(f"{eqn.primitive.name} of {first.aval.str_short()}")
        for inner in _inner_jaxprs(eqn):
            found += _cuts(inner, {
                iv for iv, ov in zip(inner.invars, eqn.invars)
                if not isinstance(ov, Literal) and ov in weights})
    return found


@pytest.mark.parametrize("program", ["segment", "admit"])
def test_no_program_cuts_a_block_weight(served, program):
    """The CPU form of PR 39's gain: the traced tick (and the admission
    prefill) holds no slice, dynamic_slice or gather of a leaf of the block
    weights: each layer's weights are arguments of their own. (The
    embedding's lookup gathers its own table, no block's.)"""
    _, _, cb = served
    fn, args, kw = cb._program_sigs[program]
    closed = jax.make_jaxpr(functools.partial(fn, **kw))(*args)
    flat, _ = jax.tree_util.tree_flatten_with_path(args)
    assert len(flat) == len(closed.jaxpr.invars)
    weights = {var for (path, _), var in zip(flat, closed.jaxpr.invars)
               if len(path) > 1 and getattr(path[1], "key", None) == "blocks"}
    assert len(weights) == len(jax.tree.leaves(cb.params["blocks"])) > 0
    assert _cuts(closed.jaxpr, weights) == []


def test_guard_sees_a_cut_weight():
    """The guard's own check: a scan that slices a stacked weight in its
    body, the form the tick had, is found."""
    def f(w, x):
        return jax.lax.scan(lambda c, _: (c @ w[1], None), x, None,
                            length=2)[0]
    closed = jax.make_jaxpr(jax.jit(f))(jnp.ones((3, 4, 4)), jnp.ones((4,)))
    assert _cuts(closed.jaxpr, {closed.jaxpr.invars[0]})
    assert not _cuts(closed.jaxpr, {closed.jaxpr.invars[1]})


# ---- one copy, and the caller's stays the caller's -----------------------

def test_engine_tree_has_the_input_trees_bytes(served):
    _, params, cb = served
    assert _nbytes(cb.params) == _nbytes(params)
    blocks = cb.params["blocks"]
    assert len(blocks) == cb._n_layers
    want = jax.tree.map(lambda a: (a.shape[1:], a.dtype), params["blocks"])
    for layer in blocks:
        assert jax.tree.map(lambda a: (a.shape, a.dtype), layer) == want


def test_callers_stacked_arrays_stay_usable(served):
    """Nothing of the caller's is deleted or donated: the tree still
    generates, and layer ``i`` of the engine's is slice ``i`` of it."""
    model, params, cb = served
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(params))
    out = generate(model, params, jnp.asarray([_REQS[0]], jnp.int32), 5)
    assert [int(t) for t in out[0, len(_REQS[0]):]] == _serve(cb)[0]
    for i, layer in enumerate(cb.params["blocks"]):
        jax.tree.map(lambda a, s: np.testing.assert_array_equal(a, s[i]),
                     layer, params["blocks"])


@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_engine_is_each_stacked_leafs_last_owner(family):
    """A caller at full memory drops its tree after construction; the cut
    then frees every stacked leaf as its slices exist (never two copies of
    the weights). Until the cut ``params`` is the stack, and reading it
    moves nothing."""
    model = _model(family)
    params, _ = model.init(jax.random.key(1))
    refs = [weakref.ref(leaf) for leaf in jax.tree.leaves(params["blocks"])]
    cb = ContinuousBatcher(model, params, **_KW)
    del params
    gc.collect()
    assert isinstance(cb.params["blocks"], dict)
    assert all(r() is not None for r in refs)      # the engine holds them
    cb._cut_weights()
    gc.collect()
    assert all(r() is None for r in refs)
    assert len(cb.params["blocks"]) == cb._n_layers
    blocks = cb.params["blocks"]
    cb._cut_weights()                              # once: nothing to do
    assert cb.params["blocks"] is blocks


# ---- the conversion's edges ----------------------------------------------

@pytest.mark.parametrize("family", ["llama", "gpt2"])
def test_reload_weights_converts_again(family):
    """``reload_weights`` takes a stacked tree as the constructor does, and
    the reloaded engine serves the NEW weights as a fresh engine would."""
    model = _model(family)
    old, _ = model.init(jax.random.key(0))
    new, _ = model.init(jax.random.key(2))
    cb = ContinuousBatcher(model, old, **_KW)
    before = _serve(cb)
    cb.reload_weights(new)
    assert isinstance(cb.params["blocks"], tuple)  # cut at the reload
    assert _nbytes(cb.params) == _nbytes(new)
    fresh = ContinuousBatcher(model, new, **_KW)
    want = _serve(fresh)
    assert _serve(cb) == want != before


def test_int8_tree_converts_with_its_dtypes():
    """Every leaf under ``blocks`` is cut, the quantised kernels' ``q`` and
    ``scale`` leaves like any other, and keeps its dtype."""
    model = _model("llama")
    params, _ = model.init(jax.random.key(0))
    qp = jax.jit(quantize_params_int8)(params)
    cb = ContinuousBatcher(model, qp, **_KW)
    cb._cut_weights()
    want = jax.tree.map(lambda a: (a.shape[1:], a.dtype), qp["blocks"])
    assert jnp.dtype(jnp.int8) in {d for _, d in jax.tree.leaves(
        want, is_leaf=lambda x: isinstance(x, tuple))}
    for layer in cb.params["blocks"]:
        assert jax.tree.map(lambda a: (a.shape, a.dtype), layer) == want
    assert _nbytes(cb.params) == _nbytes(qp)
    assert all(len(out) == 5 for out in _serve(cb))


def test_mesh_sharded_tree_keeps_its_shardings(devices8):
    """Under a mesh a slice keeps its leaf's sharding on the axes that
    remain (the layer axis and its entry of the spec go)."""
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model = _model("llama")
    params, _ = model.init(jax.random.key(0))
    mesh = make_mesh("data=2,tensor=2", devices=devices8)
    sharded = shard_pytree(params, pick_strategy(mesh, model), mesh)
    cb = ContinuousBatcher(model, sharded, slots=4, t_max=32, prompt_buf=8,
                           segment=3, mesh=mesh)
    cb._cut_weights()
    split = 0
    for layer in cb.params["blocks"]:
        for a, s in zip(jax.tree.leaves(layer),
                        jax.tree.leaves(sharded["blocks"])):
            assert isinstance(a.sharding, NamedSharding)
            assert a.sharding.mesh == s.sharding.mesh
            assert a.sharding.is_equivalent_to(
                NamedSharding(mesh, jax.sharding.PartitionSpec(
                    *s.sharding.spec[1:])), a.ndim)
            split += not a.sharding.is_fully_replicated
    assert split > 0                    # the tensor axis really shards
    plain = ContinuousBatcher(model, params, slots=4, t_max=32,
                              prompt_buf=8, segment=3)
    assert _serve(cb) == _serve(plain)


def test_programs_lower_from_the_engines_abstract_tree():
    """A compile rehearsal hands the programs abstract arguments itself
    (``perfbench/rehearse3_serve.py``): the weights' are the ENGINE's tree
    after the cut, not the caller's stack, which the programs refuse."""
    model = _model("llama")
    params, _ = model.init(jax.random.key(0))
    cb = ContinuousBatcher(model, params, **_KW)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    arr = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt)
    caches = jax.tree.map(sds, cb._caches)
    K, W = 2, cb.Tb
    rest = (caches, arr((K, cb.nb), jnp.int32), arr((K, W), jnp.int32),
            arr((K, W), jnp.float32), arr((K, W), jnp.int32),
            arr((K, 0), jnp.float32), arr((K, W), jnp.int32),
            arr((K, W), jnp.int32))
    with pytest.raises(KeyError):
        cb._admit_c.lower(jax.tree.map(sds, params), *rest)
    cb._cut_weights()
    cb._admit_c.lower(jax.tree.map(sds, cb.params), *rest).compile()


# ---- dcp-serve: one copy of the weights a device -------------------------

@pytest.mark.parametrize("fleet", [
    [], ["--replicas", "2"],
    ["--autoscale", "1:2", "--upgrade_to", "next", "--prefix_cache"]],
    ids=["one_engine", "replicas", "elastic"])
def test_cli_leaves_one_copy_a_device(fleet, tmp_path, capsys, monkeypatch):
    """``dcp-serve`` hands its restored tree over and keeps no stacked tree
    on a device: after a serve call every stacked leaf the loader restored
    is gone (a fleet's copy waits on the host), and what an engine holds is
    the restored tree's bytes, once."""
    from distributed_compute_pytorch_tpu import cli_generate, cli_serve
    model = _model("gpt2")
    restored, held = [], []

    def load(*_args, **_kw):
        params, _ = model.init(jax.random.key(len(restored)))
        restored.extend(weakref.ref(a)
                        for a in jax.tree.leaves(params["blocks"]))
        held.append(_nbytes(params))
        return model, params, None

    run = ContinuousBatcher._run

    def run_then_look(self, *args, **kw):
        out = run(self, *args, **kw)
        gc.collect()
        assert all(r() is None for r in restored)
        assert isinstance(self.params["blocks"], tuple)
        assert _nbytes(self.params) == held[0]
        return out

    monkeypatch.setattr(cli_generate, "load_model_and_params", load)
    monkeypatch.setattr(ContinuousBatcher, "_run", run_then_look)
    reqs = tmp_path / "reqs.txt"
    reqs.write_text("5, 9, 12\n7\n1 2 3 4 5\n3 3\n")
    assert cli_serve.main(
        ["--ckpt_path", "unread", "--model", "gpt2", "--requests",
         str(reqs), "--slots", "2", "--segment", "3", "--max_new_tokens",
         "4", "--heartbeat", "0", "--elastic_window", "2"] + fleet) == 0
    assert restored
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert [len(ln["new"]) for ln in lines] == [4] * 4
