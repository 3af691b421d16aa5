"""A layer says what it keeps: the engine allocates, writes, copies and
accounts a layer's cache by the PLACEMENT of its declared leaves
(``ops/attention.py::CacheLeaf``: keyed by block or by slot), never by the
layer's kind.

Two halves. (1) A block DEFINED HERE, of a kind ``serve.py`` has never
heard of, under leaf names ``serve.py`` nowhere spells, is served through
``ContinuousBatcher`` without an edit there: admission into slots that are
not ``0..K-1`` lands every leaf where the table and the slot ids say and a
pad row writes nothing; ticks, a reused slot and ``logit_probe`` with
``prefill`` give the straightforward forward's numbers. (2) For the five
tiny configurations of layer kinds and a dense family the engine's caches
are exactly the declaration, and the byte accounting reads what the
families' own tests pin.
"""

import dataclasses
import importlib
import pathlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.infer import _POOL_SPEC
from distributed_compute_pytorch_tpu.models.llama import LlamaConfig, LlamaLM
from distributed_compute_pytorch_tpu.ops.attention import (
    CacheLeaf, gather_kv_blocks)
from distributed_compute_pytorch_tpu.serve import ContinuousBatcher, Request

ROWS, SUM = "memo_rows", "memo_sum"      # the leaves' names: the test's own
V, D = 64, 16


@dataclass(frozen=True)
class MemoConfig:
    num_layers: int = 2


@dataclass(frozen=True)
class MemoBlock:
    """A mixer with one leaf of each placement. Token ``t`` of a row leaves
    the layer as ``x_t + tanh(mean(x_0..x_t) w) + x_{t // 2} / 2``: the
    mean from a running SUM a slot (a state), the echo of an earlier token
    read back from the pool of the tokens' ROWS through the block table."""

    cache_kind = "memo"

    def cache_leaves(self, slots, pool_blocks, block_tokens, dtype,
                     kv_dtype="bf16"):
        return {ROWS: CacheLeaf((1, pool_blocks, 1, block_tokens, D), dtype,
                                tokens=block_tokens),
                SUM: CacheLeaf((slots, D), jnp.float32, 0)}

    def read_path(self, cache):
        return "gather"

    @staticmethod
    def _out(p, x, total, count, echo):
        return x + jnp.tanh((total / count) @ p["w"]) + echo / 2

    def apply(self, p, x, *, kv_mask=None, kv_sink=None):
        B, T = x.shape[:2]
        real = (jnp.ones((B, T)) if kv_mask is None else kv_mask)[..., None]
        total = jnp.cumsum(x * real, axis=1)
        if kv_sink is not None:
            # the sum at each row's last real token (pads only trail)
            kv_sink.append({ROWS: x[None, :, None], SUM: total[:, -1]})
        t = jnp.arange(T)
        return self._out(p, x, total, (t + 1.0)[None, :, None], x[:, t // 2])

    def decode_step(self, p, x, cache, pos, live=None, counts_sink=None):
        table, pool = cache["table"], cache[ROWS]
        bt = pool.shape[3]
        pos = jnp.broadcast_to(jnp.atleast_1d(pos), x.shape[:1])
        blk = jnp.take_along_axis(table, (pos // bt)[:, None], 1)[:, 0]
        pool = pool.at[0, blk, 0, pos % bt].set(x[:, 0].astype(pool.dtype))
        view = gather_kv_blocks(pool, table)[0, :, 0]        # [B, nb bt, D]
        echo = jnp.take_along_axis(view, (pos // 2)[:, None, None], 1)
        total = cache[SUM] + x[:, 0]
        y = self._out(p, x, total[:, None], (pos + 1.0)[:, None, None], echo)
        return y, {ROWS: pool, SUM: total}


@dataclass(frozen=True)
class MemoLM:
    config: MemoConfig = MemoConfig()
    cache_block_tokens = None

    @property
    def num_layers(self):
        return self.config.num_layers

    def layer_block(self, i):
        return MemoBlock()

    def layer_params(self, params, i):
        return params["layers"][i]

    def kv_cache_spec(self):
        return 1, D

    def init(self, key):
        ks = jax.random.split(key, self.num_layers + 2)
        return {"wte": jax.random.normal(ks[0], (V, D)),
                "layers": [{"w": jax.random.normal(k, (D, D)) / 4}
                           for k in ks[1:-1]],
                "head": jax.random.normal(ks[-1], (D, V))}

    def embed(self, params, tokens, positions=None):
        return params["wte"][tokens]

    def readout(self, params, x):
        return x @ params["head"]

    def forward(self, params, tokens):
        """The whole sequence at once: logits ``[T, V]`` and each layer's
        input ``[T, D]``."""
        x = self.embed(params, jnp.asarray(tokens)[None])
        fed = []
        for i in range(self.num_layers):
            fed.append(x[0])
            x = self.layer_block(i).apply(params["layers"][i], x)
        return np.asarray(self.readout(params, x)[0]), fed


@pytest.fixture(scope="module")
def memo():
    model = MemoLM()
    return model, model.init(jax.random.key(0))


def test_the_engine_has_never_heard_of_the_kind_or_its_leaves():
    src = (pathlib.Path(__file__).parents[1]
           / "distributed_compute_pytorch_tpu" / "serve.py").read_text()
    for name in (ROWS, SUM, "_OFF_TABLE", "_SLOT_LEAVES",
                 "_admit_ring", " pool_leaves(", "record_greedy_mismatch"):
        assert name not in src, name
    # the kinds of models/hybrid.py are labels there: in the refusal table
    # and in prose, never compared
    for kind in ("ring", "state", "latent", "paged+tail", "latent+index"):
        assert f'== "{kind}"' not in src and f'"{kind}" in self' not in src


def test_admission_writes_each_leaf_where_its_placement_says(memo):
    """Two rows of different lengths into slots 3 and 1 of four, in a
    dispatch of four rows (two of them pads), over caches full of a
    sentinel: every block the rows' tables name for a real token holds the
    layer's input of those tokens, the two slots hold the sums, and nothing
    else was touched: not the blocks past a row's last token, not the
    other slots, not the trash block."""
    model, params = memo
    cb = ContinuousBatcher(model, params, slots=4, t_max=64, prompt_buf=32)
    bt = cb.bt
    assert cb.stats_snapshot()["cache_kinds"] == ["memo", "memo"]
    assert cb.stats_snapshot()["paged_read"] == "gather"
    cb._caches = jax.tree.map(lambda a: jnp.full_like(a, 7.0), cb._caches)
    rng = np.random.default_rng(0)
    rows = {3: [int(t) for t in rng.integers(1, V, bt + 3)],
            1: [int(t) for t in rng.integers(1, V, 5)]}
    cb._tables[3, :2] = [5, 2]
    cb._tables[1, :1] = [9]
    cb._dispatch_prefill([(b, known, 0, len(known))
                          for b, known in rows.items()], 4, 2 * bt, 0)
    for i, cache in enumerate(cb._caches):
        pool, sums = np.asarray(cache[ROWS]), np.asarray(cache[SUM])
        fed = {b: np.asarray(model.forward(params, known)[1][i])
               for b, known in rows.items()}
        np.testing.assert_allclose(pool[0, 5, 0], fed[3][:bt], atol=1e-6)
        np.testing.assert_allclose(pool[0, 2, 0, :3], fed[3][bt:], atol=1e-6)
        np.testing.assert_allclose(pool[0, 9, 0, :5], fed[1], atol=1e-6)
        untouched = np.delete(pool, [5, 2, 9], axis=1)
        assert (untouched == 7.0).all()
        for b in rows:
            np.testing.assert_allclose(sums[b], fed[b].sum(0), atol=1e-5)
        assert (sums[[0, 2]] == 7.0).all()


def test_ticks_and_a_reused_slot_give_the_forward_of_each_request(memo):
    """Five requests of different lengths through two slots (so slots are
    reused, each with the former tenant's sum and rows behind it): every
    served token is the forward's best for its prefix."""
    model, params = memo
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32,
                           segment=3)
    rng = np.random.default_rng(1)
    reqs = [Request(tokens=[int(t) for t in rng.integers(1, V, n)],
                    max_new=m)
            for n, m in ((9, 7), (1, 5), (20, 4), (2, 9), (13, 6))]
    for r, res in zip(reqs, cb.serve_detailed(reqs)):
        assert res.status == "ok" and len(res.tokens) == r.max_new
        seq = list(r.tokens) + list(res.tokens)
        logits = model.forward(params, seq[:-1])[0][len(r.tokens) - 1:]
        took = logits[np.arange(r.max_new), res.tokens]
        assert float(np.max(logits.max(-1) - took)) < 1e-4
    assert cb.last_slot_leaks == 0 and cb.last_block_leaks == 0
    assert cb.stats["state_rows_advanced"] == 0      # its layers keep tokens


@pytest.mark.parametrize("prefill", [0, 7, 16, 21])
def test_prefill_then_decode_through_scratch_caches(memo, prefill):
    """``logit_probe``: scratch caches of one slot and as many blocks as
    the stream takes, by the declaration; the first ``prefill`` tokens
    through admission, the rest through ticks."""
    model, params = memo
    cb = ContinuousBatcher(model, params, slots=2, t_max=64, prompt_buf=32)
    toks = np.random.default_rng(2).integers(1, V, 40)
    got = cb.logit_probe(toks, prefill=prefill)
    want = model.forward(params, toks)[0][prefill:]
    assert got.shape == want.shape
    assert float(np.max(np.abs(got - want))) < 1e-4


# ---- (2) the engine's caches are the declaration -------------------------

def hybrid(family):
    t = importlib.import_module(f"tests.test_hybrid_{family}")
    return t.build()


def llama():
    model = LlamaLM(dataclasses.replace(LlamaConfig.tiny(), max_seq_len=64))
    return model, model.init(jax.random.key(0))[0]


F32 = 4
CASES = {
    # per layer kind: bytes a cached token, bytes a slot (what the
    # families' own tests pin; K-EXAONE's from its shapes: K and V of 2
    # heads of 16 in float32, in the pool and in the ring alike)
    "exaone": (hybrid, {}, {"ring": 2 * 2 * 16 * F32,
                            "paged": 2 * 2 * 16 * F32}, {}),
    "joyai": (hybrid, {}, {"latent": 128 * F32}, {}),
    "zaya": (hybrid, {}, {"paged+tail": 2 * 2 * 16 * F32},
             {"paged+tail": 208 * F32}),
    "glm": (hybrid, {}, {"state": 0,
                         "latent+index": 128 * F32 + 16 * F32 // 4},
            {"state": 4 * 16 * 16 * F32 + 3 * 3 * 64 * F32,
             "latent+index": 3 * 16 * F32}),
    "solar": (hybrid, {}, {"paged": 2 * 2 * 16 * F32, "state": 0},
              {"state": 4 * 16 * 16 * F32 + 3 * 3 * 64 * F32}),
    "llama": (llama, {}, None, {}),
    "llama-int8": (llama, {"kv_dtype": "int8"}, None, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_engines_caches_are_exactly_what_the_layers_declare(case):
    build, kw, per_token, per_slot = CASES[case]
    model, params = build(case) if build is hybrid else build()
    slots = 4
    cb = ContinuousBatcher(model, params, slots=slots, t_max=64,
                           prompt_buf=32, **kw)
    P = cb._pool.num_blocks
    dtype = jnp.float32
    if hasattr(model, "layer_block"):
        declared = [model.layer_block(i).cache_leaves(
            slots, P, cb.bt, dtype) for i in range(model.num_layers)]
        kinds = [model.layer_block(i).cache_kind
                 for i in range(model.num_layers)]
    else:
        hk, hd = model.kv_cache_spec()
        pool = {"kv": CacheLeaf((2, P, hk, cb.bt, hd), jnp.int8
                                if kw else dtype, tokens=cb.bt)}
        if kw:
            pool["scale"] = CacheLeaf((2, P, hk, cb.bt, 1), jnp.float32,
                                      tokens=cb.bt)
        n = model.config.num_layers
        declared, kinds = [pool] * n, ["paged"] * n
        isz = 1 if kw else F32
        per_token = {"paged": 2 * hk * (hd * isz + (F32 if kw else 0))}
    assert len(cb._caches) == len(declared)
    home = jax.tree.leaves(params)[0].sharding
    for cache, leaves in zip(cb._caches, declared):
        assert list(cache) == list(leaves)
        for name, leaf in leaves.items():
            assert cache[name].shape == leaf.shape, name
            assert cache[name].dtype == jnp.dtype(leaf.dtype), name
            assert cache[name].sharding == home, name
            # a leaf is keyed by its blocks or by its slots, on the axis
            # it says
            assert leaf.shape[1 if leaf.by_block else leaf.slot_axis] == (
                P if leaf.by_block else slots)
    snap = cb.stats_snapshot()
    assert snap["cache_kinds"] == kinds
    assert snap["cache_bytes_per_token"] == per_token
    assert snap["state_bytes_per_slot"] == per_slot
    assert snap["paged_read"] == ("selected" if case == "glm" else "gather")
    # the scratch of logit_probe is the same declaration for one slot
    small = cb._declared(1, 3)
    for leaves, mine in zip(declared, small):
        for name, leaf in leaves.items():
            axis = 1 if leaf.by_block else leaf.slot_axis
            want = list(leaf.shape)
            want[axis] = 3 if leaf.by_block else 1
            assert mine[name].shape == tuple(want)


def test_under_a_mesh_the_leaves_keyed_by_block_take_the_pools_sharding(
        devices8):
    from distributed_compute_pytorch_tpu.core.mesh import (
        make_mesh, named_sharding)
    from distributed_compute_pytorch_tpu.parallel.api import (
        pick_strategy, shard_pytree)
    model, params = llama()
    mesh = make_mesh("data=2,tensor=2", devices=devices8)
    params = shard_pytree(params, pick_strategy(mesh, model), mesh)
    for kv_dtype in ("bf16", "int8"):
        cb = ContinuousBatcher(model, params, slots=4, t_max=64,
                               prompt_buf=16, mesh=mesh, kv_dtype=kv_dtype)
        for cache in cb._caches:
            for leaf in cache.values():
                assert leaf.sharding == named_sharding(mesh, _POOL_SPEC)
                assert tuple(leaf.sharding.spec[1:3]) == ("data", "tensor")
