"""TPU-gated KV-cache write kernels (ops/pallas/cache_update.py).

``tests/test_cache_update.py`` runs these kernels in the Pallas
interpreter on the faked CPU mesh; Mosaic compilation is where a block
shape dies, so this file compiles each write on a REAL TPU — bf16 and the
int8 + f32-scale form, hd=64, GQA hk=4 — and compares it bit for bit with
its XLA fallback. Skipped automatically when no TPU is attached.

Run on hardware with ``DCP_TEST_TPU=1 python -m pytest
tests/test_cache_update_tpu.py tests/test_flash_tpu.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from distributed_compute_pytorch_tpu.ops.pallas.cache_update import (
    _pool_scatter, _rowwise_select, kv_insert_pallas, kv_insert_rows_pallas,
    kv_pool_insert_rows_pallas)

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="requires a real TPU (suite runs on the faked CPU mesh)")

HK, HD = 4, 64      # GQA kv heads, GPT-2/Llama head width

# leaf name -> (trailing width, dtype): the two cache forms serving stores
FORMS = {"bf16": {"kv": (HD, jnp.bfloat16)},
         "int8": {"kv": (HD, jnp.int8), "scale": (1, jnp.float32)}}


def _trees(form, rows, t):
    """A random cache ``{leaf: [2, rows, HK, t, w]}`` in the given form."""
    key = jax.random.key(0)
    return {name: (jax.random.normal(jax.random.fold_in(key, i),
                                     (2, rows, HK, t, w)) * 40).astype(dt)
            for i, (name, (w, dt)) in enumerate(FORMS[form].items())}


def _assert_equal(got, ref):
    for name in ref:
        np.testing.assert_array_equal(
            np.asarray(got[name].astype(jnp.float32)),
            np.asarray(ref[name].astype(jnp.float32)), err_msg=name)


@pytest.mark.parametrize("form", ["bf16", "int8"])
def test_pool_rows_write_matches_scatter_on_tpu(form):
    """The per-tick serving write: one window per decode row into a paged
    pool whose block size is the dtype's window (8 bf16, 32 int8) — the
    default ``ContinuousBatcher`` geometry."""
    bt = 32 if form == "int8" else 8
    P, B = 24, 8
    pool = _trees(form, P, bt)
    upd = {n: a[:, :B, :, :1] + 1 for n, a in _trees(form, P, bt).items()}
    blocks = jnp.asarray([3, 7, 0, 23, 11, 5, 0, 16], jnp.int32)
    offsets = jnp.asarray([0, bt - 1, 2, 5, 1, bt // 2, 3, 7], jnp.int32)
    got = jax.jit(kv_pool_insert_rows_pallas)(pool, upd, blocks, offsets)
    ref = {n: _pool_scatter(pool[n], upd[n], blocks, offsets) for n in pool}
    # rows 2 and 6 are parked: they share the trash block 0, whose
    # content is garbage by contract (each step rewrites a whole window
    # it read before the other's write) — every other block is exact
    _assert_equal({n: a[:, 1:] for n, a in got.items()},
                  {n: a[:, 1:] for n, a in ref.items()})


@pytest.mark.parametrize("form", ["bf16", "int8"])
def test_rows_write_matches_select_on_tpu(form):
    """Per-row positions into the contiguous [2, B, hk, T, hd] cache."""
    B, T = 8, 256
    cache = _trees(form, B, T)
    upd = {n: a[:, :, :, :1] + 1 for n, a in _trees(form, B, T).items()}
    pos = jnp.asarray([0, 7, 8, 31, 32, 100, 254, 255], jnp.int32)
    got = jax.jit(kv_insert_rows_pallas)(cache, upd, pos)
    ref = {n: _rowwise_select(cache[n], upd[n], pos) for n in cache}
    _assert_equal(got, ref)


@pytest.mark.parametrize("pos", [0, 7, 33, 255])
@pytest.mark.parametrize("form", ["bf16", "int8"])
def test_lockstep_write_matches_dus_on_tpu(form, pos):
    """One shared position for the whole batch (``infer.py`` decode)."""
    B, T = 8, 256
    cache = _trees(form, B, T)
    upd = {n: a[:, :, :, :1] + 1 for n, a in _trees(form, B, T).items()}
    got = jax.jit(kv_insert_pallas)(cache, upd, jnp.int32(pos))
    ref = {n: lax.dynamic_update_slice_in_dim(cache[n], upd[n], pos, axis=3)
           for n in cache}
    _assert_equal(got, ref)
