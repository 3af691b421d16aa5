"""``L.dropout``: a Bernoulli(keep) mask from XLA's bit generator, decided
on integers, drawn again (not kept) for the backward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_compute_pytorch_tpu.models import layers as L

KEY = jax.random.key(7)


def _kept(y):
    return np.asarray(y != 0)


@pytest.mark.parametrize("rate", [0.1, 0.25, 0.5])
def test_keep_share_and_mean(rate):
    n, keep = 1 << 20, 1.0 - rate
    y = L.dropout(jnp.ones((1024, 1024), jnp.float32), rate, KEY, True)
    sigma = (keep * (1 - keep) / n) ** 0.5
    assert abs(_kept(y).mean() - keep) < 4 * sigma
    # inverted scaling: what is kept is x / keep, so the mean stays 1
    np.testing.assert_allclose(np.asarray(y)[_kept(y)], 1 / keep, rtol=1e-6)
    assert abs(float(y.mean()) - 1.0) < 4 * sigma / keep


@pytest.mark.parametrize("rate", [0.1, 0.25, 0.5, 1e-7])
def test_threshold_is_within_2_to_minus_16_of_the_rate(rate):
    """The mask is ``bits < threshold`` on the generator's 32-bit words, no
    uniform float in between: the realised keep probability is
    threshold / 2**32 exactly."""
    jaxpr = jax.make_jaxpr(lambda x: L._mask_and_scale(
        x, jnp.zeros(4, jnp.uint32), 1.0 - rate, x.shape))(jnp.ones((8, 128)))
    by_name = {eq.primitive.name: eq for eq in jaxpr.eqns}
    assert by_name["rng_bit_generator"].params["dtype"] == jnp.uint32
    assert "random_bits" not in by_name and "threefry2x32" not in by_name
    threshold = int(by_name["lt"].invars[1].val)
    assert abs(threshold / 2 ** 32 - (1.0 - rate)) < 2 ** -16


@pytest.mark.parametrize("other", [
    jax.random.key(8),                  # another key
    jax.random.fold_in(KEY, 1),         # another layer index or step
    jax.random.split(KEY)[0],           # a child of the same key
], ids=["key", "fold_in", "split"])
def test_same_key_same_mask_other_key_other_mask(other):
    x = jnp.ones((256, 128), jnp.float32)
    a = L.dropout(x, 0.5, KEY, True)
    np.testing.assert_array_equal(a, L.dropout(x, 0.5, KEY, True))
    np.testing.assert_array_equal(
        a, jax.jit(lambda x, k: L.dropout(x, 0.5, k, True))(x, KEY))
    b = L.dropout(x, 0.5, other, True)
    assert 0.4 < (_kept(a) != _kept(b)).mean() < 0.6


def _plain(x, key):
    return L.dropout(x, 0.25, key, True)


def _checkpointed(x, key):
    return jax.checkpoint(_plain)(x, key)


def _scanned(x, key):
    """Two iterations, each layer its own key, as ``scan_blocks`` does."""
    def body(h, layer):
        return _plain(h, jax.random.fold_in(key, layer)), None
    return jax.lax.scan(body, x, jnp.arange(2))[0]


@pytest.mark.parametrize("fn", [_plain, _checkpointed, _scanned],
                         ids=["plain", "checkpoint", "scan"])
def test_backward_uses_the_forwards_mask(fn):
    x = jnp.full((64, 128), 3.0, jnp.float32)
    y, g = jax.jit(jax.value_and_grad(lambda x: fn(x, KEY).sum()))(x)
    out = fn(x, KEY)
    assert 0 < _kept(out).mean() < 1
    # d sum(dropout(x)) / dx is mask / keep, once a pass through the helper:
    # the same elements are zero, and one pass is exact
    np.testing.assert_array_equal(_kept(g), _kept(out))
    np.testing.assert_allclose(np.asarray(g), np.asarray(out / x), rtol=1e-6)
    if fn is not _scanned:
        np.testing.assert_array_equal(
            np.asarray(g), _kept(out) / np.float32(0.75))
    np.testing.assert_allclose(float(y), float(out.sum()), rtol=1e-6)


def test_backward_keeps_the_key_and_not_the_mask():
    """The residual between the passes is the generator's seed: nothing of
    the mask's size crosses from the forward to the backward."""
    x = jnp.ones((64, 128), jnp.float32)
    _, vjp = jax.vjp(lambda x: _plain(x, KEY), x)
    kept = [leaf.shape for leaf in jax.tree.leaves(vjp)
            if hasattr(leaf, "shape")]
    assert kept == [(4,)], kept


def test_broadcast_dims_share_the_mask():
    x = jnp.ones((4, 6, 5, 16), jnp.float32)
    y = _kept(L.dropout(x, 0.5, KEY, True, broadcast_dims=(1, 2)))
    assert (y == y[:, :1, :1, :]).all() and 0 < y.mean() < 1
    g = jax.grad(lambda x: L.dropout(x, 0.5, KEY, True,
                                     broadcast_dims=(1, 2)).sum())(x)
    np.testing.assert_array_equal(np.asarray(g), y * 2.0)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_dtype_is_kept(dtype):
    x = jnp.ones((8, 128), dtype)
    assert L.dropout(x, 0.1, KEY, True).dtype == dtype
    assert jax.grad(lambda x: L.dropout(x, 0.1, KEY, True).sum().astype(
        jnp.float32))(x).dtype == dtype


class _NoKey:
    """Stands where a key would: any use of it fails the test."""

    def __getattr__(self, name):
        raise AssertionError(f"the key was touched: .{name}")


@pytest.mark.parametrize("rate,train", [(0.1, False), (0.0, True)],
                         ids=["eval", "rate0"])
def test_identity_returns_the_input_and_touches_no_key(rate, train):
    x = jnp.ones((8, 128))
    assert L.dropout(x, rate, _NoKey(), train) is x


def test_mask_is_the_same_on_a_sharded_input(devices8):
    """XLA's generator gives the bits of the whole array whatever its
    sharding: a DP-N step draws the one-device step's masks."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    x = jnp.ones((128, 64), jnp.float32)
    f = jax.jit(lambda x: L.dropout(x, 0.5, KEY, True))
    sharded = jax.device_put(
        x, NamedSharding(Mesh(np.array(devices8), ("data",)), P("data")))
    np.testing.assert_array_equal(f(x), f(sharded))
