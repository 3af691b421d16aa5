"""Weights from the seed, made on the device in one jitted call, in the
type the cell runs them in. The layout comes from the reference's
``param_spec`` (a nested dict of ``(shape, std | ("const", value))``), so
neither side takes anything the other has made: the program and the
reference are both handed these arrays.

The generator is JAX's ``rbg`` implementation (the device's own bit
generator): one cheap program instead of the unrolled threefry that takes
the best part of a minute to compile for a few hundred million elements.
The same seed gives the same weights on the same kind of device."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A key from any whole-number seed (seeds above 2**31 are folded in
    two halves, so nothing depends on how wide an int the backend takes)."""
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    key = jax.random.fold_in(key, seed >> 31)
    return jax.random.fold_in(key, stream)


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def gen_fn(spec: dict, dtype):
    """The traceable ``key -> params`` function for ``spec``. ``dtype`` is
    one type for every leaf, or a tree of type names shaped like ``spec``."""
    leaves, treedef = jax.tree_util.tree_flatten(spec, is_leaf=_is_leaf)
    if isinstance(dtype, dict):
        dtypes = [jnp.dtype(d) for d in jax.tree_util.tree_leaves(dtype)]
    else:
        dtypes = [jnp.dtype(dtype)] * len(leaves)

    def gen(key):
        out = []
        for i, ((shape, init), dt) in enumerate(zip(leaves, dtypes)):
            if isinstance(init, tuple):          # ("const", value)
                out.append(jnp.full(shape, init[1], dt))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * init).astype(dt))
        return jax.tree_util.tree_unflatten(treedef, out)

    return gen


def make_params(spec: dict, seed: int, dtype, shardings=None, device=None):
    """Draw every leaf of ``spec``. ``shardings`` (a matching tree) or
    ``device`` says where the arrays are born."""
    gen = gen_fn(spec, dtype)
    kw = {}
    if shardings is not None:
        kw["out_shardings"] = shardings
    fn = jax.jit(gen, **kw)
    if device is not None and shardings is None:
        with jax.default_device(device):
            return fn(seed_key(seed))
    return fn(seed_key(seed))
