"""Divergence sentinel: prove dp replicas still agree, cheaply.

Data-parallel training has a correctness invariant nothing in the hot
path checks: after every update, all dp replicas hold bit-identical
parameters. The invariant breaks silently — a flipped DRAM bit, an SDC
on one chip, a nondeterministic kernel reduction order — and the
symptom (loss divergence, garbage samples) surfaces hours or days
later with the causal step long gone. veScale (arXiv:2509.07003)
treats replica consistency as a first-class training invariant; this
module is that check for our stack.

Mechanism — ``make_divergence_check(mesh)``:

- Each replica computes a u32 FINGERPRINT of its local copy of the
  (nominally replicated) pytree: per-leaf BIT-PATTERN sum — bitcast
  each f32 element to u32, sum mod 2^32 — folded FNV-style across
  leaves. The sum is one pass over every element with EXACT modular
  integer arithmetic, so unlike any float reduction it has no rounding
  shadow: a float sum-of-squares misses a low-mantissa flip (the delta
  rounds away under a large accumulator) and misses denormals outright
  (their squares underflow to zero), while a single flipped bit always
  changes its element's u32 pattern and therefore the modular sum.
  (A crafted multi-element cancellation can still collide; against
  random corruption — the threat model — the fingerprint is sound.
  The fold makes leaf identity matter too, so swapped equal-content
  leaves still trip.)
- The fingerprints are compared INSIDE the mesh: a ``shard_map``
  manual over the dp axes computes ``pmax(fp) - pmin(fp)``; replicas
  agree iff the spread is 0. No host gather of parameters, no O(model)
  transfer — the comparison moves 4 bytes per replica.
- The whole check is one compiled function invoked at the LOG cadence
  (where the trainer already syncs for the loss fetch), so the steady
  state pays nothing and a desync is caught within one interval.

The HASH CHAIN is the complementary cross-RUN check: a sha256 chain
over per-step (loss, grad_sumsq) scalars, emitted in the metrics
JSONL at each log flush. Two runs that executed bitwise-identically
have identical chain digests at every flush; the first differing
digest bisects the first diverging step — `diff` on two JSONL files
replaces an ad-hoc reproducibility investigation.
"""

from __future__ import annotations

import hashlib
import struct

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from distributed_compute_pytorch_tpu.parallel import collectives as coll


def tree_fingerprint(tree) -> jax.Array:
    """u32 fingerprint of a pytree: per-leaf sum (mod 2^32) of the f32
    elements' u32 bit patterns, FNV-folded in leaf order. Exact integer
    arithmetic — no float reduction whose rounding could swallow a
    single-bit delta. Pure and jit-safe; inside a dp-manual region each
    replica fingerprints its OWN buffers."""
    fp = jnp.uint32(2166136261)
    for x in jax.tree_util.tree_leaves(tree):
        bits = lax.bitcast_convert_type(
            jnp.asarray(x).astype(jnp.float32), jnp.uint32)
        fp = fp * jnp.uint32(16777619) ^ jnp.sum(bits, dtype=jnp.uint32)
    return fp


def make_divergence_check(mesh):
    """Compiled ``check(tree) -> int`` returning the cross-replica
    fingerprint spread (0 == replicas bit-agree). ``None`` when the
    mesh has no dp axis of size > 1 — nothing is replicated, nothing
    can desync.

    ``in_specs=P()`` hands each shard_map body instance the device's
    LOCAL copy of every (replicated) leaf — exactly the buffers that
    could have silently diverged — and ``pmax - pmin`` over the dp
    axes compares the fingerprints without leaving the mesh."""
    dp = coll.dp_axes(mesh)
    if not dp:
        return None

    def body(tree):
        fp = lax.pcast(tree_fingerprint(tree), dp, to="varying")
        return lax.pmax(fp, dp) - lax.pmin(fp, dp)

    fn = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(),
                               out_specs=P()))

    def check(tree) -> int:
        return int(fn(tree))

    return check


class HashChain:
    """sha256 hash chain over per-step scalars for bitwise run diffing.

    ``update(*values)`` folds the little-endian f64 encoding of each
    value into ``state = sha256(state || packed)`` — a true chain, so
    a digest at step N commits to every value at steps <= N. Digests
    are emitted in the metrics JSONL at the log cadence; the first
    flush where two runs' digests differ brackets the first diverging
    step."""

    SEED = b"dcp-hash-chain-v1"

    def __init__(self):
        self._state = hashlib.sha256(self.SEED).digest()
        self.steps = 0

    def update(self, *values: float) -> None:
        packed = b"".join(struct.pack("<d", float(v)) for v in values)
        self._state = hashlib.sha256(self._state + packed).digest()
        self.steps += 1

    def digest(self) -> str:
        return self._state.hex()
