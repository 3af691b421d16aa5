"""Data-parallel weight-update sharding (ZeRO-1) collectives.

"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (PAPERS.md) observes that under plain data parallelism every
replica all-reduces full gradients and then runs the SAME O(params)
optimizer update on the SAME replicated optimizer state — N-1 redundant
update passes and N-1 redundant copies of ``opt_state`` (2x params for
AdamW). The fix is a pure dataflow transform:

    all-reduce(grads) -> update          becomes
    reduce-scatter(grads) -> shard-local update -> all-gather(params)

Comm volume is unchanged (an all-reduce IS a reduce-scatter + all-gather),
the update compute and optimizer memory drop by the dp-axis size, and the
params the next forward sees are bit-identical up to reduction order.

Two integration styles live here:

- **Annotation-driven (the paper's, used by the exact path in
  ``train/step.py``)**: the update stage runs inside a ``shard_map``
  manual over the dp axis whose in/out specs mark each leaf's shard
  layout; XLA's SPMD partitioner materialises the pending gradient psum
  AS a reduce-scatter at the region boundary and the closing
  ``with_sharding_constraint`` to replicated AS the param all-gather.
  ``update_shard_spec``/``tree_update_specs`` choose the per-leaf layout.
- **Explicit manual-region collectives** (:func:`reduce_scatter`,
  :func:`all_gather`, :func:`quantized_reduce_scatter`): for code already
  inside a shard_map body that holds per-rank values — the quantized
  train path in ``train/step.py`` computes per-shard grads inside the
  region and reduces them here, which is the only place a QUANTIZED
  gradient collective can honestly exist at the JAX level (the automatic
  partitioner's reductions are always exact f32; EQuARX does this inside
  XLA itself).

The quantized reduce-scatter (EQuARX-motivated) exchanges block-scaled
int8 instead of f32: each rank splits its local gradient into N chunks
along the shard dim, quantizes each chunk with one f32 scale per
``block`` contiguous elements (symmetric abs-max/127), all-to-alls the
int8 payload + scales, and dequant-accumulates in f32. Wire bytes drop
~4x (int8 + scales/block vs f32); error is bounded by the sum over ranks
of each block's quantization step (tests/test_collectives.py pins it on
adversarial large-dynamic-range gradients). Chunks too small to amortise
scales (< ``min_int8_elems``) fall back to a bf16 exchange instead —
still half the f32 bytes, no scale bookkeeping.
"""

from __future__ import annotations

import math
import re

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_compute_pytorch_tpu.core.mesh import BATCH_AXES

# leaves smaller than this stay replicated (biases, norm scales): the
# all-gather latency would cost more than the duplicate update saves —
# same threshold philosophy as parallel.api.FSDP.min_size_to_shard
MIN_SIZE_TO_SHARD = 1024

# int8 quantization granularity: one f32 scale per this many elements
DEFAULT_BLOCK = 256

# below this many elements per exchanged chunk the int8 scales stop
# amortising; exchange bf16 instead (the ISSUE's "leaf too small" fallback)
MIN_INT8_ELEMS = 2048


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    """The batch axes a ``DataParallel`` gradient psum pends over (size>1
    only) — the axes a ZeRO-1 update shards across."""
    return tuple(a for a in BATCH_AXES
                 if a in mesh.axis_names and mesh.shape[a] > 1)


def dp_size(mesh: Mesh) -> int:
    return math.prod(mesh.shape[a] for a in dp_axes(mesh)) or 1


def update_shard_spec(shape: tuple[int, ...], n: int,
                      axes: tuple[str, ...],
                      min_size: int = MIN_SIZE_TO_SHARD) -> P:
    """PartitionSpec sharding one leaf 1/n for the weight update: the
    largest dim divisible by ``n`` carries the (possibly multi-axis) dp
    axes; indivisible or tiny leaves stay replicated (``P()``) and pay
    the old replicated update — they are the byte-budget rounding error.
    Deterministic in ``shape`` alone, so gradient, param, and optimizer
    moment leaves of one parameter always agree on the layout."""
    if n <= 1 or int(np.prod(shape)) < min_size:
        return P()
    best, best_dim = -1, None
    for d, s in enumerate(shape):
        if s % n == 0 and s > best:
            best, best_dim = s, d
    if best_dim is None:
        return P()
    spec = [None] * len(shape)
    spec[best_dim] = axes if len(axes) > 1 else axes[0]
    return P(*spec)


def tree_update_specs(tree, n: int, axes: tuple[str, ...],
                      min_size: int = MIN_SIZE_TO_SHARD):
    """Per-leaf :func:`update_shard_spec` pytree (accepts abstract
    ``eval_shape`` trees). Applied uniformly to params AND opt_state:
    optimizer moments share their parameter's shape, so they land on the
    identical layout; scalars (step counts) come out ``P()``."""
    def spec(leaf):
        s = getattr(leaf, "shape", None)
        shape = tuple(s) if s is not None else np.shape(leaf)
        return update_shard_spec(shape, n, axes, min_size)
    return jax.tree.map(spec, tree)


def tree_update_shardings(tree, mesh: Mesh,
                          min_size: int = MIN_SIZE_TO_SHARD):
    """NamedSharding pytree for a state tree born in the ZeRO-1 layout
    (``train/step.py::init_fn`` out_shardings)."""
    axes = dp_axes(mesh)
    n = dp_size(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        tree_update_specs(tree, n, axes, min_size))


# ---------------------------------------------------------------------------
# explicit manual-region collectives (callers are inside a shard_map body
# manual over `axis_name`; arrays are the per-rank LOCAL values)
# ---------------------------------------------------------------------------


def reduce_scatter(x, axis_name, dim: int = 0):
    """Exact f32-accurate reduce-scatter of per-rank partials: every rank
    holds a full-shaped local contribution; rank i returns the summed
    ``1/N`` shard along ``dim``."""
    return lax.psum_scatter(x, axis_name, scatter_dimension=dim, tiled=True)


def all_gather(x, axis_name, dim: int = 0):
    """Concatenate every rank's shard along ``dim`` (tiled): the param
    re-replication leg of the RS -> update -> AG dance."""
    return lax.all_gather(x, axis_name, axis=dim, tiled=True)


def _q8_blocks(flat, block: int):
    """Block-scaled symmetric int8: ``flat [M]`` (M % block == 0) ->
    ``(q int8 [M/block, block], scale f32 [M/block, 1])``. The 1e-30
    floor keeps all-zero blocks finite (q = 0 exactly)."""
    xb = flat.reshape(-1, block)
    scale = jnp.maximum(
        jnp.max(jnp.abs(xb), axis=1, keepdims=True) / 127.0, 1e-30)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q, scale


def quantized_reduce_scatter(x, axis_name, n: int, dim: int = 0,
                             block: int = DEFAULT_BLOCK,
                             min_int8_elems: int = MIN_INT8_ELEMS):
    """Block-scaled int8 reduce-scatter of per-rank partials over
    ``axis_name`` (size ``n``).

    Each rank splits its local full-shaped contribution into ``n`` chunks
    along ``dim``, quantizes each chunk (one f32 scale per ``block``
    flattened elements, chunk tail padded to a block multiple), exchanges
    the int8 payload + scales with one ``all_to_all``, and accumulates
    the ``n`` dequantized chunks in f32 — so the CROSS-REPLICA WIRE
    carries ~1/4 the f32 bytes while the accumulation stays f32.

    Error bound: per output element, at most ``sum_over_ranks(
    block_absmax_r / 127 * 0.5)`` — each rank's contribution is off by
    at most half its block's quantization step (pinned on adversarial
    dynamic-range gradients in tests/test_collectives.py).

    Fallback: chunks smaller than ``min_int8_elems`` exchange bf16
    instead (scales would not amortise; still half the f32 wire bytes).
    ``x.shape[dim]`` must divide by ``n`` — indivisible leaves should
    stay replicated (``update_shard_spec`` returns ``P()`` for them and
    the caller psums exactly).
    """
    if x.shape[dim] % n:
        raise ValueError(
            f"quantized_reduce_scatter: dim {dim} of {x.shape} does not "
            f"divide by the axis size {n}; keep this leaf replicated")
    # chunk-major layout [n, ...chunk...] so all_to_all's split axis is 0
    moved = jnp.moveaxis(x, dim, 0)
    chunk_shape = (moved.shape[0] // n,) + moved.shape[1:]
    chunks = moved.reshape((n,) + chunk_shape)
    elems = int(np.prod(chunk_shape))
    if elems < min_int8_elems:
        sent = lax.all_to_all(chunks.astype(jnp.bfloat16), axis_name,
                              split_axis=0, concat_axis=0)
        red = jnp.sum(sent.astype(jnp.float32), axis=0)
    else:
        pad = (-elems) % block
        flat = chunks.reshape(n, elems)
        if pad:
            flat = jnp.pad(flat, ((0, 0), (0, pad)))
        q, s = jax.vmap(lambda c: _q8_blocks(c, block))(flat)
        q = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0)
        s = lax.all_to_all(s, axis_name, split_axis=0, concat_axis=0)
        deq = q.astype(jnp.float32) * s            # [n, nblk, block]
        red = jnp.sum(deq, axis=0).reshape(-1)
        if pad:
            red = red[:elems]
        red = red.reshape(chunk_shape)
    return jnp.moveaxis(red.astype(x.dtype), 0, dim)


def shard_slice(x, axis_name, n: int, dim: int = 0):
    """This rank's 1/n shard of a REPLICATED local value ``x`` (inside a
    manual region): the zero-comm complement of :func:`all_gather`, used
    where params enter a region replicated but the update runs on the
    shard. ``axis_name`` may be a tuple of manual axes (multi-axis dp):
    the combined lexicographic rank index picks the shard, matching the
    layout ``P((a, b))`` gives the same leaf under the partitioner."""
    size = x.shape[dim] // n
    idx = axes_index(axis_name)
    return lax.dynamic_slice_in_dim(x, idx * size, size, axis=dim)


def axes_index(axis_name):
    """Combined rank index over one manual axis or a tuple of them —
    lexicographic (row-major) over the tuple, the same order a
    PartitionSpec entry ``(a, b)`` lays shards out in."""
    if isinstance(axis_name, str):
        return lax.axis_index(axis_name)
    idx = lax.axis_index(axis_name[0])
    for a in axis_name[1:]:
        idx = idx * lax.psum(1, a) + lax.axis_index(a)
    return idx


def spec_shard_dim(spec: P):
    """The dim a :func:`update_shard_spec` spec shards, or None (``P()``,
    replicated leaf)."""
    for d, entry in enumerate(spec):
        if entry is not None:
            return d
    return None


# ---------------------------------------------------------------------------
# parameter buckets: the DDP-style reduce -> update -> gather pipeline
# ---------------------------------------------------------------------------
#
# The step-level grad-accum boundary (train/step.py) reduces ONE set of
# accumulated gradients per optimizer update. Done leaf-by-leaf in a single
# pass, every reduce-scatter must finish before the first optimizer byte
# moves. Bucketing (torch DDP's bucket_cap_mb, arXiv:1810.11112 §3) instead
# groups leaves into ~fixed-byte buckets and runs reduce(k) -> update(k) ->
# gather(k) per bucket: bucket k's collective has no data dependency on
# bucket k-1's update, so XLA's async collectives overlap the wire time of
# one bucket with the optimizer math of the previous one. The grouping is
# numerically invisible — each leaf's reduction and update math is
# identical, only the issue order changes — so bucketed == single-shot
# bit-for-bit (tests/test_grad_accum.py pins it).

# DDP's default bucket size; 0 disables bucketing (single-shot boundary)
DEFAULT_BUCKET_MB = 25.0


def bucketize(tree, bucket_bytes: float):
    """Greedily group ``tree``'s leaves (flatten order) into contiguous
    buckets of at least ``bucket_bytes`` accumulated dense size. Returns a
    list of tuples of flat leaf indices covering every leaf exactly once;
    ``bucket_bytes <= 0`` yields one bucket with everything."""
    leaves = jax.tree_util.tree_leaves(tree)
    if bucket_bytes <= 0:
        return [tuple(range(len(leaves)))] if leaves else []
    buckets, cur, cur_b = [], [], 0
    for i, leaf in enumerate(leaves):
        cur.append(i)
        cur_b += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if cur_b >= bucket_bytes:
            buckets.append(tuple(cur))
            cur, cur_b = [], 0
    if cur:
        buckets.append(tuple(cur))
    return buckets


def _mask_tree(tree, treedef, keep):
    """``tree`` (structure ``treedef``) with every leaf whose flat index is
    not in ``keep`` replaced by ``None`` — an EMPTY subtree to jax, so the
    masked tree flattens to exactly the kept leaves and ``tree.map`` over
    identically-masked trees visits only them. This is what lets an optax
    chain update one BUCKET of leaves: paths (and so the name-keyed decay
    mask) are preserved, out-of-bucket leaves simply do not exist."""
    keep = set(keep)
    leaves = treedef.flatten_up_to(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [l if i in keep else None for i, l in enumerate(leaves)])


class OptStateBuckets:
    """Split/merge an optimizer state along parameter buckets.

    Any subtree of ``opt_state`` whose structure equals the params treedef
    (AdamW's mu/nu, momentum traces, Adadelta accumulators) is masked per
    bucket like the params; everything else (step counts, schedule state)
    is SHARED into every bucket unchanged. On merge, per-bucket outputs
    reassemble the params-shaped trees leaf-by-leaf and scalar state is
    taken from the first bucket — every bucket computed it from the same
    input count, so the copies are identical by construction (this is also
    why each bucket's bias correction is consistent: all buckets read the
    pre-update count)."""

    def __init__(self, opt_state, params_treedef, buckets):
        self.params_treedef = params_treedef
        self.buckets = [tuple(sorted(b)) for b in buckets]

        def is_params_tree(x):
            try:
                return jax.tree_util.tree_structure(x) == params_treedef
            except Exception:  # noqa: BLE001 — non-pytree nodes
                return False

        self._outer, self._outer_def = jax.tree_util.tree_flatten(
            opt_state, is_leaf=is_params_tree)
        self._is_ptree = [is_params_tree(l) for l in self._outer]

    def state_for(self, k: int):
        """The bucket-``k`` view of the opt_state handed to ``tx.update``."""
        keep = self.buckets[k]
        return jax.tree_util.tree_unflatten(self._outer_def, [
            _mask_tree(l, self.params_treedef, keep) if p else l
            for l, p in zip(self._outer, self._is_ptree)])

    def merge(self, bucket_states):
        """Reassemble the full new opt_state from per-bucket outputs."""
        outs = [self._outer_def.flatten_up_to(s) for s in bucket_states]
        n_leaves = self.params_treedef.num_leaves
        merged = []
        for pos, is_p in enumerate(self._is_ptree):
            if not is_p:
                merged.append(outs[0][pos])
                continue
            full = [None] * n_leaves
            for k, keep in enumerate(self.buckets):
                got = jax.tree_util.tree_leaves(outs[k][pos])
                for i, leaf in zip(keep, got):
                    full[i] = leaf
            merged.append(jax.tree_util.tree_unflatten(self.params_treedef,
                                                       full))
        return jax.tree_util.tree_unflatten(self._outer_def, merged)


def bucketed_update(grads, opt_state, params, specs, buckets, *,
                    reduce_leaf, slice_leaf, gather_leaf, update_fn):
    """The pipelined boundary: per bucket, reduce the accumulated local
    gradients (``reduce_leaf(g, spec, p)`` — psum, reduce-scatter, or the
    quantized exchange), slice the replicated params to the update shard
    (``slice_leaf``), apply the optimizer to the bucket
    (``update_fn(g, o, p) -> (new_p, new_o)`` on masked trees), and
    all-gather the updated shard back (``gather_leaf``). Buckets are
    independent dataflow chains, so XLA overlaps bucket k's collective
    with bucket k-1's update. Returns ``(new_params, new_opt_state)``
    with the same structure/sharding as the inputs."""
    treedef = jax.tree_util.tree_structure(params)
    p_leaves = treedef.flatten_up_to(params)
    g_leaves = treedef.flatten_up_to(grads)
    s_leaves = treedef.flatten_up_to(specs)
    state_bk = OptStateBuckets(opt_state, treedef, buckets)
    new_p = [None] * len(p_leaves)
    out_states = []
    for k, keep in enumerate(state_bk.buckets):
        g_k = {i: reduce_leaf(g_leaves[i], s_leaves[i], p_leaves[i])
               for i in keep}
        p_k = {i: slice_leaf(p_leaves[i], s_leaves[i]) for i in keep}
        g_tree = jax.tree_util.tree_unflatten(
            treedef, [g_k.get(i) for i in range(len(p_leaves))])
        p_tree = jax.tree_util.tree_unflatten(
            treedef, [p_k.get(i) for i in range(len(p_leaves))])
        np_tree, no_tree = update_fn(g_tree, state_bk.state_for(k), p_tree)
        for i, leaf in zip(keep, jax.tree_util.tree_leaves(np_tree)):
            new_p[i] = gather_leaf(leaf, s_leaves[i])
        out_states.append(no_tree)
    return (jax.tree_util.tree_unflatten(treedef, new_p),
            state_bk.merge(out_states))


# ---------------------------------------------------------------------------
# jaxpr collective audit — the grad-accum "one reduction per update" proof
# ---------------------------------------------------------------------------

# cross-replica reduction primitives (jaxpr names on the supported jax
# versions). all_gather is recorded too (the ZeRO-1 param gather leg) but
# is not a GRADIENT collective — callers filter on `prim`.
_REDUCE_PRIMS = ("psum", "psum_scatter", "reduce_scatter", "all_to_all")
_LOOP_PRIMS = ("scan", "while")


def jaxpr_collectives(fn_or_jaxpr, *args, **kwargs):
    """Walk a function's jaxpr (or an already-made ``ClosedJaxpr``) and
    record every cross-replica collective: ``{prim, axes, bytes,
    in_loop}`` per equation, recursing through pjit/shard_map/scan/cond
    sub-jaxprs. ``bytes`` is the summed operand size — for a gradient
    reduction, the bytes that cross the wire per participating chip
    (up to the collective algorithm's constant). ``in_loop`` marks
    equations under a ``scan``/``while`` body: a gradient collective
    there executes once PER MICROBATCH, which is exactly what the
    step-level accumulation boundary exists to eliminate."""
    jx = fn_or_jaxpr
    if not hasattr(jx, "eqns"):
        if hasattr(jx, "jaxpr"):            # ClosedJaxpr
            jx = jx.jaxpr
        else:
            jx = jax.make_jaxpr(fn_or_jaxpr)(*args, **kwargs).jaxpr
    recs = []

    def visit(j, in_loop):
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name in _REDUCE_PRIMS or name == "all_gather":
                axes = eqn.params.get("axes",
                                      eqn.params.get("axis_name", ()))
                if isinstance(axes, str):
                    axes = (axes,)
                nbytes = sum(
                    int(np.prod(v.aval.shape)) * v.aval.dtype.itemsize
                    for v in eqn.invars if hasattr(v, "aval"))
                recs.append({"prim": name, "axes": tuple(axes),
                             "bytes": nbytes, "in_loop": in_loop})
            inner_loop = in_loop or name in _LOOP_PRIMS
            for v in eqn.params.values():
                for u in (v if isinstance(v, (list, tuple)) else (v,)):
                    if hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                        visit(u.jaxpr, inner_loop)
                    elif hasattr(u, "eqns"):
                        visit(u, inner_loop)

    visit(jx, False)
    return recs


def grad_collective_stats(fn_or_jaxpr, *args, dp_axes=None,
                          min_bytes: int = 4 * MIN_SIZE_TO_SHARD):
    """Summarise a step function's GRADIENT collectives over the dp axes:
    reductions at least ``min_bytes`` big (gradient-leaf-sized — the
    scalar loss pmean and [C]-sized BatchNorm statistic psums fall under
    the floor and are not gradient traffic). Returns ``{"boundary": n,
    "in_loop": n, "bytes": total}`` — the grad-accum contract is
    ``in_loop == 0`` and ``boundary``/``bytes`` independent of the
    accumulation factor N (tests/test_grad_accum.py)."""
    recs = jaxpr_collectives(fn_or_jaxpr, *args)
    out = {"boundary": 0, "in_loop": 0, "bytes": 0}
    for r in recs:
        if r["prim"] == "all_gather" or r["bytes"] < min_bytes:
            continue
        if dp_axes is not None and not set(r["axes"]) & set(dp_axes):
            continue
        out["in_loop" if r["in_loop"] else "boundary"] += 1
        out["bytes"] += r["bytes"]
    return out


# the collectives XLA can emit; async pairs appear as NAME-start /
# NAME-done and are one transfer, counted at the -start
_HLO_COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(-start|-done)?\(")

# shape tokens like f32[8,128], bf16[256], pred[], s8[4,4]: first digit
# run in the dtype is the bit width (pred is 1 byte)
_HLO_SHAPE_RE = re.compile(r"\b(pred|bf16|[fsu]\d+\w*)\[([\d,]*)\]")


def _hlo_shape_bytes(s: str) -> int:
    total = 0
    for dt, dims in _HLO_SHAPE_RE.findall(s):
        if dt == "pred":
            item = 1
        else:
            m = re.search(r"\d+", dt)
            item = max(1, int(m.group()) // 8) if m else 4
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * item
    return total


def compiled_hlo_text(fn, *args, **kwargs) -> str:
    """The COMPILED HLO module of ``fn(*args)`` as text. ``fn`` may be a
    jitted callable (has ``.lower``) or a plain function (jitted here);
    ``args`` may be arrays or ``ShapeDtypeStruct``s. HLO text is a
    compiler-internal format: callers must try/except what they parse
    out of it rather than let a dialect change break a run."""
    lowered = (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(
        *args, **kwargs)
    return lowered.compile().as_text()


def hlo_collectives(fn, *args, **kwargs):
    """POST-COMPILE collective census: count the cross-device
    collectives (and their result wire bytes) in the compiled HLO
    module of ``fn(*args)``.

    The jaxpr census above sees only collectives present BEFORE
    compilation — explicit ``psum``/``shard_map`` traffic. On the pure
    SPMD-jit path the partitioner INSERTS the collectives during
    compilation, so :func:`jaxpr_collectives` truthfully reports 0
    while the wire is busy (the PR 8 gap ``--collective_stats``
    documents). Reading the compiled module closes it: whatever XLA
    actually emitted — including partitioner-inserted all-reduces and
    async ``-start``/``-done`` pairs (counted once, at the start) —
    is counted here. See :func:`count_hlo_collectives` for the record."""
    return count_hlo_collectives(compiled_hlo_text(fn, *args, **kwargs))


def count_hlo_collectives(txt: str) -> dict:
    """``{"ops": {name: count}, "count", "bytes"}`` over compiled HLO
    text; bytes are each op's RESULT shape sizes — the per-participant
    output payload, comparable to the jaxpr census's operand-bytes
    convention up to the algorithm's constant."""
    ops: dict[str, int] = {}
    count = 0
    nbytes = 0
    for line in txt.splitlines():
        m = _HLO_COLLECTIVE_RE.search(line)
        if m is None or m.group(2) == "-done":
            continue
        name = m.group(1)
        ops[name] = ops.get(name, 0) + 1
        count += 1
        # result shapes sit between '=' and the op name; fall back to
        # the whole line when the layout is unexpected
        head = line.split("=", 1)[0] if "=" in line else line
        lhs = line[len(head) + 1:line.index(m.group(0))] \
            if "=" in line else line
        nbytes += _hlo_shape_bytes(lhs)
    return {"ops": ops, "count": count, "bytes": nbytes}


# a Pallas kernel reaches the device as a Mosaic custom call; its name
# (the ``name=`` every kernel in ops/pallas passes) rides the op_name
# metadata as a scope
_MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_KERNEL_NAME_RE = re.compile(r"\bdcp_[a-z0-9_]+")
def count_hlo_kernels(txt: str) -> dict:
    """POST-COMPILE kernel census, the custom-call sibling of
    :func:`count_hlo_collectives`: the Mosaic (Pallas) calls in
    compiled HLO text, by kernel name. Returns ``{"count": n,
    "kernels": {name: count}, "shapes": {name: [shape, ...]}}`` —
    ``shapes`` holds the first call's RESULT shapes per kernel (compiled
    HLO prints operands by name only), so a caller can check each
    chip's kernel covers its LOCAL batch (no gather feeding it). A
    kernel that fell back to an XLA path is simply absent: this, not a
    log line, is the proof it reached the device."""
    kernels: dict[str, int] = {}
    shapes: dict[str, list] = {}
    for line in txt.splitlines():
        if _MOSAIC_TARGET not in line:
            continue
        m = _OP_NAME_RE.search(line)
        names = _KERNEL_NAME_RE.findall(m.group(1)) if m else []
        name = names[-1] if names else "unnamed"
        kernels[name] = kernels.get(name, 0) + 1
        if name not in shapes:
            # result shapes sit between '=' and the op
            lhs = line.partition("=")[2].partition(" custom-call(")[0]
            shapes[name] = [f"{dt}[{dims}]" for dt, dims
                            in _HLO_SHAPE_RE.findall(lhs)]
    return {"count": sum(kernels.values()), "kernels": kernels,
            "shapes": shapes}
