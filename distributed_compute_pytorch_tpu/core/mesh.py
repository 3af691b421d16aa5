"""Device mesh / topology and multi-host rendezvous.

Replaces the reference's process-group lifecycle (``/root/reference/main.py:47-53``:
env-var TCP rendezvous on hard-coded ``localhost:12355`` + gloo) and its
one-process-per-device spawn (``main.py:150``) with the TPU-idiomatic design:

- ONE process per host, ``jax.distributed.initialize`` for multi-host
  rendezvous (the coordinator plays the MASTER_ADDR role).
- A named ``jax.sharding.Mesh`` over all devices; parallelism is expressed as
  sharding over named axes and compiled collectives ride ICI within a slice
  and DCN across slices — no gloo/NCCL equivalent to hand-write.

Canonical axis names used throughout the framework:

====== =============================================================
axis   meaning
====== =============================================================
data   data parallel (batch sharding; grads psum over this axis)
fsdp   parameter/optimizer sharding (ZeRO-3 style), also shards batch
tensor tensor (Megatron-style) model parallelism
seq    sequence/context parallelism (ring attention)
pipe   pipeline stages
expert expert parallelism (MoE)
====== =============================================================

For tests without TPU hardware, fake an N-device CPU mesh with
``XLA_FLAGS=--xla_force_host_platform_device_count=N JAX_PLATFORMS=cpu``
(must be set before JAX backends initialise — see ``tests/conftest.py``).
"""

from __future__ import annotations

import dataclasses
import math
import os
from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Axes over which the global batch is sharded. Everything else (tensor, seq,
# pipe) sees the same examples.
BATCH_AXES = ("data", "fsdp")
ALL_AXES = ("data", "fsdp", "tensor", "seq", "pipe", "expert")

_initialized = False


def initialize_distributed(coordinator: str | None = None,
                           num_processes: int | None = None,
                           process_id: int | None = None) -> None:
    """Multi-host rendezvous — the ``setup()`` equivalent (``main.py:47-50``).

    A no-op for single-process runs (the common dev/test path). On a TPU pod,
    call once per host before touching devices; all hosts block until the
    full world joins, exactly like ``dist.init_process_group`` blocking on
    rendezvous (``main.py:50``), except there is one process per *host*, not
    per device.
    """
    global _initialized
    if _initialized:
        return
    if coordinator is None and num_processes is None:
        # Single-controller / auto-detected environments (Cloud TPU metadata,
        # or plain single-process): nothing to do.
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True


def process_count() -> int:
    return jax.process_count()


def process_index() -> int:
    """This host's index — the closest analogue of the reference's ``rank``."""
    return jax.process_index()


def is_coordinator() -> bool:
    """True on the logical rank-0 host (reference's ``rank == 0`` guards,
    ``main.py:66,93``)."""
    return jax.process_index() == 0


@dataclass(frozen=True)
class MeshSpec:
    """An ordered mapping of axis name -> size; at most one size may be -1
    (inferred from the device count), mirroring the ergonomics of the
    reference's single ``--gpus`` knob (``main.py:144``)."""

    axes: tuple[tuple[str, int], ...]

    @classmethod
    def parse(cls, spec: str | dict[str, int]) -> "MeshSpec":
        if isinstance(spec, str):
            d: dict[str, int] = {}
            for part in spec.split(","):
                part = part.strip()
                if not part:
                    continue
                name, _, size = part.partition("=")
                d[name.strip()] = int(size) if size else -1
            spec = d or {"data": -1}
        for name in spec:
            if name not in ALL_AXES:
                raise ValueError(
                    f"unknown mesh axis {name!r}; known axes: {ALL_AXES}")
        return cls(axes=tuple(spec.items()))

    def resolve(self, n_devices: int) -> "MeshSpec":
        """Fill in a single -1 so the axis sizes multiply to ``n_devices``."""
        sizes = dict(self.axes)
        unknown = [k for k, v in sizes.items() if v == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {unknown}")
        known = math.prod(v for v in sizes.values() if v != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes {sizes}")
            sizes[unknown[0]] = n_devices // known
        elif known > n_devices:
            raise ValueError(
                f"mesh {sizes} wants {known} devices, have {n_devices}")
        # known < n_devices is allowed: make_mesh undersubscribes onto the
        # first `known` devices (elastic resize / deliberate partial use)
        return MeshSpec(axes=tuple(sizes.items()))

    @property
    def names(self) -> tuple[str, ...]:
        return tuple(k for k, _ in self.axes)

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(v for _, v in self.axes)

    def size(self, name: str) -> int:
        return dict(self.axes).get(name, 1)


def make_mesh(spec: str | dict[str, int] | MeshSpec = "data=-1",
              devices: list | None = None) -> Mesh:
    """Build the named device mesh the whole framework computes over.

    This is the structural replacement for the reference's world: where
    ``main.py`` had ``world_size`` processes each owning one device
    (``main.py:148,150``), we have one ``Mesh`` whose axes carry the
    parallelism. Data-parallel world size == ``mesh.shape['data'] *
    mesh.shape.get('fsdp', 1)``.
    """
    if not isinstance(spec, MeshSpec):
        spec = MeshSpec.parse(spec)
    if devices is None:
        devices = jax.devices()
    spec = spec.resolve(len(devices))
    total = int(np.prod(spec.shape))
    if total < len(devices):
        # an explicit spec smaller than the attached device set is the
        # elastic-resize case (resume a preempted v4-32 run on a v4-8, or
        # deliberately undersubscribe a shared host): use the first N.
        # Single-process only — in a multi-process run devices[:N] could
        # strip every device of a later process, which would then hang in
        # the first collective; resize across hosts by relaunching with
        # fewer processes instead.
        if jax.process_count() > 1:
            raise ValueError(
                f"mesh spec {dict(zip(spec.names, spec.shape))} uses "
                f"{total} of {len(devices)} devices; undersubscription is "
                f"single-process only — relaunch with fewer processes")
        import warnings
        warnings.warn(
            f"mesh spec {dict(zip(spec.names, spec.shape))} uses "
            f"{total} of {len(devices)} devices", stacklevel=2)
        devices = devices[:total]
    dev_array = np.asarray(devices).reshape(spec.shape)
    return Mesh(dev_array, axis_names=spec.names)


_mesh_stack: list[Mesh] = []


class use_mesh:
    """Context manager establishing the *current* mesh, so layers deep inside
    a model (e.g. ring attention picking its ``seq`` axis) can find the mesh
    without threading it through every call signature."""

    def __init__(self, mesh: Mesh):
        self.mesh = mesh

    def __enter__(self) -> Mesh:
        _mesh_stack.append(self.mesh)
        return self.mesh

    def __exit__(self, *exc) -> None:
        _mesh_stack.pop()


def current_mesh() -> Mesh | None:
    return _mesh_stack[-1] if _mesh_stack else None


_manual_stack: list[frozenset] = []


class use_manual_axes:
    """Trace-time declaration that ``axes`` are MANUAL in the enclosing
    shard_map region. ``constrain``/``constrain_replicated`` and
    BatchNorm's sync statistics (:func:`manual_batch_axes`) consult
    this and treat the declared axes' dims as local. Used by the
    dp-manual train paths (``train/step.py``), whose shard_map body
    runs the whole model forward manual over the dp axes.
    """

    def __init__(self, axes):
        self.axes = frozenset(axes)

    def __enter__(self):
        _manual_stack.append(self.axes)
        return self

    def __exit__(self, *exc):
        _manual_stack.pop()


def _manual_axis_names() -> tuple[set, object]:
    """``(manual_axis_names, abstract_mesh_or_None)`` from the current
    trace context: the axes the abstract mesh types Manual, plus those
    declared via :class:`use_manual_axes`."""
    extra: set = set().union(*_manual_stack) if _manual_stack else set()
    am = jax.sharding.get_abstract_mesh()
    if am is None or am.empty:
        return extra, None
    manual = {n for n, t in zip(am.axis_names, am.axis_types)
              if t == jax.sharding.AxisType.Manual}
    return manual | extra, am


def manual_batch_axes():
    """``(axes, world)``: the BATCH axes that are currently MANUAL (the
    step functions run their grad-accum / quantized bodies inside a
    shard_map manual over the dp axes) and their combined size.

    Layers whose train-time math reduces over the batch dimension
    (BatchNorm) consult this: inside such a region the batch dim is
    shard-LOCAL, so a plain ``jnp.mean`` would compute per-replica
    statistics — psum/pmean over the returned axes restores the global
    (sync-BN) semantics the framework pins (``tests/test_batchnorm.py``).
    Returns ``((), 1)`` outside manual regions, where the automatic
    partitioner already inserts the cross-device reduction."""
    mesh = current_mesh()
    if mesh is None or not _manual_stack:
        return (), 1
    manual, _ = _manual_axis_names()
    axes = tuple(a for a in BATCH_AXES
                 if a in manual and a in mesh.axis_names
                 and mesh.shape[a] > 1)
    world = math.prod(mesh.shape[a] for a in axes) if axes else 1
    return axes, world


def constrain(x, spec: P):
    """Pin ``x``'s sharding when a mesh context is active (no-op off-mesh).

    Axes absent from the mesh (or size 1) are dropped from the spec, so
    callers can name their ideal layout unconditionally. Inside a
    shard_map manual region (the pipeline runs blocks manual over
    ``pipe``/``seq``) the constraint is built on the ABSTRACT mesh — it
    knows which axes are Manual — and may only name still-Auto axes;
    a constraint on the concrete mesh there is an error.
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    manual, am = _manual_axis_names()

    def clean(entry):
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in mesh.axis_names
                         and mesh.shape[a] > 1 and a not in manual)
            return kept or None
        return (entry if (entry in mesh.axis_names and mesh.shape[entry] > 1
                          and entry not in manual) else None)

    cleaned = tuple(clean(a) for a in spec)
    if all(a is None for a in cleaned):
        return x
    target = am if (manual and am is not None) else mesh
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(target, P(*cleaned)))


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    """``NamedSharding(mesh, spec)`` with axes absent from the mesh (or
    size 1) dropped from the spec — the out-of-jit counterpart of
    :func:`constrain`, for ``jax.device_put`` of host-built arrays into
    their ideal layout (the serving loop's persistent KV caches,
    ``serve.ContinuousBatcher``). Callers name the full ideal spec
    unconditionally and get whatever subset the mesh can express."""
    def clean(entry):
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry
                         if a in mesh.axis_names and mesh.shape[a] > 1)
            return kept or None
        return (entry if (entry in mesh.axis_names
                          and mesh.shape[entry] > 1) else None)

    return NamedSharding(mesh, P(*(clean(a) for a in spec)))


def constrain_replicated(x):
    """Pin ``x`` fully replicated when a mesh context is active (no-op
    off-mesh and inside manual regions).

    ``constrain`` can't express this — it drops all-``None`` specs as a
    no-op — so the gather-output numerics guard (``layers.Embedding``)
    gets its own entry point."""
    mesh = current_mesh()
    if mesh is None:
        return x
    manual, _ = _manual_axis_names()
    if manual:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*([None] * x.ndim))))


def constrain_activations(x, manual_axes=(), seq_axis: str = "seq"):
    """Residual-stream layout pin: ``[B, T, d]`` batch-sharded over
    ``(data, fsdp)``, everything else replicated — the canonical
    activation layout between transformer blocks.

    Two reasons this exists: (1) it is the layout the scaling-book recipe
    wants (activations follow the batch; TP collectives stay inside the
    block); (2) it is a NUMERICS guard — on 3-axis meshes (batch over
    data x fsdp, params over fsdp x tensor) XLA's SPMD partitioner has
    been observed to MISCOMPILE unannotated residual + TP-matmul chains
    (wrong values on the mixed shards; repro'd pure-jax on jax 0.9.0 CPU
    — see tests/test_generate.py's 3-axis mesh cases). Explicit
    boundary pins keep the partitioner on the well-trodden path.

    No-op inside manual regions (the pipeline owns layout there) and on
    ring/seq meshes (the ring's shard_map owns the token dim)."""
    if manual_axes:
        return x
    mesh = current_mesh()
    if mesh is not None and dict(mesh.shape).get(seq_axis, 1) > 1:
        return x
    return constrain(x, P(("data", "fsdp"), None, None))


def constrain_seq_parallel(x, manual_axes=(), seq_axis: str = "seq"):
    """Megatron sequence-parallel activation pin: residual stream
    ``[B, T, d]`` with the token dim sharded over ``tensor``. Shared by
    every transformer block family (one policy, one place). No-op inside
    manual regions (the pipeline owns layout there) and when a ring/seq
    axis already owns the token dim."""
    if manual_axes:
        return x
    mesh = current_mesh()
    if mesh is not None and dict(mesh.shape).get(seq_axis, 1) > 1:
        return x
    return constrain(x, P(("data", "fsdp"), "tensor", None))


def batch_sharding(mesh: Mesh, ndim: int = 1) -> NamedSharding:
    """Sharding for a global batch: leading dim split over the batch axes
    present in ``mesh``, remaining dims replicated. The SPMD analogue of the
    reference's ``DistributedSampler`` handing each rank its slice
    (``main.py:109``) — except the split happens in the array's sharding, not
    in N separate processes."""
    axes = tuple(a for a in BATCH_AXES if a in mesh.axis_names and
                 mesh.shape[a] > 1) or tuple(
        a for a in BATCH_AXES if a in mesh.axis_names)
    spec = P(axes if axes else None, *([None] * (ndim - 1)))
    return NamedSharding(mesh, spec)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def dp_world_size(mesh: Mesh) -> int:
    """Number of data-parallel shards (the reference's ``world_size``,
    ``main.py:148``)."""
    return math.prod(mesh.shape[a] for a in BATCH_AXES if a in mesh.axis_names)


def local_batch_size(global_batch: int, mesh: Mesh) -> int:
    ws = dp_world_size(mesh)
    if global_batch % ws:
        raise ValueError(f"global batch {global_batch} not divisible by "
                         f"data-parallel world size {ws}")
    return global_batch // ws
