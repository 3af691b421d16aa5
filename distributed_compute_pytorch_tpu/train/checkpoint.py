"""Checkpoint save/restore.

The reference saves once, at end of training, from *every* rank to the same
path (``/root/reference/main.py:133`` — a write race, SURVEY §A.6) and has no
restore path at all. Here (SURVEY §5.4):

- exactly one logical writer per datum (coordinator for the single-file
  format; each process for its own shards in the sharded format),
- a stable schema independent of the parallelism strategy (a checkpoint
  written under FSDP restores under pure DP, a different mesh size — the
  elastic-resize path — and vice versa; likewise ZeRO-1's dp-sharded
  ``opt_state`` — ``train/step.py shard_update`` — saves in logical
  form and restores into either the sharded or the replicated layout,
  ``tests/test_zero1.py``),
- a restore path, including restore-into-sharded-layout.

Two formats:

- **v1 single-file** (default, ``save``): one ``.npz`` of path-flattened
  unsharded leaves + JSON manifest. Simple, portable — but gathering every
  leaf to one host is O(total params) host RAM and defeats FSDP at scale.
- **v2 sharded** (``save_sharded``): a DIRECTORY. Each process writes only
  its addressable shard data (``part-NNNNN.npz`` + ``part-NNNNN.json``
  listing each entry's leaf and index span) with no cross-host
  communication and no full-leaf materialisation; the coordinator commits
  ``manifest.json`` last. ``restore`` reassembles any mesh layout via
  ``jax.make_array_from_callback``, reading only the spans each host needs.

``AsyncCheckpointer`` overlaps the file write with training: the
device->host fetch is synchronous (the values must be this step's), the
serialisation+write happens on a background thread, and the next save (or
close) joins the previous write first.

Integrity + retention (the silent-corruption story): every leaf (v1)
and every shard entry (v2) is saved with a CRC-32 of its raw bytes in
the manifest/part index, and restore VERIFIES what it reads — a
bit-rotted or truncated-but-loadable file surfaces as a clear
:class:`CheckpointCorruptError` naming the leaf, never as silently
wrong weights. (CRC-32 is an integrity check against storage/transfer
corruption, not a cryptographic signature.) ``keep_last=N`` retains the
last N checkpoints — v1 single files rotate to ``{path}.prev-K``, v2
directories keep N part GENERATIONS with a ``history`` list in the
manifest — and :func:`restore_with_fallback` walks them newest-first,
returning the newest checkpoint that verifies (the trainer's resume
path, so one corrupted save costs ``checkpoint_every`` steps, not the
run).

No framework-specific pickle anywhere — everything is plain numpy + JSON.
``.npz`` holds only numpy's own dtypes, so ml_dtypes leaves (bfloat16
parameters and optimizer moments) are stored as their same-width unsigned
view; every leaf's dtype name is recorded beside its CRC and views the
bytes back on restore.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from distributed_compute_pytorch_tpu.core.mesh import is_coordinator
from distributed_compute_pytorch_tpu.utils.fsio import atomic_write

PyTree = Any
_FORMAT_VERSION = 1
_SHARDED_VERSION = 2
_SEP = "::"
_MANIFEST = "manifest.json"


class CheckpointCorruptError(RuntimeError):
    """A checkpoint failed its integrity verification (CRC mismatch,
    unreadable part, or torn container) — restore from a different
    checkpoint (:func:`restore_with_fallback` automates that)."""


def _crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).tobytes()) & 0xFFFFFFFF


def _storable(arr: np.ndarray) -> np.ndarray:
    """``arr`` as ``.npz`` can hold it: ml_dtypes leaves (bfloat16) load
    back as opaque void records, so they go in as the unsigned view of
    the same width (same bytes, same CRC)."""
    if arr.dtype.isbuiltin == 1:
        return arr
    return arr.view(f"u{arr.dtype.itemsize}")


def _as_saved(arr: np.ndarray, dtype_name: str | None) -> np.ndarray:
    """Undo :func:`_storable` with the dtype name the save recorded
    (``None``: a checkpoint from before names were recorded — numpy's
    own dtypes only, nothing to undo)."""
    if dtype_name is None or arr.dtype.name == dtype_name:
        return arr
    return arr.view(jnp.dtype(dtype_name))


def dominant_float_dtype(leaves):
    """The floating dtype holding the most elements among ``(shape,
    dtype)`` pairs — what ``--param_dtype`` set, since norm scales stay
    float32 under bfloat16 weights. ``None`` when nothing floats."""
    sizes: dict = {}
    for shape, dtype in leaves:
        dtype = jnp.dtype(dtype)
        if jnp.issubdtype(dtype, jnp.floating):
            sizes[dtype] = sizes.get(dtype, 0) + int(np.prod(shape))
    return max(sizes, key=sizes.get) if sizes else None


def _rotate(path: str, keep_last: int) -> None:
    """Shift ``path`` -> ``path.prev-1`` -> ... -> ``path.prev-(N-1)``
    (files or directories), dropping the oldest. Called before a v1
    write so the last ``keep_last`` checkpoints stay restorable."""
    if keep_last <= 1 or not os.path.exists(path):
        return
    oldest = f"{path}.prev-{keep_last - 1}"
    if os.path.isdir(oldest):
        shutil.rmtree(oldest, ignore_errors=True)
    elif os.path.exists(oldest):
        os.unlink(oldest)
    for k in range(keep_last - 2, 0, -1):
        src = f"{path}.prev-{k}"
        if os.path.exists(src):
            os.replace(src, f"{path}.prev-{k + 1}")
    os.replace(path, f"{path}.prev-1")


def _flatten(tree: PyTree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        flat[key] = np.asarray(leaf)
    return flat


def _gather_host(tree: PyTree) -> PyTree:
    """Bring every leaf to host, unsharded.

    For multi-host sharded arrays (some shards not addressable locally),
    all-gather via a replicated device_put first.
    """
    def fetch(x):
        if isinstance(x, jax.Array) and jnp.issubdtype(
                x.dtype, jax.dtypes.prng_key):
            # unwrap BEFORE the allgather: key-dtype arrays reject
            # np.asarray, and under multi-host the rng key is replicated
            # but not fully addressable
            x = jax.random.key_data(x)
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            from jax.experimental import multihost_utils
            return np.asarray(multihost_utils.process_allgather(
                x, tiled=True))
        return np.asarray(x)
    return jax.tree.map(fetch, tree)


def _write_v1(path: str, host_tree, epoch: int, extra: dict | None,
              keep_last: int = 1) -> None:
    """Serialise + atomically write an (already host-gathered) tree as the
    v1 single file. Shared by the sync and async paths so the schema cannot
    drift between them. The manifest records a CRC-32 per leaf (verified
    on restore); ``keep_last > 1`` rotates the existing file to
    ``.prev-1`` first so the previous good checkpoint survives."""
    flat = _flatten(host_tree)
    manifest = {"format": _FORMAT_VERSION, "epoch": epoch,
                "extra": extra or {},
                "checksums": {k: _crc(v) for k, v in flat.items()},
                "leaves": {k: [v.dtype.name, list(v.shape)]
                           for k, v in flat.items()}}
    _rotate(path, keep_last)
    atomic_write(path,
                 lambda f: np.savez(f, __manifest__=json.dumps(manifest),
                                    **{k: _storable(v)
                                       for k, v in flat.items()}))


def save(path: str, state, *, epoch: int = 0, extra: dict | None = None,
         keep_last: int = 1) -> None:
    """Write ``state`` (a TrainState or any pytree) to ``path``.

    Coordinator-only write with atomic rename — the fix for the reference's
    every-rank-writes race (``main.py:133``). ``keep_last``: retain that
    many checkpoints (rotated ``.prev-K`` files; module docstring).
    """
    host_tree = _gather_host(state)   # collective: all processes participate
    if not is_coordinator():
        return
    _write_v1(path, host_tree, epoch, extra, keep_last)


def load_manifest(path: str) -> dict:
    if os.path.isdir(path):
        with open(os.path.join(path, _MANIFEST)) as f:
            return json.load(f)
    with np.load(path, allow_pickle=False) as z:
        return json.loads(str(z["__manifest__"]))


# ---------------------------------------------------------------------------
# v2 sharded format
# ---------------------------------------------------------------------------


def _unwrap_keys(tree: PyTree) -> PyTree:
    """PRNG-key leaves -> raw uint32 data (key dtype rejects np.asarray)."""
    def unwrap(x):
        if isinstance(x, jax.Array) and jnp.issubdtype(
                x.dtype, jax.dtypes.prng_key):
            return jax.random.key_data(x)
        return x
    return jax.tree.map(unwrap, tree)


def _span_of(index: tuple, shape: tuple[int, ...]) -> list[list[int]]:
    """Normalise a device-shard index (tuple of slices) to [[lo, hi], ...]."""
    out = []
    for sl, dim in zip(index, shape):
        lo = sl.start or 0
        hi = sl.stop if sl.stop is not None else dim
        out.append([int(lo), int(hi)])
    # index tuples can be shorter than rank (trailing dims unsharded)
    for dim in shape[len(index):]:
        out.append([0, int(dim)])
    return out


def exists(path: str) -> bool:
    """Is there a COMMITTED checkpoint at ``path``? A sharded directory
    without its manifest (crash mid-save) counts as no checkpoint."""
    if os.path.isdir(path):
        return os.path.exists(os.path.join(path, _MANIFEST))
    return os.path.isfile(path)


def save_sharded(path: str, state, *, epoch: int = 0,
                 extra: dict | None = None, keep_last: int = 1) -> None:
    """Write ``state`` as a sharded checkpoint DIRECTORY at ``path``.

    Each process writes exactly the index spans it is the *lowest-indexed
    owner* of — replicated leaves are written once (by the span's first
    owner, the coordinator for fully-replicated ones), sharded leaves are
    written without ever materialising the full array, and no cross-host
    gather happens at all.

    Crash safety: every save is a new *generation* — part files are named
    ``part-g{G}-NNNNN`` and the commit point is the atomic replace of
    ``manifest.json`` (which records G). A crash mid-save leaves the
    previous generation's manifest and parts untouched; the half-written
    new generation is pruned by the next successful save. Every process
    derives G by reading the current manifest itself (only the coordinator
    ever writes it, and saves are collectively ordered), so no
    communication is needed.

    Integrity + retention: every entry carries a CRC-32 (verified on
    restore — module docstring); ``keep_last > 1`` retains the parts of
    the last N generations, listed in the manifest's ``history`` so
    :func:`restore_with_fallback` can reach them when the newest
    generation is corrupt.
    """
    state = _unwrap_keys(state)
    pid = jax.process_index()
    n_proc = jax.process_count()
    os.makedirs(path, exist_ok=True)
    if n_proc > 1:
        # order generation derivation after the previous save's commit:
        # without this, a fast process could enter save N+1 and read the
        # gen-(N-1) manifest while the coordinator still writes gen N
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("dcp:ckpt-sharded-begin")
    try:
        prev_manifest = load_manifest(path)
    except FileNotFoundError:
        prev_manifest = None
    gen = (0 if prev_manifest is None
           else int(prev_manifest.get("generation", -1)) + 1)
    flat_entries: dict[str, np.ndarray] = {}
    part_index: list[dict] = []
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = _SEP.join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in keypath)
        if not isinstance(leaf, jax.Array):
            # host scalars/arrays are replicated by construction
            if is_coordinator():
                arr = np.asarray(leaf)
                name = f"{key}@full"
                flat_entries[name] = _storable(arr)
                part_index.append({"key": key, "entry": name,
                                   "span": _span_of((), arr.shape),
                                   "gshape": list(arr.shape),
                                   "dtype": arr.dtype.name,
                                   "crc32": _crc(arr)})
            continue
        shape = leaf.shape
        # lowest process index owning each distinct span writes it; every
        # process can compute ownership from the (global) sharding map, so
        # no communication is needed
        owners: dict[tuple, int] = {}
        for dev, idx in leaf.sharding.devices_indices_map(shape).items():
            span = tuple(tuple(s) for s in _span_of(idx, shape))
            p = dev.process_index
            if span not in owners or p < owners[span]:
                owners[span] = p
        mine = {span for span, p in owners.items() if p == pid}
        for shard in leaf.addressable_shards:
            span = tuple(tuple(s) for s in _span_of(shard.index, shape))
            if span not in mine:
                continue
            mine.discard(span)      # each distinct span once per process
            name = f"{key}@" + ",".join(f"{lo}:{hi}" for lo, hi in span)
            data = np.asarray(shard.data)
            flat_entries[name] = _storable(data)
            part_index.append({"key": key, "entry": name,
                               "span": [list(s) for s in span],
                               "gshape": list(shape),
                               "dtype": data.dtype.name,
                               "crc32": _crc(data)})
    part_file = f"part-g{gen}-{pid:05d}.npz"
    atomic_write(os.path.join(path, part_file),
                 lambda f: np.savez(f, **flat_entries))
    atomic_write(os.path.join(path, f"part-g{gen}-{pid:05d}.json"),
                 lambda f: json.dump({"file": part_file,
                                      "entries": part_index}, f),
                 mode="w")
    if n_proc > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("dcp:ckpt-sharded-parts")
    if is_coordinator():
        # retention history: this generation first, then the previous
        # manifest's surviving history (legacy manifests without one
        # contribute their own generation), truncated to keep_last
        cur = {"generation": gen, "epoch": epoch, "extra": extra or {},
               "num_parts": n_proc}
        hist = [cur]
        if prev_manifest is not None:
            ph = prev_manifest.get("history")
            if ph is None and prev_manifest.get("generation") is not None:
                ph = [{"generation": int(prev_manifest["generation"]),
                       "epoch": prev_manifest.get("epoch", 0),
                       "extra": prev_manifest.get("extra", {}),
                       "num_parts": prev_manifest.get("num_parts",
                                                      n_proc)}]
            hist += [h for h in (ph or [])
                     if int(h["generation"]) != gen]
        hist = hist[:max(1, int(keep_last))]
        manifest = {"format": _SHARDED_VERSION, "epoch": epoch,
                    "extra": extra or {},
                    "generation": gen, "num_parts": n_proc,
                    "history": hist}
        # COMMIT: atomic replace; the previous generation stays valid
        # until this succeeds
        atomic_write(os.path.join(path, _MANIFEST),
                     lambda f: json.dump(manifest, f), mode="w")
        # best-effort prune of generations that fell out of retention
        kept = {f"part-g{int(h['generation'])}-" for h in hist}
        for fn in os.listdir(path):
            if (fn.startswith("part-")
                    and not any(fn.startswith(p) for p in kept)):
                try:
                    os.unlink(os.path.join(path, fn))
                except OSError:
                    pass


def _sharded_entry_map(path: str,
                       generation: int | None = None) -> dict[str, list]:
    """leaf key -> [(part_file, entry_name, span, gshape, crc, dtype), ...].

    Reads exactly the ``num_parts`` part manifests of the committed
    manifest's generation — parts from other (stale or half-written)
    generations are never consulted. ``generation`` overrides which
    RETAINED generation to read (the restore-fallback path; it must
    appear in the manifest's ``history``)."""
    manifest = load_manifest(path)
    n = int(manifest.get("num_parts", 0))
    gen = manifest.get("generation")
    if generation is not None:
        hit = [h for h in manifest.get("history", [])
               if int(h["generation"]) == int(generation)]
        if not hit:
            raise FileNotFoundError(
                f"{path}: generation {generation} is not in the "
                f"manifest's retention history")
        gen = int(generation)
        n = int(hit[0].get("num_parts", n))
    entries: dict[str, list] = {}
    for i in range(n):
        if gen is None:
            # pre-generation layout (manifests without the key): unprefixed
            # part names
            part_path = os.path.join(path, f"part-{i:05d}.json")
        else:
            part_path = os.path.join(path, f"part-g{int(gen)}-{i:05d}.json")
        if not os.path.exists(part_path):
            raise FileNotFoundError(
                f"{path}: manifest names {n} parts (generation {gen}) but "
                f"part {i} is missing (incomplete or corrupted checkpoint)")
        with open(part_path) as f:
            part = json.load(f)
        for e in part["entries"]:
            entries.setdefault(e["key"], []).append(
                (part["file"], e["entry"], e["span"], e.get("gshape"),
                 e.get("crc32"), e.get("dtype")))
    return entries


def _assemble(path: str, pieces, span_lo, out):
    """Fill ``out`` (whose global position starts at ``span_lo``) from any
    overlapping saved pieces, verifying each piece's CRC as it is read.
    ``pieces``: [(file, entry, span, gshape, crc, dtype), ...]."""
    zcache: dict[str, Any] = {}
    try:
        for fname, entry, span, _, crc, dtype_name in pieces:
            # overlap of [span] with [span_lo, span_lo+out.shape)
            sel_src, sel_dst = [], []
            ok = True
            for (lo, hi), olo, n in zip(span, span_lo, out.shape):
                s = max(lo, olo)
                e = min(hi, olo + n)
                if s >= e:
                    ok = False
                    break
                sel_src.append(slice(s - lo, e - lo))
                sel_dst.append(slice(s - olo, e - olo))
            if not ok:
                continue
            if fname not in zcache:
                try:
                    zcache[fname] = np.load(os.path.join(path, fname),
                                            allow_pickle=False)
                except Exception as e:  # torn zip container
                    raise CheckpointCorruptError(
                        f"{path}/{fname}: unreadable part file "
                        f"({e})") from e
            data = _as_saved(zcache[fname][entry], dtype_name)
            if crc is not None and _crc(data) != crc:
                # verify-on-restore: bit rot / torn writes surface as a
                # clear error, never as silently wrong weights
                raise CheckpointCorruptError(
                    f"{path}/{fname}: entry {entry!r} failed its CRC-32 "
                    f"integrity check (corrupted checkpoint)")
            out[tuple(sel_dst)] = data[tuple(sel_src)]
    finally:
        for z in zcache.values():
            z.close()


def _restore_sharded(path: str, template, shardings=None, *,
                     _prefix: str = "", generation: int | None = None):
    entries = _sharded_entry_map(path, generation)
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    flat_shardings = (jax.tree_util.tree_leaves(shardings)
                      if shardings is not None else [None] * len(paths))
    leaves = []
    for (path_keys, leaf), shard in zip(paths, flat_shardings):
        key = _prefix + _SEP.join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path_keys)
        if key not in entries:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        pieces = entries[key]
        is_key = _is_key_leaf(leaf)
        if is_key and not isinstance(leaf, jax.Array):
            # key-data shape depends on the key impl; abstract templates
            # (params-only restores) never carry key leaves
            raise TypeError(
                f"leaf {key!r} is a PRNG key; the v2 restore needs a "
                f"concrete template for key leaves")
        shape = tuple(jax.random.key_data(leaf).shape if is_key
                      else _leaf_shape(leaf))
        dtype = (jax.random.key_data(leaf).dtype if is_key
                 else getattr(leaf, "dtype", None))
        saved_shape = pieces[0][3]
        if saved_shape is not None and tuple(saved_shape) != shape:
            # without this check the span-assembly would silently zero-fill
            # the uncovered region of a resized leaf
            raise ValueError(
                f"checkpoint leaf {key!r} was saved with shape "
                f"{tuple(saved_shape)} but the template wants {shape} — "
                f"model configuration changed since the save")

        def read_span(index, shape=shape, dtype=dtype, pieces=pieces):
            lo = [sl.start or 0 for sl in index] + [0] * (len(shape) - len(index))
            n = [((sl.stop if sl.stop is not None else shape[i])
                  - (sl.start or 0)) for i, sl in enumerate(index)]
            n += list(shape[len(index):])
            out = np.zeros(tuple(n), dtype)
            _assemble(path, pieces, lo, out)
            return out

        if shard is not None and not is_key:
            # each host reads only the spans its devices need — restore
            # stays O(local shard bytes) even when the mesh changed size
            # (elastic resize) or layout (FSDP <-> DP)
            new = jax.make_array_from_callback(shape, shard, read_span)
        else:
            full = read_span(tuple(slice(0, s) for s in shape))
            if is_key:
                new = jax.random.wrap_key_data(jnp.asarray(full))
            else:
                new = jnp.asarray(full, dtype=dtype)
            if shard is not None:
                new = jax.device_put(new, shard)
        leaves.append(new)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class AsyncCheckpointer:
    """Overlap checkpoint writes with training.

    ``save`` fetches/serialises the state synchronously only as far as
    required for correctness (device->host copies of this step's values),
    then hands the file write to a background thread. A new ``save`` (or
    ``close``/context exit) joins the previous write first, so at most one
    write is in flight and the newest checkpoint always wins. Exceptions
    from the writer surface on the next call.
    """

    def __init__(self, sharded: bool = False, keep_last: int = 1):
        self.sharded = sharded
        self.keep_last = keep_last
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    def _join(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, path: str, state, *, epoch: int = 0,
             extra: dict | None = None) -> None:
        self._join()
        if self.sharded:
            # sharded save is collective (barrier before the manifest
            # commit), so it runs inline; the per-process write itself is
            # already O(local shards)
            save_sharded(path, state, epoch=epoch, extra=extra,
                         keep_last=self.keep_last)
            return
        host_tree = _gather_host(state)       # synchronous: step's values
        if not is_coordinator():
            return

        def write():
            try:
                # rotation happens on this thread too: the previous
                # write was joined above, so nobody else touches path
                _write_v1(path, host_tree, epoch, extra, self.keep_last)
            except BaseException as e:  # noqa: BLE001 — re-raised on join
                self._error = e

        self._thread = threading.Thread(target=write, daemon=True,
                                        name="dcp-ckpt-write")
        self._thread.start()

    def close(self) -> None:
        self._join()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _leaf_shape(leaf) -> tuple:
    """Template-leaf shape; works for concrete arrays AND abstract
    ``jax.eval_shape`` templates (``np.shape`` would try to asarray a
    ShapeDtypeStruct)."""
    s = getattr(leaf, "shape", None)
    return tuple(s) if s is not None else np.shape(leaf)


def _is_key_leaf(leaf) -> bool:
    dt = getattr(leaf, "dtype", None)
    return dt is not None and jnp.issubdtype(dt, jax.dtypes.prng_key)


def restore(path: str, template, shardings=None, *, _prefix: str = "",
            generation: int | None = None):
    """Read a checkpoint back into ``template``'s pytree structure.

    ``template`` provides structure/shapes/dtypes — a freshly-initialised
    TrainState, or an ABSTRACT ``jax.eval_shape`` tree (what
    ``dcp-generate --mesh`` passes: a bigger-than-one-chip checkpoint must
    never materialise unsharded params just to build a template);
    ``shardings`` (optional, same structure) places each leaf directly
    into its mesh layout — restore-into-FSDP works without ever
    materialising the full model on one device per leaf batch. Both formats
    restore under ANY mesh (elastic resize): the v1 file holds unsharded
    leaves; the v2 directory is reassembled span-by-span.

    Everything read is verified against the saved CRC-32s (when the
    checkpoint carries them — older checkpoints restore uncheck-ed);
    corruption raises :class:`CheckpointCorruptError` naming the leaf.
    ``generation`` picks an older RETAINED v2 generation (fallback path).

    ``_prefix`` offsets every template key into the stored tree (see
    :func:`restore_params`).
    """
    if os.path.isdir(path):
        return _restore_sharded(path, template, shardings,
                                _prefix=_prefix, generation=generation)
    paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    flat_shardings = (jax.tree_util.tree_leaves(shardings)
                      if shardings is not None else [None] * len(paths))
    # NpzFile reads lazily per key: only the template's leaves are ever
    # decompressed, so a params-only restore (restore_params) never pays
    # for the optimizer-moment trees also stored in the file
    try:
        z = np.load(path, allow_pickle=False)
    except Exception as e:   # torn zip container
        raise CheckpointCorruptError(
            f"{path}: unreadable checkpoint file ({e})") from e
    with z:
        available = set(z.files)
        try:
            manifest = json.loads(str(z["__manifest__"]))
        except Exception:
            manifest = {}        # pre-integrity checkpoints
        _restore_v1_leaves(z, available, paths, flat_shardings, leaves,
                           _prefix, manifest.get("checksums", {}), path,
                           manifest.get("leaves", {}))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def restore_with_fallback(path: str, template, shardings=None):
    """Restore the newest checkpoint at ``path`` that VERIFIES, walking
    the retention chain on corruption: the live v1 file then its
    rotated ``.prev-K`` siblings, or the committed v2 generation then
    the older generations in the manifest's ``history``. Returns
    ``(state, manifest)`` — the manifest of whichever checkpoint
    actually restored, so the caller resumes at ITS epoch/step. Raises
    the LAST failure when every candidate is corrupt/unreadable.

    This is the trainer's resume path: one bit-rotted save costs
    ``checkpoint_every`` steps of progress, never the run.
    """
    candidates: list[tuple[str, int | None]] = [(path, None)]
    if os.path.isdir(path):
        try:
            hist = load_manifest(path).get("history", [])[1:]
        except Exception:
            hist = []
        candidates += [(path, int(h["generation"])) for h in hist]
    else:
        k = 1
        while os.path.exists(f"{path}.prev-{k}"):
            candidates.append((f"{path}.prev-{k}", None))
            k += 1
    last_err: Exception | None = None
    for cand, gen in candidates:
        try:
            state = restore(cand, template, shardings, generation=gen)
            manifest = load_manifest(cand)
            if gen is not None:
                hit = [h for h in manifest.get("history", [])
                       if int(h["generation"]) == gen]
                manifest = dict(manifest, **hit[0])
            if last_err is not None:
                import sys
                print(f"[checkpoint] WARNING: newest checkpoint corrupt "
                      f"({last_err}); restored fallback "
                      f"{cand}" + (f" generation {gen}" if gen is not None
                                   else ""),
                      file=sys.stderr, flush=True)
            return state, manifest
        except (CheckpointCorruptError, OSError, KeyError, ValueError,
                json.JSONDecodeError, EOFError) as e:
            last_err = e
    raise last_err if last_err is not None else FileNotFoundError(path)


def _place(arr, shard):
    """Put a host array into ``shard``'s layout; in a MULTI-PROCESS world
    the sharding spans non-addressable devices and ``device_put`` refuses —
    each process then contributes only its addressable shards (the same
    contract the v2 path already uses)."""
    if shard is None:
        return arr
    if getattr(shard, "is_fully_addressable", True):
        return jax.device_put(arr, shard)
    host = np.asarray(arr)
    return jax.make_array_from_callback(host.shape, shard,
                                        lambda idx: host[idx])


def _restore_v1_leaves(z, available, paths, flat_shardings, leaves,
                       _prefix, checksums=None, src="", saved=None):
    checksums = checksums or {}
    saved = saved or {}
    for (path_keys, leaf), shard in zip(paths, flat_shardings):
        key = _prefix + _SEP.join(
            str(getattr(k, "key", getattr(k, "idx", k))) for k in path_keys)
        if key not in available:
            raise KeyError(f"checkpoint missing leaf {key!r}")
        arr = _as_saved(z[key], saved.get(key, (None,))[0])
        if key in checksums and _crc(arr) != checksums[key]:
            # verify-on-restore (module docstring): corruption is a
            # loud, named error — never silently wrong weights
            raise CheckpointCorruptError(
                f"{src}: leaf {key!r} failed its CRC-32 integrity "
                f"check (corrupted checkpoint)")
        if _is_key_leaf(leaf):
            if shard is not None and not getattr(
                    shard, "is_fully_addressable", True):
                # place the raw KEY DATA (replicated; rank-agnostic spec)
                # then reinterpret — device_put can't take the
                # non-addressable sharding and the callback path can't
                # carry the opaque key dtype
                from jax.sharding import NamedSharding, PartitionSpec
                data = _place(np.asarray(arr),
                              NamedSharding(shard.mesh, PartitionSpec()))
                new = jax.random.wrap_key_data(data)
            else:
                new = jax.random.wrap_key_data(jnp.asarray(arr))
                if shard is not None:
                    new = jax.device_put(new, shard)
            leaves.append(new)
            continue
        want = _leaf_shape(leaf)
        if want and arr.shape != want:
            # same contract as the v2 path: a silently wrong-shaped
            # leaf (model config drifted since the save) must not load
            raise ValueError(
                f"checkpoint leaf {key!r} was saved with shape "
                f"{arr.shape} but the template wants {want} — model "
                f"configuration changed since the save")
        dtype = getattr(leaf, "dtype", None)
        if shard is not None and not getattr(shard, "is_fully_addressable",
                                             True):
            # multi-process: cast HOST-side and let make_array_from_callback
            # slice it — jnp.asarray first would round-trip the full global
            # leaf through local device 0 (transient full-leaf HBM spike)
            leaves.append(_place(np.asarray(arr, dtype=dtype), shard))
        else:
            leaves.append(_place(jnp.asarray(arr, dtype=dtype), shard))


def saved_param_dtype(path: str):
    """The parameter dtype a (v1 or v2) checkpoint was trained in — the
    dominant floating dtype of its ``params`` subtree — read from the
    manifests alone. ``None`` for checkpoints that predate the recorded
    dtype names (numpy's own dtypes only; float32 in practice)."""
    prefix = ".params" + _SEP
    if os.path.isdir(path):
        specs = [(gshape, name) for key, pieces
                 in _sharded_entry_map(path).items()
                 if key.startswith(prefix)
                 for _, _, _, gshape, _, name in pieces[:1]]
    else:
        specs = [(shape, name) for k, (name, shape)
                 in load_manifest(path).get("leaves", {}).items()
                 if k.startswith(prefix)]
    if any(shape is None or name is None for shape, name in specs):
        return None
    return dominant_float_dtype(specs)


def restore_params(path: str, params_template, shardings=None):
    """Restore ONLY the model parameters from a (v1 or v2) checkpoint.

    Inference loaders (``dcp-generate``) have no optimizer, so they cannot
    rebuild the full TrainState template that :func:`restore` wants; this
    reads just the ``params`` subtree by offsetting every key with the
    state's ``.params`` prefix.
    """
    return restore(path, params_template, shardings,
                   _prefix=".params" + _SEP)
