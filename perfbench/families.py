"""A configuration's ``family`` names a module, ``perfbench/family/
<family>.py``, found by that name the way a configuration, a traffic mix,
a cell, a metric and a reader are found by theirs. Everything that is
specific to a family sits behind this one lookup: how the program builds
the model, the plain reference that goes with it, the config's dropout
keys, the call shapes of its kernels, and its own counts of operations and
bytes. A new family adds a module there and a reference module; no file is
edited (``README.md``, "Adding things")."""

from __future__ import annotations

import importlib
import pathlib

from perfbench import bytes as nbytes
from perfbench import flops

_HERE = pathlib.Path(__file__).resolve().parent


def family(cfg: dict):
    """The module of the configuration's family."""
    name = f"perfbench.family.{cfg['family']}"
    try:
        return importlib.import_module(name)
    except ModuleNotFoundError as e:
        if e.name != name:
            raise
        raise LookupError(
            f"no family {cfg['family']!r}: looked for "
            f"{_HERE / 'family' / (cfg['family'] + '.py')}") from None


def without_dropout(cfg: dict) -> dict:
    """``cfg`` with the family's dropout keys at 0 (``cfg`` itself, equal,
    where they already are)."""
    keys = family(cfg).DROPOUT_KEYS
    return {k: (0.0 if k in keys else v) for k, v in cfg.items()}


def kernel_shape(cfg: dict, which: str, counters: dict, chips: int):
    """The shapes of one call of a kernel in a run, under the name
    ``which`` that a kernel-roofline metric's file gives (``train``,
    ``decode``): keyword arguments for the operation and byte functions
    the file names. None where the family does not say."""
    fn = getattr(family(cfg), "kernel_shapes", None)
    return None if fn is None else fn(cfg, which, counters, chips)


def count_fn(cfg: dict, name: str):
    """The operation or byte function called ``name``: the family's own
    where it has one, else the kernel's in ``flops.py`` / ``bytes.py``."""
    for mod in (family(cfg), flops, nbytes):
        fn = getattr(mod, name, None)
        if callable(fn):
            return fn
    raise LookupError(
        f"no count {name!r} in perfbench/family/{cfg['family']}.py, "
        f"perfbench/flops.py or perfbench/bytes.py")


def reference_module(cfg: dict):
    return importlib.import_module(family(cfg).REFERENCE)


def build_program_model(cfg: dict, run: dict):
    from distributed_compute_pytorch_tpu.models.registry import build_model
    fam = family(cfg)
    return build_model(fam.BUILD_MODEL, **fam.model_kwargs(cfg, run))
