"""Tier-1 guards the yardstick: the benchmark's own CPU tests, collected
from where they are.

``pyproject.toml`` collects ``tests/`` only and the driver measures every PR
with ``perfbench/``, so without this file nothing the driver runs says that
the benchmark still reads what it says it reads. Each module of
``perfbench/tests`` is imported and its tests (and the fixtures they ask
for) are handed to pytest under ``test_<module>__<test>``: no copy, and no
edit under ``perfbench/``, which only a ``benchmark`` PR may touch.
"""

import functools
import importlib
import pathlib

import pytest
from _pytest.fixtures import getfixturemarker

SUITE = pathlib.Path(__file__).resolve().parent.parent / "perfbench" / "tests"

# failing at its own guard, not at what it tests; a ``benchmark`` issue's
# to repair (PERF.md section 7, "Left out of ISSUE 26" (1) and (2))
KNOWN_FAILING = {
    "test_a_scope_declared_later_is_left_out_of_both_unscoped_shares":
        "asserts that 'router' is not in obs.tracing.SCOPES, and PR 28 "
        "declared it: the test needs another name for its later scope, "
        "an edit under perfbench/ that only a benchmark PR may make",
}

# asserts that ITS module's CONFIG and CELL are the manifest's LAST entries,
# which holds only until the next PR appends, as the contract asks. Run
# against the manifest as that PR left it: its lists of configurations and
# cells end at the module's own; every other assertion (the cell's entry,
# its per-layer lists, what its metrics move) reads the manifest as it is.
# Replacing ``[-1]`` by a lookup by name is an edit under perfbench/.
LAST_WHEN_APPENDED = {"test_the_cell_is_in_the_manifest_as_appended_entries"}


def _up_to(entries, name):
    names = [e["name"] for e in entries]
    return entries[:names.index(name) + 1]


def _with_the_manifest_cut_at_its_entries(test, module):
    load_json = module.run.load_json

    def cut(path):
        m = load_json(path)
        if pathlib.Path(path).name == "BENCHMARK.json":
            m = dict(m, configs=_up_to(m["configs"], module.CONFIG),
                     workloads=_up_to(m["workloads"], module.CELL))
        return m

    @functools.wraps(test)
    def wrapped():
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(module.run, "load_json", cut)
            test()
    return wrapped


for _path in sorted(SUITE.glob("test_*.py")):
    _module = importlib.import_module(f"perfbench.tests.{_path.stem}")
    for _name, _obj in vars(_module).items():
        if _name.startswith("test_") and callable(_obj):
            if _name in KNOWN_FAILING:
                _obj = pytest.mark.skip(reason=KNOWN_FAILING[_name])(_obj)
            elif _name in LAST_WHEN_APPENDED:
                _obj = _with_the_manifest_cut_at_its_entries(_obj, _module)
            globals()[f"{_path.stem}__{_name[len('test_'):]}"] = _obj
        elif getfixturemarker(_obj) is not None:
            assert _name not in globals(), f"two fixtures named {_name}"
            globals()[_name] = _obj
