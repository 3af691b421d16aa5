"""Test harness: fake an 8-device CPU mesh in one process.

SURVEY.md §4: the reference has no tests; our multi-process collective tests
run without a cluster via ``xla_force_host_platform_device_count`` — this
must be set before JAX initialises its backends, hence here, before any test
imports jax.
"""

import os

# DCP_TEST_TPU=1 keeps the real backend so the TPU-gated tests
# (test_flash_tpu.py, test_cache_update_tpu.py) run on hardware instead
# of skipping.
_USE_TPU = os.environ.get("DCP_TEST_TPU") == "1"

if not _USE_TPU:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
# determinism + speed for CPU test runs
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

# persistent compile cache: repeat suite runs skip XLA compilation entirely
# (keyed by HLO hash + jaxlib version, so it can't serve stale programs)
from distributed_compute_pytorch_tpu.utils.compilation_cache import (  # noqa: E402
    enable as _enable_compile_cache)

_enable_compile_cache()

# a pytest plugin may have imported jax before this file set the
# environment; the config update wins as long as no backend is up yet
if not _USE_TPU:
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 faked CPU devices, got {len(devs)}"
    return devs


def _map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:                      # no procfs: nothing to observe
        return 0


@pytest.fixture(autouse=True, scope="module")
def _drop_executables_before_the_map_limit():
    """Every loaded CPU executable maps its own code pages (~6 mappings
    each) and nothing unloads one while a jit cache still points at it:
    the suite in ONE process climbs to ~63,000 mappings against Linux's
    default ``vm.max_map_count`` of 65,530, and past it ``mmap`` fails
    inside jaxlib and the interpreter segfaults (seen in
    ``deserialize_executable`` at ~90% of tier-1, two runs in three).
    Between modules, once the process holds more than a third of that,
    drop the compiled programs; whatever a later module needs again
    comes back from the persistent cache."""
    yield
    if _map_count() > 20000:
        import gc
        jax.clear_caches()
        gc.collect()
