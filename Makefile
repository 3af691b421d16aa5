# Developer/CI entry points. `make tier1` is THE gating command: it is
# byte-for-byte the tier-1 verify line from ROADMAP.md, so the builder,
# CI, and a laptop all run the identical suite (CPU backend, slow tests
# excluded, collection errors tolerated so one broken module can't hide
# the rest of the signal).
#
# What `-m 'not slow'` excludes (the container's 870s tier-1 timeout
# otherwise truncates the suite tail — PR 2 note):
# 1. subprocess/e2e tests that pay a fresh XLA compile per process
#    (test_elastic supervisor drills);
# 2. heavy REDUNDANT mesh parametrizations whose siblings keep the
#    coverage in tier-1 (test_generate fsdp=8 — the 3-axis case shards
#    fsdp too; test_serve long-stream MoE — family-independent host
#    logic pinned by gpt2/llama, MoE exactness has its own tests);
# 3. the `_container_backend_gap` set (test_pipeline/
#    test_ladder_models/test_llama/test_moe/test_remat/
#    test_trainer_strategy): composed-mesh and remat parity cases
#    parked in earlier rounds for burning ~6 min of budget without
#    signal on the CPU backend. They run in `make test`; ROADMAP C8
#    re-triages them against the installed jax.
# Nothing marked slow is the only in-budget test of a feature that can
# pass on this container. Run the full suite with `make test`.

SHELL := /bin/bash

.PHONY: tier1 test

tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# the full suite without the tier-1 harness wrapping (local iteration)
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q
