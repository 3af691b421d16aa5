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

.PHONY: tier1 test bench bench-smoke serve-chaos-smoke serve-prefix-smoke \
	serve-tier-smoke serve-spec-smoke serve-kvq-smoke serve-load-smoke \
	serve-router-smoke serve-elastic-smoke serve-disagg-smoke \
	serve-journal-smoke serve-width-smoke bench-diff

tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# the full suite without the tier-1 harness wrapping (local iteration)
test:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q

bench:
	python bench.py

# CPU-sized end-to-end runs of the bench plumbing (tiny models, faked
# multi-device CPU meshes) inside tier-1 time budgets:
# - zero1: sharded init, both step programs, the opt-HBM byte meter;
#   fails if sharding doesn't shrink per-chip opt state
# - serve: the mesh-sharded continuous-batching loop's transport
#   counters; fails unless each segment costs exactly one device->host
#   fetch issued AFTER the next segment's dispatch (overlap), admission
#   waves are single multi-row prefills, and the KV cache lands sharded
# - grad-accum: the step-level accumulation A/B (legacy MultiSteps vs
#   boundary vs bucketed boundary); fails unless the compiled update
#   holds ZERO grad collectives inside the microbatch scan, wire bytes
#   per update drop N x, and one fused dispatch beats N legacy ones
# - serve-chaos: the fault-tolerance drill — injected harvest fault at
#   segment 2 on a 1-fault schedule; fails unless recovery completes
#   (all requests ok), the recovered streams are token-identical to a
#   fault-free run, goodput under the fault stays > 0, and no cache
#   row leaks its slot; records recovery time
# - serve-prefix: the paged-KV prefix cache on a Zipf-shared prompt
#   stream (hot system prompts, cold tails); fails unless the hit rate
#   is positive, cache-on output is token-identical to cache-off,
#   prefill_tokens_saved > 0, COW runs, no block/slot leaks, and the
#   warm-cache admission TTFT proxy is not degraded; records
#   prefill-bytes-saved
# - serve-tier: the hierarchical KV spill tier (kv_tier.py) on a
#   starved device pool with a 3x-oversized hot prefix set cycled
#   round-robin (the LRU-adversarial Zipf schedule); fails unless
#   spill-on gets prefix hits where spill-off gets exactly none, the
#   host+disk tier hit counters are positive with the disk tier
#   crossed, output is token-identical to tier-off, device occupancy
#   stays bounded while the host pool absorbs the overflow, the
#   warm-promote TTFT proxy is not degraded vs cold prefill, and no
#   slot/device-block/host-block leaks
# - serve-spec: speculative decoding on a repetitive stream (the
#   n-gram self-drafting best case with random rejects mixed in);
#   fails unless spec-on output is token-identical to spec-off (the
#   accept rule is exact), the acceptance rate is positive, useful
#   tokens per verify window exceed 1 (each window costs one weight
#   stream — the >1.5x hardware-target mechanism), auto-disable never
#   trips, and no block/slot leaks; records walls with spread
# - serve-kvq: the quantized KV pool A/B (--kv_dtype int8) — the same
#   Poisson hot-prefix stream on bf16 vs int8 engines, then every
#   serving drill repeated under int8 (spec decode, host+disk spill,
#   prefix handoff + its corrupt-scale/dtype-stamp declines,
#   crash-restart reconstruction + journal replay); fails unless
#   greedy match >= 99% with per-position KL finite and small,
#   resident prefix tokens per pool byte >= 1.8x bf16, scale CRCs
#   stay clean, every decline is counted instead of raised, and no
#   engine leaks a slot/block/host block
# - serve-load: the open-loop Poisson load drill over the telemetry
#   subsystem (obs/); fails unless goodput > 0 with finite p99 TTFT,
#   tokens are identical to the unloaded path, no slot/block leaks,
#   the span trace validates as Chrome-trace JSON, and the disabled-
#   telemetry record path costs < 1% of a segment wall
# - serve-router: the replica-set drill — the same Poisson stream
#   offered to 1 and 3 router replicas (each harvest carrying an 80 ms
#   injected device-latency sleep the replica threads overlap), then
#   to 3 replicas with one killed mid-stream; fails unless 3-replica
#   goodput scales > 1.5x, goodput stays > 0 through the kill with
#   every stream token-identical to the unloaded single-replica
#   reference, sessions migrate, and no survivor leaks a slot/block
# - serve-elastic: the elastic-fleet drill — an offered-load ramp hits
#   a 1-replica fleet under the ElasticFleetController (max 3) with the
#   same injected 80 ms harvest latency, and a same-value weight push
#   lands mid-ramp through the rolling upgrade walk; fails unless the
#   controller scales up at its first control step with elastic goodput
#   > 1.3x the fixed single replica on the identical load, the push
#   drops zero requests with tokens identical to the unloaded
#   reference, the whole fleet lands on the new weights version,
#   nothing leaks a slot/block/host block on any member, and the
#   scale/upgrade events land in the flight recorder
# - serve-disagg: the chunked + disaggregated prefill drill — a mixed
#   Poisson stream of short requests and bunched ~1.8k-token prompts
#   served with chunking off/on against a no-long-prompt baseline, then a
#   3-replica fleet as a unified pool vs a 1-prefill + 2-decode split;
#   fails unless the chunked decode-tick p99 (harvest-span gaps) stays
#   within a fixed 4x of the baseline where unchunked blows past it,
#   TTFT stays finite, chunked/split tokens are identical to the
#   unchunked/unified references, at least one handoff moves KV blocks
#   instead of replaying tokens, and nothing leaks a slot or block;
#   records TTFT p99 unified vs split (the hardware A/B)
# - serve-journal: the crash-durability drill — a journaling serve
#   subprocess SIGKILLed mid-stream (fsync=os), restarted, recovered
#   from the write-ahead session journal; fails unless the restarted
#   run's tokens are identical to an unkilled reference, >= 1 session
#   resumed from journaled state, nothing leaks, and the journal-on
#   decode-tick p99 stays within 1.25x of journal-off (best of 3)
# - serve-width: the width-bucketed paged-decode drill — a mixed
#   Poisson stream (short chatty sessions + one deep anchor climbing
#   the rung ladder) served with bucketing off (one full-horizon
#   program) and on; fails unless tokens are identical on vs off
#   (greedy + sampled rows), the bucketed run gathers at least 2x
#   fewer KV blocks than the full-width equivalent, decode-tick p99
#   stays within 1.25x of full-width (best of 3), compiled programs stay bounded
#   by the ladder, >= 1 bucket growth fires, and nothing leaks
# - bench-diff (last): the regression gate's self-test — one smoke's
#   record diffed against itself through obs/regress.py must pass
#   (a gate that flags identical runs is broken)
bench-smoke:
	JAX_PLATFORMS=cpu python bench.py --zero1-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-smoke
	JAX_PLATFORMS=cpu python bench.py --grad-accum-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-chaos-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-prefix-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-tier-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-spec-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-kvq-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-load-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-router-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-elastic-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-disagg-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-journal-smoke
	JAX_PLATFORMS=cpu python bench.py --serve-width-smoke
	$(MAKE) bench-diff

# the bench-regression gate (obs/regress.py): BASE/NEW default to a
# fresh smoke record diffed against itself (the self-consistency check
# bench-smoke runs); point them at two bench records / BENCH_r*.json
# files to gate a real trajectory step, e.g.
#   make bench-diff BASE=old.json NEW=new.json
BASE ?= /tmp/_bench_diff_self.json
NEW ?= /tmp/_bench_diff_self.json
bench-diff:
	@if [ "$(BASE)" = "/tmp/_bench_diff_self.json" ]; then \
		JAX_PLATFORMS=cpu python bench.py --zero1-smoke > /tmp/_bench_diff_self.json; \
	fi
	JAX_PLATFORMS=cpu python bench.py --diff $(BASE) $(NEW)

serve-chaos-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-chaos-smoke

serve-prefix-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-prefix-smoke

serve-tier-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-tier-smoke

serve-spec-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-spec-smoke

serve-kvq-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-kvq-smoke

serve-load-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-load-smoke

serve-router-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-router-smoke

serve-elastic-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-elastic-smoke

serve-disagg-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-disagg-smoke

serve-journal-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-journal-smoke

serve-width-smoke:
	JAX_PLATFORMS=cpu python bench.py --serve-width-smoke
