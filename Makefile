# Convenience targets mirroring the reference's Makefile/run-tests entry
# points (there is no build step: the framework is pure Python + JAX).

PY ?= python

.PHONY: test test-fast lint analysis-smoke perf-smoke fault-smoke swarm-smoke capacity-smoke capacity2-smoke obs-smoke chaos-smoke service-smoke trace-smoke mesh-smoke lanes-smoke memo-smoke scenario-smoke spec-smoke lab0 lab1 lab2 lab3 lab4 dryrun handout clean

test:            ## full acceptance + parity suite
	$(PY) -m pytest tests/ -q

test-fast:       ## skip the slowest files (TPU-engine parity compiles)
	$(PY) -m pytest tests/ -q --ignore=tests/test_tpu_engine.py \
	    --ignore=tests/test_tpu_sharded.py --ignore=tests/test_tpu_lab4.py

lab0 lab1 lab2 lab3 lab4:   ## scored lab runs via the CLI driver
	$(PY) run_tests.py --lab $(subst lab,,$@)

# lint = the soundness sanitizer's full pass (ISSUE 10): the protocol
# conformance linter (C1-C4 over specs/protocols/adapters/labs + the
# ProtocolSpec compile gate) AND the jaxpr hot-path auditor (J0-J5
# over the lowered dispatch-site programs of the pingpong engines on a
# virtual CPU mesh, retrace check included).  Exit 1 on any unwaived
# finding; .sanitizer-waivers documents the justified exceptions.
# docs/analysis.md is the field guide.
lint:            ## soundness sanitizer: conformance linter + jaxpr auditor
	$(PY) -m dslabs_tpu.analysis all

# analysis-smoke = the sanitizer's own test suite (tests/test_analysis.py):
# one deliberately-violating red fixture per rule asserting the exact
# finding code (C1-C4, J0-J5), the clean-pass pin on every shipped
# protocol, the jaxpr zero-findings pin on the pingpong superstep +
# promote for BOTH engines, SpecError compile-gate shapes, waiver-file
# handling, and the CLI rc contract — then the CLI itself end to end.
analysis-smoke:  ## sanitizer suite (red fixtures per rule + shipped-tree clean pin) on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m analysis -p no:cacheprovider
	$(PY) -m dslabs_tpu.analysis all

# perf-smoke = the BASELINE.json states/min floor PLUS the dry-run
# 8-virtual-device superstep-vs-host-reference parity gate (exact unique/
# explored/verdict match on pingpong + paxos d5 + shardstore —
# tests/test_superstep.py, ISSUE 3 acceptance).
perf-smoke:      ## fast CPU perf gate vs the BASELINE.json floor
	$(PY) -m pytest tests/ -q -m perf -s -p no:cacheprovider

# fault-smoke = the full injected-fault recovery suite: the in-process
# retry/failover/resume/watchdog paths (tests/test_supervisor.py) PLUS
# the process-isolation warden's deterministic kill/hang/crash matrix
# (tests/test_warden.py — child SIGKILLed mid-search resumes from the
# checkpoint, a hung child is reaped within its heartbeat grace,
# exit-code classification pinned, .prev-rotation torn-write recovery).
# Tier-1 keeps only the FAST warden tests (spawn-light, no accelerator);
# the slowest spawn-heavy variants are additionally marked `slow` and
# run only here.
fault-smoke:     ## injected-fault recovery suite (retry/failover/resume/watchdog/warden) on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m fault -p no:cacheprovider

# swarm-smoke = the whole swarm-explorer suite (tests/test_swarm.py)
# INCLUDING the deep-narrow paxos/lab4 scenarios that tier-1 skips
# (marked slow+perf): determinism, verdict parity, dedup sharing,
# frontier-seeding resume parity, dispatch-seam fault injection, loud
# overflow accounting, and the portfolio acceptance (BFS alone
# TIME_EXHAUSTED vs portfolio violation with a minimized,
# replay-verified witness).
swarm-smoke:     ## swarm explorer suite incl. slow deep-narrow scenarios, on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_swarm.py -q -p no:cacheprovider

# capacity-smoke = the host-RAM spill-tier suite (tests/test_spill.py):
# strict DEPTH_EXHAUSTED exact unique/explored parity with the device
# visited table capped at ~1/8 of the state count (single-device AND
# sharded engines), SIGKILL-mid-spill resume parity, the supervisor's
# CapacityOverflow->spill-retry capacity ladder, spill-dispatch fault
# injection, and the foreign-checkpoint refusal.
capacity-smoke:  ## host-RAM spill tier + capacity-ladder suite on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m capacity -p no:cacheprovider

# capacity2-smoke = capacity round 2 (ISSUE 15, tpu/packing.py +
# tpu/symmetry.py + the async spill gear): packed-vs-unpacked EXACT
# parity on pingpong + lab1 (strict and beam, device + host loops +
# sharded), the >= 2x bytes_per_state pins on the lab1/paxos specs and
# the packed-capacity depth test (a frontier sized in packed bytes
# completes a depth the unpacked layout provably cannot fit),
# SIGKILL-mid-run packed-checkpoint resume + the loud packed<->raw
# cross-resume conversion/refusal, the symmetry-reduced paxos quotient
# (pinned canonical counts, verdict parity, replay-verified witness),
# and the async drain's exactness + overlap accounting — PLUS the
# packed end-to-end leg of tools/obs_smoke.py (STATUS capacity block).
capacity2-smoke: ## capacity round 2: packed encoding + symmetry reduction + async spill, on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m capacity2 -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/obs_smoke.py

# obs-smoke = the unified telemetry suite (tests/test_telemetry.py):
# span-count == dispatch-count on both engines, the zero-added-
# dispatches/transfers overhead guard (per-device lanes + STATUS.json
# writer enabled), per-device skew lanes on the 8-device mesh, SIGKILL
# flight-log survival with the in-flight dispatch named, the
# report-CLI golden sections + --json schema pin, the live-monitor
# watch view, and supervisor retry/failover event plumbing — PLUS the
# CLI end-to-end steps via tools/obs_smoke.py: `telemetry watch --once`
# and `telemetry report` on a finished run.  docs/observability.md is
# the field guide.
obs-smoke:       ## unified telemetry suite (flight recorder / metrics / reports / watch) on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m obs -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/obs_smoke.py

# chaos-smoke = the elastic-mesh resilience suite (tests/test_chaos.py):
# the degraded-mesh width ladder sharded(D)->sharded(D/2)->...->device->
# host with exact cross-width resume parity (8->4->2->1 on the CPU
# dryrun mesh, strict pingpong + lab1, SIGKILL-mid-level warden
# variant), the adaptive OOM knob-shrink re-level, and the seeded chaos
# soak (>= 20 deterministic faults across >= 3 dispatch sites, exact
# fault-free parity asserted) — plus the long soak variants tier-1
# skips (marked slow).  `python -m dslabs_tpu.tpu.chaos` is the by-hand
# entry point.
chaos-smoke:     ## elastic-mesh resilience suite (degraded ladder / knob shrink / seeded chaos soak) on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m chaos -p no:cacheprovider

# service-smoke = the multi-tenant checking-service suite
# (tests/test_service.py): the unified child-death taxonomy table
# (warden exit codes + stderr OOM markers, agreeing with
# supervisor.classify_oom), the structured queue-full retry-after
# rejection (never raises, never blocks), journal torn-tail replay +
# tmp/replace compaction, DRR fairness + per-tenant quotas, the
# CPU-pinned conformance admission gate rejecting an unsound spec with
# SpecError-derived findings BEFORE any twin compiles, and the
# tenant-isolation chaos soak (3 tenants, seeded oom/hang/crash fault
# schedule on one tenant: neighbors' verdicts bit-exact vs solo
# baselines, the victim degraded-but-sound or structured-failed) —
# then the `python -m dslabs_tpu.service` CLI end to end.
# docs/service.md is the field guide.
service-smoke:   ## multi-tenant checking service suite (queue / admission / fairness / isolation soak) on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m service -p no:cacheprovider

# trace-smoke = the end-to-end causal-tracing + cost-accounting suite
# (tests/test_tracing.py, ISSUE 13): trace-ID propagation
# submit -> journal -> scheduler -> warden env -> child flight logs,
# the SIGKILL acceptance (one pingpong job submitted to a local
# server, its warden child SIGKILLed mid-level, `telemetry trace`
# still renders the full causal chain from disk alone and names the
# in-flight dispatch), per-tenant COSTS.jsonl sums agreeing with the
# jobs' SearchOutcome counters exactly, torn SERVER_STATUS/COSTS
# reads, and the run-dir retention sweep — then the trace-assembler
# leg of tools/obs_smoke.py (the CLI end to end).
# docs/observability.md "Tracing a job end-to-end" is the field guide.
trace-smoke:     ## causal tracing + cost-ledger suite (assembler / COSTS / retention) on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m trace -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/obs_smoke.py

# mesh-smoke = the owner-sharded multi-chip superstep suite
# (tests/test_mesh_exchange.py, ISSUE 12): the width-parity matrix —
# exact unique/explored/verdict parity between the sharded engine and
# the host-dedup reference (and the object checker's count) at
# n_devices in {1, 2, 4, 8} on pingpong + lab1 — the <= 2
# dispatches/level budget
# pin with a zero-collective promote lowering, Pallas-vs-jnp
# visited-table bit-exact parity (incl. the table-full overflow
# contract) standalone AND through a full sharded search, the
# cross-width checkpoint resume chain 8->4->2->1, first-class carry
# placement (partition rules -> NamedSharding everywhere) — all on the
# CPU virtual 8-device mesh, no TPU hardware needed.  ISSUE 18 adds the packed-wire suite
# (tests/test_mesh_packing.py): packed-vs-raw exchange parity across
# widths {1,2,4,8} + the >= 8x wire bytes-per-state floor, the
# delta-lane (varint) pb parity, cross-width resume through the packed
# checkpoint format, packed-spill parity at 1/8 capacity, the
# pack/decode dispatch-site audits, and the mesh_unpacked /
# skew_agg observability pins.  docs/perf.md "mesh dispatch model" +
# "The wire format" are the field guides.
mesh-smoke:      ## owner-sharded superstep width-parity + packed-wire suite on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m mesh -p no:cacheprovider

# lanes-smoke = the batched-job-lanes suite (tests/test_lanes.py,
# ISSUE 14): lane-vs-solo EXACT parity (unique/explored/verdict
# bit-identical at L in {1, 2, 4}, pingpong + lab1, strict + beam +
# mixed per-lane depth limits), continuous-batching swap-in parity
# with zero recompiles, the dispatches-per-job amortisation pin
# (4-lane batch <= 0.5x the 4-solo dispatch count), SIGKILL-mid-batch
# per-lane checkpoint resume through the LaneBatchWarden child,
# poisoned-lane eviction leaving neighbors bit-exact, per-tenant
# COSTS sums across a batched drain == the solo drain's, and the
# solo-path overhead guard (lanes off = solo dispatch/device_get counts
# untouched) — all CPU, no TPU needed.  PLUS the lanes leg of
# tools/obs_smoke.py (a lane batch's STATUS.json through `watch`).  docs/service.md "Batched job lanes"
# is the field guide.
lanes-smoke:     ## batched job lanes: parity matrix + continuous batching + resume + cost split on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m lanes -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/obs_smoke.py

# memo-smoke = the cross-job memoization suite (tests/test_memo.py,
# ISSUE 16): structural-fingerprint identity (rename-only resubmits
# hit, one-handler edits miss), visited-tier save/load with loud
# pack/symmetry refusals, the exact-key verdict-cache hit (zero
# dispatches, journaled memo_hit, ~0 COSTS device_secs), warm-start
# and incremental re-check exact parity vs cold runs (incl. the
# strict/beam x packed on/off sweep and SIGKILL-mid-warm-start
# resume), stale-verdict impossibility, the 3-tenant <10% resubmit
# billing pin, and the memo-off overhead guard — all CPU.  PLUS the
# memo leg of tools/obs_smoke.py (one job drained twice: the second
# is a journaled memo_hit).  docs/memo.md is the field guide.
memo-smoke:      ## cross-job memoization: verdict cache + warm start + incremental re-check parity on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m memo -p no:cacheprovider
	JAX_PLATFORMS=cpu $(PY) tools/obs_smoke.py

# scenario-smoke = the checkable-fault-scenario suite
# (tests/test_scenarios.py, ISSUE 19): fault-free parity / overhead
# guard on both engines (zero-budget FaultModel == plain spec,
# exactly), the paxos partition-then-heal safety pins and the
# broken-quorum witness that NAMES its HEAL event, crash
# durable-vs-volatile semantics on _step_one, fault lanes through
# packing/symmetry/spill/checkpoint (incl. SIGKILL-mid-scenario
# resume and the fault-signature fingerprint refusal), the C6
# conformance fixtures, telemetry/warden counter wiring, and the
# partitioned-scenario chaos-soak leg.  docs/scenarios.md is the
# field guide.
scenario-smoke:  ## checkable fault scenarios: partition/crash/drop-dup model events + witness replay on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m scenario -p no:cacheprovider

# spec-smoke = the replicated-protocol spec layer (ISSUE 20): the
# generated lab3/lab4 twins vs the retired hand twins
# (tests/fixtures/hand_twins/) as parity oracles, the slot/quorum
# compile gates, and the packed slot-lane roundtrips.
spec-smoke:      ## replicated-protocol spec layer: generated-vs-hand parity matrix + slot/quorum gates on CPU
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m spec -p no:cacheprovider

dryrun:          ## multi-chip sharding dry run on a virtual CPU mesh
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

handout:         ## student distribution (lab solutions AST-stripped)
	$(PY) tools/handout.py --out /tmp/dslabs_tpu_handout --tar

clean:
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null; true
	rm -rf .pytest_cache
