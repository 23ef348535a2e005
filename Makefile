SHELL := /bin/bash

# Test entry point — the reference's `mpirun -n 2 py.test -s`
# (/root/reference/Makefile:2-3) becomes the virtual 8-device SPMD suite
# (tests/conftest.py is the `mpirun` analogue: it forces an 8-device CPU
# mesh before jax initializes).
test:
	python -m pytest tests/ -x -q

# The tier-1 command as the driver runs it after a PR (six xdist workers
# by file, 1,470 s, passes counted from the junit file), verbatim from the
# `commands` of its last run: one target so humans run the line that is
# scored.  It writes /tmp/_t1.log and /tmp/_t1.xml.
tier1:
	set -o pipefail; rm -rf /tmp/_t1.log /tmp/_t1.xml; timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; said=$$(sed -n 's/.*<testsuite [^>]*errors="\([0-9]*\)" failures="\([0-9]*\)" skipped="\([0-9]*\)" tests="\([0-9]*\)".*/\4 \1 \2 \3/p' /tmp/_t1.xml 2>/dev/null | head -n 1 | awk '{n=$$1-$$2-$$3-$$4; print (n<0 ? 0 : n)}'); echo DOTS_PASSED=$${said:-$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c)}; echo WORKERS_DOWN=$$(grep -acE '\[gw[0-9]+\] node down' /tmp/_t1.log 2>/dev/null); exit $$rc

# Seeded fault-injection suite (FaultPlan chaos: CRC quarantine, worker
# eviction, reconnect backoff, PS crash-resume, checkpoint corruption).
# Endurance chaos runs (>60 s, real CLI processes) are `slow`-marked so
# the tier-1 lane keeps its time limit; run them with `-m slow`.
smoke-chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py tests/test_checkpoint.py -q -m 'not slow' -p no:cacheprovider

# Elastic resilience suite: signal-safe preemption (a tiny preempt →
# resume-on-another-device-count round trip runs in-process), N→M
# resume, the replica-consensus SDC guard, and rollback-on-divergence.
# The real-SIGTERM endurance CLI test is `slow`-marked (run with -m slow).
smoke-elastic:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py tests/test_loader.py -q -m 'not slow' -p no:cacheprovider

# Robust aggregation + quorum admission suite (ops/robust.py): reducer
# math vs numpy, the typed decode_sum-only refusal, scoreboard lifecycle,
# quorum/deadline fills, seq dedup, quorum x eviction interplay.  The
# real-process CLI endurance run is `slow`-marked (run with -m slow).
smoke-robust:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_robust.py tests/test_faults.py -q -m 'not slow' -p no:cacheprovider

# Sharded PS fleet suite (shard/): partition plans + HELO-time digest
# agreement, fleet-wide worker identity, per-shard versions, quorum
# composition per shard, kill_shard_at crash-resume, snapshot key
# parity, and the pslint shard-drift coverage proofs.  The real-process
# CLI fleet endurance run is `slow`-marked (run with -m slow).
smoke-shard:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_shard.py -q -m 'not slow' -p no:cacheprovider

# Fleet availability suite (ISSUE 7): hot-standby replication + PROM
# promotion (zero-rewind failover with checkpoint_every=0), coordinated
# SNAP snapshot barriers + manifest-verified resume (skew/partial/tamper
# refusals), and partition-tolerant degraded mode.  The real-process CLI
# promotion endurance run is `slow`-marked (run with -m slow).
smoke-failover:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_failover.py -q -m 'not slow' -p no:cacheprovider

# Hierarchical aggregation suite (shard/hierarchy, ISSUE 8): group-local
# fill policy + pre-reduce, Byzantine containment (group scoreboard
# quarantines, root stays quiet), aggregator kill -> supervised restart
# (zero rank churn) or direct-fallback failover, the adaptive
# fill-deadline + latency-weighted admission units, and the MoE async
# stress workload.  The real-process MoE CLI endurance run is
# `slow`-marked (run with -m slow).
smoke-hier:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_hierarchy.py tests/test_moe.py -q -m 'not slow' -p no:cacheprovider

# Flow-control & overload suite (ISSUE 10, transport.py): the Deadline
# budget type, the Backoff redial ladder, Session credit/pacing gates
# (priority classes, oldest-first shedding), v8 credit advertisement,
# pre-decode admission shedding, the overload injectors, and the CLI
# refusal matrix.
smoke-overload:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_flow.py tests/test_faults.py -q -m 'not slow' -p no:cacheprovider

# Project-native static analysis (tools/pslint): lock-discipline,
# JIT-hygiene, protocol/stats-drift, typed-error policy,
# concurrency/deadlock (PSL5xx lock graph), the credit-gate
# protocol model checker (PSL6xx, exhaustive at 2 senders x window 2),
# buffer-ownership dataflow (PSL7xx), and the whole-program lockset
# race pass (PSL8xx: thread roles x held locks over every self.attr).
# Exits non-zero on any unsuppressed finding; tier-1 enforces the same
# checkers via tests/test_pslint.py (plus the fixture corpus and the
# real-module tamper tests proving they detect).  Pure-stdlib AST
# analysis — no jax import; tests pin the full run under ~3 s.
lint:
	python -m tools.pslint pytorch_ps_mpi_tpu

# Same run, machine-readable: one JSON object with per-finding
# file/line/id/rule/message/fix_hint (exit codes unchanged) — the CI
# consumption surface.
lint-json:
	python -m tools.pslint pytorch_ps_mpi_tpu --format json

# Incremental lint for the edit loop: gates only files dirty vs the git
# index (clean tree = instant exit; whole-program context is kept when
# anything IS dirty, so cross-module checkers never fabricate one-sided
# findings).  Falls back to the full run outside a git repo.
lint-fast:
	python -m tools.pslint pytorch_ps_mpi_tpu --changed

# Serve-tier suite (ISSUE 14, serve/): the READ-class credit gate
# (separate budget, oldest-first shed, open_read valve), versioned
# snapshot subscription (full read -> conditional deltas -> unchanged
# short-circuits, encode-once fanout, failover without rewind), the
# continuous-batching inference front-end (typed shed, p50/p95,
# hot-swap), RequestLatency semantics, and the CLI refusal matrix.
smoke-serve:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py -q -m 'not slow' -p no:cacheprovider

# Bucket-streamed async gradients (ISSUE 15, protocol v11): the
# per-bucket grad+fused-encode step (fused == host-encode == whole-tree
# bitwise, Pallas interpreter parity), the multipart credit gate (one
# credit per GRADIENT, whole-gradient park/shed), per-(rank, seq)
# assembly with partial-timeout retirement, rank-distinct interleaved
# fills, the aggregator's per-bucket pre-reduce, the solo-large-leaf
# bucket planner, and the CLI refusal matrix.
smoke-bucket:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_bucket_stream.py -q -m 'not slow' -p no:cacheprovider

# Compressed parameter wire (ISSUE 16, protocol v12): the host-side
# bf16/int8 wire codecs (RNE bit-twiddle, per-block symmetric quant,
# worth-it guard on sub-block leaves), the codec-id byte on
# PARM/DELT/REPL frames, delta framing off the post-decode ring
# (bitwise patches, full-snapshot fallback on ring miss / redial /
# restore, forced-full after load_state_dict), encode-once delta
# fanout, standby promotion through a compressed REPL stream, and the
# CLI refusal matrix.
smoke-codec-wire:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_codec_wire.py -q -m 'not slow' -p no:cacheprovider

# Thread-race detection lane (ISSUE 20): the PSL8xx fixture exactness
# + real-module tamper tests (stripping a real lock must convict the
# exact line), and the runtime race sanitizer's unit + e2e coverage
# (PS_RACE_SANITIZER holds(_lock) probes: typed RaceDetectedError on
# an off-lock caller, race_checks>0 / race_trips==0 on the flood e2e).
smoke-races:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_pslint.py -q -k races -p no:cacheprovider
	env JAX_PLATFORMS=cpu python -m pytest tests/test_flow.py::test_flooded_fleet_completes_with_shedding_not_evictions -q -p no:cacheprovider

# On a TPU (through the chip tool): every training path starts, compiles
# and steps; fails without a chip.  --devices 4 on the four-chip host.
chip-smoke:
	python chip_smoke.py

.PHONY: chip-smoke test tier1 smoke-chaos smoke-elastic smoke-robust smoke-shard smoke-failover smoke-hier smoke-overload lint lint-json lint-fast smoke-serve smoke-bucket smoke-codec-wire smoke-races
