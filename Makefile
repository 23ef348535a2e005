SHELL := /bin/bash

# Test entry point — the reference's `mpirun -n 2 py.test -s`
# (/root/reference/Makefile:2-3) becomes the virtual 8-device SPMD suite
# (tests/conftest.py is the `mpirun` analogue: it forces an 8-device CPU
# mesh before jax initializes).
test:
	python -m pytest tests/ -x -q

# The ROADMAP.md tier-1 verify command, verbatim (one target so CI and
# humans run the exact same line the driver scores).
tier1:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# Fast CPU smoke for the overlap sync engine: exercises the scheduler
# logic (plan, hooks, parity, refusals, no-recompile) without TPUs.
smoke-overlap:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_overlap.py tests/test_collectives.py -q -m 'not slow' -p no:cacheprovider

# Seeded fault-injection suite (FaultPlan chaos: CRC quarantine, worker
# eviction, reconnect backoff, PS crash-resume, checkpoint corruption).
# Endurance chaos runs (>60 s, real CLI processes) are `slow`-marked so
# the tier-1 lane keeps its 870 s budget; run them with `-m slow`.
smoke-chaos:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_faults.py tests/test_checkpoint.py -q -m 'not slow' -p no:cacheprovider

# Chaos evidence run: drives the real TCP PS + workers under seeded
# FaultPlans and records steps-survived / quarantine counters / loss
# parity into benchmarks/CHAOS_EVIDENCE.json.
chaos-evidence:
	python benchmarks/chaos_evidence.py --save

# Elastic resilience suite: signal-safe preemption (a tiny preempt →
# resume-on-another-device-count round trip runs in-process), N→M
# resume, the replica-consensus SDC guard, and rollback-on-divergence.
# The real-SIGTERM endurance CLI test is `slow`-marked (run with -m slow).
smoke-elastic:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_elastic.py tests/test_loader.py -q -m 'not slow' -p no:cacheprovider

# Elastic evidence run: real SIGTERM preemption → resume on a different
# --force-cpu-devices count (incl. ZeRO+EF) with loss parity vs an
# uninterrupted baseline; injected replica corruption caught within K
# steps; injected loss spike rolled back — benchmarks/ELASTIC_EVIDENCE.json.
elastic-evidence:
	python benchmarks/elastic_evidence.py --save

# Robust aggregation + quorum admission suite (ops/robust.py): reducer
# math vs numpy, the typed decode_sum-only refusal, scoreboard lifecycle,
# quorum/deadline fills, seq dedup, quorum x eviction interplay.  The
# real-process CLI endurance run is `slow`-marked (run with -m slow).
smoke-robust:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_robust.py tests/test_faults.py -q -m 'not slow' -p no:cacheprovider

# Robust evidence run: straggler quorum recovery (>=80% fault-free
# throughput), Byzantine trimmed_mean vs diverging mean, and bitwise
# duplicate suppression — benchmarks/ROBUST_EVIDENCE.json.
robust-evidence:
	python benchmarks/robust_evidence.py --save

# Sharded PS fleet suite (shard/): partition plans + HELO-time digest
# agreement, fleet-wide worker identity, per-shard versions, quorum
# composition per shard, kill_shard_at crash-resume, snapshot key
# parity, and the pslint shard-drift coverage proofs.  The real-process
# CLI fleet endurance run is `slow`-marked (run with -m slow).
smoke-shard:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_shard.py -q -m 'not slow' -p no:cacheprovider

# Shard evidence run: K=4 fleet aggregate updates/sec >= 2x the single
# PS at quota 4, and the straggler+Byzantine+shard-death chaos suite at
# loss parity < 2x — benchmarks/SHARD_EVIDENCE.json.
shard-evidence:
	python benchmarks/shard_evidence.py --save

# Fleet availability suite (ISSUE 7): hot-standby replication + PROM
# promotion (zero-rewind failover with checkpoint_every=0), coordinated
# SNAP snapshot barriers + manifest-verified resume (skew/partial/tamper
# refusals), and partition-tolerant degraded mode.  The real-process CLI
# promotion endurance run is `slow`-marked (run with -m slow).
smoke-failover:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_failover.py -q -m 'not slow' -p no:cacheprovider

# Failover evidence run: primary kill with NO checkpointing -> standby
# promotion at zero update rewind and loss parity < 2x; coordinated
# snapshot -> whole-fleet kill -> manifest resume with every shard at
# one verified cut; partition chaos (2 links black-holed, healing
# mid-run) + straggler completing in degraded mode —
# benchmarks/FAILOVER_EVIDENCE.json.
failover-evidence:
	python benchmarks/failover_evidence.py --save

# Hierarchical aggregation suite (shard/hierarchy, ISSUE 8): group-local
# fill policy + pre-reduce, Byzantine containment (group scoreboard
# quarantines, root stays quiet), aggregator kill -> supervised restart
# (zero rank churn) or direct-fallback failover, the adaptive
# fill-deadline + latency-weighted admission units, and the MoE async
# stress workload.  The real-process MoE CLI endurance run is
# `slow`-marked (run with -m slow).
smoke-hier:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_hierarchy.py tests/test_moe.py -q -m 'not slow' -p no:cacheprovider

# Hierarchy evidence run: a 12-worker G=3 fleet — root traffic ~G frames
# per update, aggregator kill -> direct fallback, group-contained 100x
# Byzantine, straggler absorbed by group quorum + latency weighting, at
# tail-loss parity < 2x vs fault-free — benchmarks/HIER_EVIDENCE.json.
hier-evidence:
	python benchmarks/hier_evidence.py --save

# Flow-control & overload suite (ISSUE 10, transport.py): the Deadline
# budget type, the Backoff redial ladder, Session credit/pacing gates
# (priority classes, oldest-first shedding), v8 credit advertisement,
# pre-decode admission shedding, the overload injectors, and the CLI
# refusal matrix.
smoke-overload:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_flow.py tests/test_faults.py -q -m 'not slow' -p no:cacheprovider

# Overload evidence run: a 6x seeded flood through a 4-credit window
# (+ slow consumer) holds queue depth / staleness / RSS bounded,
# degrades by counted shedding with zero spurious evictions, recovers
# to >= 0.8x fault-free throughput within 10 fills, and the flood x
# quorum x K=2 fleet x aggregator composition completes at tail-loss
# ratio < 2x — benchmarks/OVERLOAD_EVIDENCE.json.
overload-evidence:
	python benchmarks/overload_evidence.py --save

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

# Wire-throughput baseline for the zero-copy data plane (ROADMAP item
# 1): updates/sec x payload-size x K-shards over the REAL multihost TCP
# path, recorded to benchmarks/WIRE_EVIDENCE.json so the protocol
# rewrite lands against a measured (host-CPU) number instead of
# folklore.  Baseline history: the v8 blob pipeline measured 10.8
# updates/sec on the large-payload K=1 cell (whole-wall, jit compiles
# included); the v9 segmented plane (PR 13) measures >= 55/sec steady
# state on the same cell (>= 5x; warmup methodology + the whole-wall
# twin are recorded in the JSON), plus the PARM-fanout cell
# (parm_encodes == versions) and a per-stage encode/send/decode
# breakdown.  Run with PS_BUFFER_SENTINEL=1 (the harness forces it):
# the gates require sentinel_checks > 0 with zero trips.
wire-evidence:
	python benchmarks/wire_evidence.py --save

# Serve-tier suite (ISSUE 14, serve/): the READ-class credit gate
# (separate budget, oldest-first shed, open_read valve), versioned
# snapshot subscription (full read -> conditional deltas -> unchanged
# short-circuits, encode-once fanout, failover without rewind), the
# continuous-batching inference front-end (typed shed, p50/p95,
# hot-swap), RequestLatency semantics, and the CLI refusal matrix.
smoke-serve:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_serve.py -q -m 'not slow' -p no:cacheprovider

# Serve evidence run: 8 subscribers sustain reads off ONE encode per
# version; a 6x reader flood sheds ONLY READ frames (training
# updates/sec retained >= 0.8x the reader-free twin, zero evictions);
# a subscriber rides a shard failover with no version rewind; and the
# inference front-end reports p50/p95 under continuous batching and
# sheds with a typed error at overload —
# benchmarks/SERVE_EVIDENCE.json.
serve-evidence:
	python benchmarks/serve_evidence.py --save

# Bucket-streamed async gradients (ISSUE 15, protocol v11): the
# per-bucket grad+fused-encode step (fused == host-encode == whole-tree
# bitwise, Pallas interpreter parity), the multipart credit gate (one
# credit per GRADIENT, whole-gradient park/shed), per-(rank, seq)
# assembly with partial-timeout retirement, rank-distinct interleaved
# fills, the aggregator's per-bucket pre-reduce, the solo-large-leaf
# bucket planner, and the CLI refusal matrix.
smoke-bucket:
	env JAX_PLATFORMS=cpu python -m pytest tests/test_bucket_stream.py -q -m 'not slow' -p no:cacheprovider

# Bucket-stream evidence run: gradsync_virtual w8 identity < 20 ms
# under the solo bucket plan (vs 39.1 ms before it, host CPU), interleaved
# whole-tree vs bucket-streamed wire cells at the ~1.3 MB payload
# (pooled medians — single runs on this 1-CPU host swing ~±30%),
# the streaming-latency mechanism measurement (first bucket decodable
# at a fraction of the whole-tree transfer), and the bucket x quorum x
# straggler chaos composition at loss parity < 2x —
# benchmarks/BUCKET_EVIDENCE.json.
bucket-evidence:
	python benchmarks/bucket_evidence.py --save

# Compressed parameter wire (ISSUE 16, protocol v12): the host-side
# bf16/int8 wire codecs (RNE bit-twiddle, per-block symmetric quant,
# worth-it guard on sub-block leaves), the codec-id byte on
# PARM/DELT/REPL frames, delta framing off the post-decode ring
# (bitwise patches, full-snapshot fallback on ring miss / redial /
# restore, forced-full after load_state_dict), encode-once delta
# fanout, standby promotion through a compressed REPL stream, the
# fused-sync-encode counter, and the CLI refusal matrix.  The fused
# sync encode's parity tests ride smoke-overlap (tests/test_overlap.py).
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

bench:
	python bench.py

# On a TPU (through the chip tool): every training path starts, compiles
# and steps; fails without a chip.  --devices 4 on the four-chip host.
chip-smoke:
	python chip_smoke.py

.PHONY: chip-smoke test tier1 smoke-overlap smoke-chaos chaos-evidence smoke-elastic elastic-evidence smoke-robust robust-evidence smoke-shard shard-evidence smoke-failover failover-evidence smoke-hier hier-evidence smoke-overload overload-evidence lint lint-json lint-fast wire-evidence smoke-serve serve-evidence smoke-bucket bucket-evidence smoke-codec-wire smoke-races bench
