"""Per-step timing / metrics instrumentation.

The reference's observability story is a per-phase wall-clock dict returned
from ``step()`` (`/root/reference/ps.py:116,136-148,160-168,191`) with keys
``code_wait``, ``iallgather_prepare_time``, ``isend_time``, ``comm_wait``,
``decode_time``, ``optim_step_time``, ``msg_bytes``, ``packaged_bytes``, plus
``igather``'s own dict (`mpi_comms.py:73-93`) and a ``print_summary``
pretty-printer (`mpi_comms.py:176-184`).  This module reproduces that
contract — a metrics dict per step, an accumulator, and a summary printer —
with the caveat that under XLA the phases fuse into one compiled program, so
the step reports host-side dispatch/block times and static byte counts, and
per-phase device time comes from a device trace (`program_scopes` below maps
the trace's instructions to the program's named scopes).
"""

from __future__ import annotations

import contextlib
import itertools
import re
import threading
import time
from collections import deque
from typing import Any

import jax
from jax.profiler import TraceAnnotation

# Canonical metric keys, matching the reference step() dict (`ps.py:193`).
STEP_METRIC_KEYS = (
    "code_wait",              # encode phase (0.0 where the step is one program)
    "iallgather_prepare_time",  # trace+compile of the SPMD program (one-time)
    "isend_time",             # collective dispatch latency
    "comm_wait",              # block_until_ready on the synced grads
    "decode_time",            # decode phase
    "optim_step_time",        # parameter update phase
    "msg_bytes",              # encoded payload bytes per rank
    "packaged_bytes",         # on-wire bytes (after codec packaging)
)


# ---------------------------------------------------------------------------
# Overlap-schedule instrumentation
# ---------------------------------------------------------------------------
# The overlap sync engine (`parallel/overlap.py`) makes a scheduling
# decision at compile time — how the gradient pytree partitions into
# buckets — that the per-step wall-clock dicts above cannot see.  Every
# constructed plan lands here so a run's chosen schedule (bucket count,
# bytes, auto-tuned or explicit) is inspectable after the fact, the
# schedule-level analogue of the reference's per-phase timing story.

_OVERLAP_SCHEDULES: list[dict[str, Any]] = []


def record_overlap_schedule(info: "dict[str, Any]") -> None:
    """Append one schedule record (see `OverlapPlan.describe`)."""
    _OVERLAP_SCHEDULES.append(dict(info))


def overlap_schedules() -> "list[dict[str, Any]]":
    """All schedule records since process start (or the last clear)."""
    return list(_OVERLAP_SCHEDULES)


def clear_overlap_schedules() -> None:
    _OVERLAP_SCHEDULES.clear()


# ---------------------------------------------------------------------------
# Fault-tolerance observability
# ---------------------------------------------------------------------------

class RequestLatency:
    """Windowed duration tracker: EMA + rolling p50/p95 over observed
    spans — THE shared percentile engine (ISSUE 14).  Two deployments
    ride it: `RankLatency` keeps one per rank and feeds it
    inter-submission intervals (the training-side audit trail,
    unchanged semantics), and the serve tier's inference front-end
    (`serve.infer.InferenceFrontend`) feeds it per-REQUEST wall
    latencies, making p50/p95 request latency a first-class run metric
    — the SLO observability half of the "one fleet that trains and
    serves" story.

    ``observe(seconds)`` appends one duration; percentiles are computed
    over the last ``window`` observations (rolling, so a long run
    reports its RECENT tail, not its lifetime average).  Reads and
    writes may come from different threads (the inference front-end's
    engine observes while a monitoring thread calls ``stats()``), so
    every window access copies under a small lock — an unsynchronized
    deque iteration racing an append raises "deque mutated during
    iteration" in the READER."""

    __slots__ = ("alpha", "ema", "n", "_win", "_win_lock")

    def __init__(self, window: int = 64, alpha: float = 0.2):
        import threading
        from collections import deque
        self.alpha = float(alpha)
        self.ema: "float | None" = None
        self.n = 0
        self._win = deque(maxlen=int(window))
        self._win_lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._win)

    def observe(self, seconds: float) -> None:
        dt = max(float(seconds), 0.0)
        with self._win_lock:
            self.ema = dt if self.ema is None else (
                self.alpha * dt + (1 - self.alpha) * self.ema)
            self._win.append(dt)
            self.n += 1

    def _copy(self) -> "list[float]":
        with self._win_lock:
            return list(self._win)

    def percentile(self, q: float) -> "float | None":
        import numpy as _np
        data = self._copy()
        if not data:
            return None
        return float(_np.percentile(
            _np.asarray(data, _np.float64), q))

    def p50(self) -> "float | None":
        return self.percentile(50)

    def p95(self) -> "float | None":
        return self.percentile(95)

    def recent_median(self, tail: int = 9,
                      min_obs: int = 3) -> "float | None":
        """Median of the last ``tail`` observations (None below
        ``min_obs``) — the short-window robustness primitive behind
        `RankLatency.speed_weight`: one outage spike is a single
        outlier the median ignores, while sustained slowness dominates
        the window within ~tail/2 observations."""
        data = self._copy()
        if len(data) < min_obs:
            return None
        import numpy as _np
        return float(_np.median(_np.asarray(data[-tail:], _np.float64)))

    def snapshot(self) -> "dict[str, float]":
        """{ema_s, p50_s, p95_s, n} with the established rounding —
        empty dict before the first observation."""
        import numpy as _np
        with self._win_lock:
            data = list(self._win)
            ema, n = self.ema, self.n
        if not data:
            return {}
        arr = _np.asarray(data, _np.float64)
        return {
            "ema_s": round(float(ema), 4),
            "p50_s": round(float(_np.percentile(arr, 50)), 4),
            "p95_s": round(float(_np.percentile(arr, 95)), 4),
            "n": n,
        }


class RankLatency:
    """Per-rank submission-latency tracker: EMA + rolling p50/p95 of the
    time between successive gradient submissions from each rank — one
    `RequestLatency` window per rank, fed inter-arrival intervals.

    This is the audit trail behind the quorum/deadline and quarantine
    decisions: after a run, ``fault_stats["rank_latency"]`` shows which
    rank was the straggler the deadline fired against (its inter-arrival
    p95 dwarfs the fleet's) — without it, "quorum_fills: 12" names no
    culprit.  Host wall-clock only; observed at admission time on the PS.
    """

    def __init__(self, window: int = 64, alpha: float = 0.2):
        self.alpha = float(alpha)
        self._window = int(window)
        self._last: "dict[int, float]" = {}
        self._req: "dict[int, RequestLatency]" = {}

    def observe(self, rank: "int | None", now: "float | None" = None) -> None:
        if rank is None:
            return
        import time as _time
        now = _time.monotonic() if now is None else float(now)
        prev = self._last.get(rank)
        self._last[rank] = now
        if prev is None:
            return  # first submission: no interval yet
        self._req.setdefault(
            rank, RequestLatency(self._window, self.alpha)).observe(
                max(now - prev, 0.0))

    def snapshot(self) -> "dict[int, dict[str, float]]":
        return {rank: req.snapshot()
                for rank, req in sorted(self._req.items()) if len(req)}

    def fleet_p95(self, min_obs: int = 4) -> "float | None":
        """The fleet's typical-rank tail latency: the MEDIAN over ranks
        of each rank's inter-submission p95 (ranks with fewer than
        ``min_obs`` intervals abstain; None with no qualified rank).

        The median over ranks is load-bearing for the adaptive
        fill-deadline: one straggler must NOT drag the fleet figure up
        (the deadline exists precisely to close fills without it), while
        a UNIFORMLY slow fleet moves every rank's p95 — and therefore
        the median — so the derived deadline stretches instead of
        tripping spurious quorum short-fills."""
        import numpy as _np
        per_rank = [req.p95() for req in self._req.values()
                    if len(req) >= min_obs]
        if not per_rank:
            return None
        return float(_np.median(_np.asarray(per_rank)))

    def _recent_median(self, rank, tail: int = 9,
                       min_obs: int = 3) -> "float | None":
        """Median of the rank's last ``tail`` inter-submission intervals
        (None below ``min_obs``) — `RequestLatency.recent_median`, the
        load-bearing short-window choice for `speed_weight` ('persistently
        slower' means a majority of recent intervals, not one bad one;
        an EMA here floored a healthy rank's weight for dozens of fills
        after a single blip)."""
        req = self._req.get(rank)
        if req is None:
            return None
        return req.recent_median(tail=tail, min_obs=min_obs)

    def speed_weight(self, rank: "int | None", *,
                     floor: float = 0.25) -> float:
        """Contribution-weighted admission for heterogeneous fleets: a
        rank PERSISTENTLY slower than the fleet's median pace has its
        contributions down-weighted by (fleet median / its recent
        median), floored at ``floor`` — its influence decays toward its
        actual share of the fleet's throughput instead of the PS
        stalling fills to keep it at parity.  Ranks at or above the
        median pace (and unknown/too-new ranks, or a single-rank fleet)
        weigh 1.0; a single outage spike does not count as slowness
        (see `_recent_median`)."""
        if rank is None:
            return 1.0
        mine = self._recent_median(rank)
        if mine is None:
            return 1.0
        import numpy as _np
        peers = [m for r in self._req
                 for m in [self._recent_median(r)] if m is not None]
        if len(peers) < 2:
            return 1.0
        med = float(_np.median(_np.asarray(peers, _np.float64)))
        if med <= 0.0 or mine <= med:
            return 1.0
        return max(float(floor), med / mine)

    def forget(self, rank) -> None:
        """Drop a departed rank's latency state entirely — an evicted
        rank must not keep a frozen EMA/p95 in the fleet medians that
        drive `speed_weight` and `fleet_p95` (a ghost frozen at
        pre-death speed would hold the adaptive deadline tight while
        the surviving fleet slows — exactly the spurious short-fills
        the adaptation exists to prevent).  A rejoining rank re-warms
        from scratch."""
        self._last.pop(rank, None)
        self._req.pop(rank, None)


def format_fault_stats(fs: "dict[str, Any]") -> str:
    """One-line rendering of a ``fault_stats`` snapshot (see
    `multihost_async.AsyncPSServer`) — the failure-path analogue of the
    per-phase timing summary: evictions, reconnects, quarantined/dropped
    frames and gradients, with zero-valued counters elided so a clean run
    renders as ``clean``."""
    parts = []
    for key in ("evictions", "reconnects", "crc_dropped",
                "quarantined_frames", "stale_dropped", "nonfinite_dropped",
                "accept_errors", "conn_drops",
                # Robust-aggregation / quorum counters (ISSUE 4):
                "quorum_fills", "late_folded", "robust_clipped",
                "duplicate_dropped", "evicted_dropped", "quarantined_drops",
                "surplus_dropped", "breakdown_floor_stalls",
                "floor_relaxed_admits",
                # Sharded-fleet supervision (`shard.fleet.PSFleet`):
                # dead shards rebuilt from their auto-checkpoints, or
                # replaced by their hot standby (ISSUE 7).
                "shard_restores", "promotions",
                # Hot-standby replication stream (REPL/ACKR): updates
                # streamed, applied on the standby, refused after a
                # fencing PROM, and the primary's unacked lag gauge.
                "repl_sent", "repl_received", "repl_refused", "repl_lag",
                # Coordinated fleet snapshots (SNAP barriers) and the
                # router's partition-degradation counters.
                "snapshot_barriers", "partition_drops", "degraded_pulls",
                # Hierarchical aggregation (`shard.hierarchy`): AGG
                # frames admitted at the root / forwarded by aggregators,
                # worker failovers to DIRECT root connections (counted on
                # both sides: agg_failovers at the worker, direct_
                # fallbacks at the root booking the fallback HELO),
                # aggregator redials and supervised restarts.
                "agg_frames", "agg_forwards", "agg_paced",
                "agg_failovers", "agg_redials", "direct_fallbacks",
                "agg_restarts",
                # Heterogeneous-fleet admission: contributions
                # down-weighted by the latency EMA policy, and quorum
                # fill-deadlines tightened from the live p95.
                "latency_weighted", "deadline_adapted",
                # Flow control & overload (ISSUE 10): blown transport
                # Deadline budgets, sender-side credit stalls and
                # oldest-first data-frame sheds, frames shed pre-decode
                # by server admission control under pressure, and the
                # overload injectors' own accounting (extra frames
                # flooded/burst in, frames the slow-consumer injector
                # delayed).
                "deadline_expired", "credits_stalled", "shed_data_frames",
                "admission_shed", "flood_injected", "burst_injected",
                "slow_consumed",
                # Buffer-ownership sanitizer (ISSUE 12): parked-frame
                # checksums verified at flush, and mutations caught —
                # any non-zero trip count accompanied a typed
                # BufferMutatedError.
                "sentinel_checks", "sentinel_trips",
                # Race sanitizer (ISSUE 20): holds(_lock) obligations
                # probed at runtime, and cross-thread violations caught
                # — any non-zero trip count accompanied a typed
                # RaceDetectedError.
                "race_checks", "race_trips",
                # Zero-copy segmented data plane (ISSUE 13, v9):
                # encode-once PARM publishes vs cache fanout reuses,
                # iovec segments gather-sent, and decodes offloaded to
                # the off-GIL pool.
                "parm_encodes", "parm_fanout_reuse", "parm_unchanged",
                "segments_sent", "decode_offloaded",
                # Bucket-streamed async gradients (ISSUE 15, v11):
                # bucket frames sent / folded into completed
                # assemblies, partial assemblies retired, and fused
                # per-bucket grad+encode steps run.
                "buckets_sent", "buckets_filled",
                "bucket_partial_timeouts", "fused_encodes",
                # Serve tier (ISSUE 14, v10): snapshot reads served /
                # shed by the READ-class budget, full-payload delta
                # frames, the live-subscriber gauge, sender-side read
                # stalls, the subscriber's rewind detector, and the
                # inference front-end's admission + hot-swap counters.
                "reads_served", "read_shed", "delta_frames",
                "subs_active", "reads_stalled", "version_rewinds",
                "infer_requests", "infer_shed", "param_swaps",
                # Compressed parameter wire (ISSUE 16, v12): raw vs
                # wire bytes per fresh PARM encode (their ratio is the
                # compression evidence), delta-ring serves vs full
                # fallbacks, and fused sync-encode bucket syncs.
                "parm_bytes_raw", "parm_bytes_wire",
                "delta_hits", "delta_misses", "fused_sync_encodes",
                # Sync-trainer resilience counters (`MPI_PS.fault_stats`):
                # SDC-guard runs, hits and rebroadcasts.
                "sdc_checks", "sdc_mismatches", "sdc_rebroadcasts"):
        v = fs.get(key)
        if v:
            parts.append(f"{key}={v}")
    if fs.get("quarantined_ranks"):
        parts.append(f"quarantined_ranks={fs['quarantined_ranks']}")
    if fs.get("sdc_first_leaf"):
        parts.append(f"sdc_first_leaf={fs['sdc_first_leaf']!r}")
    if fs.get("rollbacks"):
        parts.append(f"rollbacks={len(fs['rollbacks'])}")
    drops = fs.get("dropped_queue_full")
    if drops:
        total = sum(drops.values())
        parts.append(f"dropped_queue_full={total} "
                     f"(ranks {sorted(drops)})")
    if fs.get("evicted_ranks"):
        parts.append(f"evicted_ranks={fs['evicted_ranks']}")
    if fs.get("groups"):
        # The hierarchy's per-group detail (aggregator rank, AGG traffic,
        # fallback ranks) stays structured under "groups"; the one-line
        # summary names which groups exist.
        parts.append(f"groups={sorted(fs['groups'])}")
    return ", ".join(parts) if parts else "clean"


@contextlib.contextmanager
def trace(logdir: str):
    """XLA-level profiling — the upgrade path from the host-side timing
    dicts: wrap any training region and inspect the written trace with
    TensorBoard/Perfetto (per-op device time, collective overlap, HBM
    pressure — everything the reference's wall-clock dicts can't see).

    Usage::

        with trace("/tmp/jax-trace"):
            for batch in data:
                opt.step(batch)
    """
    import jax

    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# Spans inside the program
# ---------------------------------------------------------------------------
# One primitive for the program's own boundaries (today the in-process
# `AsyncPS`: its PS loop and its worker threads).  Always on, like the
# per-step dicts, and bounded, unlike them: a flight recorder that has to be
# switched on before the stall is the one nobody has.

SPAN_PREFIX = "ps:"
SPAN_LOG_CAPACITY = 65536
# `time.thread_time()` is a system call (no vDSO serves the thread's CPU
# clock), and on a sandboxed host one costs 5.7 us, against 0.07 us for
# `perf_counter` (the v5e host's, PR 25).  So a read of it made on the same
# thread less than this long ago is used again: spans that open and close
# back to back share one read, and a span's `cpu` is off by at most this
# much at each end.
_CPU_READ_REUSE_S = 20e-6


class _ThreadSpans(threading.local):
    """Per thread: the ids of its open spans, outermost first, and its last
    read of its own CPU clock as ``(perf_counter, thread_time)``."""

    def __init__(self):
        self.stack: "list[int]" = []
        self.cpu_read = (float("-inf"), 0.0)

    def cpu(self, now: float) -> float:
        """The calling thread's CPU seconds, read at most
        `_CPU_READ_REUSE_S` before ``now``."""
        read_at, cpu = self.cpu_read
        if now - read_at >= _CPU_READ_REUSE_S:
            cpu = time.thread_time()
            self.cpu_read = (now, cpu)
        return cpu


class SpanLog:
    """A ring of finished spans, oldest first.  A record is a dict: ``name``,
    ``thread`` (the thread's name), ``start`` and ``end`` on
    ``time.perf_counter()``, ``cpu`` (the thread's own CPU seconds inside the
    span, so that ``end - start - cpu`` is the time it was off the CPU:
    waiting for the GIL, a lock, a queue or the device), ``id``, ``parent``
    (the ``id`` of the enclosing span on the same thread, or None) and
    whatever ids the span was given.

    Several threads append while another reads, so every access to the ring
    goes through one small lock, as in `RequestLatency`."""

    def __init__(self, capacity: int = SPAN_LOG_CAPACITY):
        self._lock = threading.Lock()
        self._ring: "deque[dict[str, Any]]" = deque(maxlen=int(capacity))
        self._ids = itertools.count(1)   # `next` is one C call: atomic
        self._threads = _ThreadSpans()
        self.dropped = 0                 # records that fell off the front
        self.dropped_until: "float | None" = None   # the last one's `end`

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def _append(self, record: "dict[str, Any]") -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
                self.dropped_until = self._ring[0]["end"]
            self._ring.append(record)

    def records(self, name: "str | None" = None, thread: "str | None" = None,
                since: "float | None" = None,
                until: "float | None" = None) -> "list[dict[str, Any]]":
        """Copies of the records, oldest first: those called ``name``, from
        the thread called ``thread``, that started at or after ``since`` and
        ended at or before ``until`` (each filter only where given)."""
        with self._lock:
            return [dict(r) for r in self._ring
                    if (name is None or r["name"] == name)
                    and (thread is None or r["thread"] == thread)
                    and (since is None or r["start"] >= since)
                    and (until is None or r["end"] <= until)]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0
            self.dropped_until = None


class _Span:
    """One open span (see `span`)."""

    __slots__ = ("_log", "_record", "_annotation", "_cpu0", "duration")

    def __init__(self, log: SpanLog, name: str, ids: "dict[str, Any]"):
        self._log = log
        self._record = {**ids, "name": name}
        self._annotation = TraceAnnotation(SPAN_PREFIX + name, **ids)
        self.duration: "float | None" = None    # seconds, once closed

    def __enter__(self) -> "_Span":
        log, record = self._log, self._record
        mine = log._threads
        stack = mine.stack
        record["thread"] = threading.current_thread().name
        record["id"] = next(log._ids)
        record["parent"] = stack[-1] if stack else None
        stack.append(record["id"])
        self._annotation.__enter__()
        self._cpu0 = mine.cpu(time.perf_counter())
        record["start"] = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        log, record = self._log, self._record
        mine = log._threads
        record["end"], record["cpu"] = end, mine.cpu(end) - self._cpu0
        self._annotation.__exit__(*exc)
        mine.stack.pop()
        self.duration = end - record["start"]
        log._append(record)

    def set(self, **ids) -> None:
        """Complete the record with what is known only after the body ran
        (also after the span has closed)."""
        with self._log._lock:
            self._record.update(ids)


_SPAN_LOG = SpanLog()


def span_log() -> SpanLog:
    """The process-wide span log."""
    return _SPAN_LOG


def span(name: str, **ids) -> _Span:
    """A named span round one boundary of the program::

        with span("async.fill", update=u) as s:
            ...
        s.set(n=len(codes))

    On exit one record goes into `span_log()`.  While it is open the span is
    also a ``jax.profiler.TraceAnnotation("ps:" + name, **ids)``: inside
    `trace(logdir)` it shows on its host thread beside the device's
    operations, on the profiler's clock; with no session it costs a flag
    test."""
    return _Span(_SPAN_LOG, name, ids)


# ---------------------------------------------------------------------------
# Counters that leave a jitted step unread
# ---------------------------------------------------------------------------
# What a step counts on the device (the expert load of a mixture-of-experts
# layer) is worth reading only after the fact.  The step hands the arrays
# over as they are — no `float()`, no callback, no host sync — and whoever
# wants the numbers fetches them later.

COUNTER_LOG_CAPACITY = 4096


class CounterLog:
    """A ring of counter records, oldest first.  A record is a dict:
    ``source`` (who appended it, e.g. ``"MPI_PS.step"``), ``step`` (the
    source's own count) and ``values`` (a pytree of **device arrays**, not
    yet read: ``jax.device_get(record["values"])`` waits for the step that
    made them).  The arrays are outputs of their step that nothing donates,
    so they stay readable however many steps follow."""

    def __init__(self, capacity: int = COUNTER_LOG_CAPACITY):
        self._lock = threading.Lock()
        self._ring: "deque[dict[str, Any]]" = deque(maxlen=int(capacity))
        self.dropped = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def append(self, source: str, step: int, values: Any) -> None:
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._ring.append({"source": source, "step": step,
                               "values": values})

    def records(self, source: "str | None" = None) -> "list[dict[str, Any]]":
        with self._lock:
            return [dict(r) for r in self._ring
                    if source is None or r["source"] == source]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self.dropped = 0


_COUNTER_LOG = CounterLog()


def counter_log() -> CounterLog:
    """The process-wide counter log (beside `span_log()`)."""
    return _COUNTER_LOG


# ---------------------------------------------------------------------------
# Which named scope a device operation belongs to
# ---------------------------------------------------------------------------
# A device trace names an operation by its HLO instruction (``fusion.412``),
# and the `jax.named_scope` it was traced under is in that instruction's
# ``op_name`` metadata, which only the compiled program's text carries.  A
# program that runs under named scopes registers its compiled executable's
# ``as_text``; the text is made and parsed when somebody asks, never on the
# training path.

_PROGRAMS: "dict[str, Any]" = {}
_PROGRAMS_LOCK = threading.Lock()
_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*\bop_name="([^"]*)"', re.MULTILINE)
# An instruction is one line of the text, but for a Pallas call that carries
# `metadata=`: its ``kernel_metadata={`` is printed over three lines, the
# ``op_name`` on the last.  Such continuation lines start with ``"`` or ``}``.
_CONTINUATION = re.compile(r'\n(?=["}])')
# A computation's header, at the left margin (instructions are indented), and
# a fusion instruction with the computation it calls.
_COMPUTATION = re.compile(r'^(?:ENTRY\s+)?%?([\w.\-]+) \([^\n]*\{$',
                          re.MULTILINE)
_FUSION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*\bfusion\([^\n]*'
    r'\bcalls=%?([\w.\-]+)', re.MULTILINE)


def register_program(name: str, text_fn) -> None:
    """``text_fn()`` returns the compiled program's HLO text (after XLA's
    passes, with metadata): the ``as_text`` of a `jax.stages.Compiled`.  The
    newest registration under a name wins; it is kept, and with it the
    compiled program (not its arguments), until the next one."""
    with _PROGRAMS_LOCK:
        _PROGRAMS[name] = {"text_fn": text_fn, "parsed": None}


def _parsed(name: str) -> "dict | None":
    """The registered program's text, read once: ``scopes`` for
    `program_scopes`, ``fusions`` for `program_fusions`."""
    with _PROGRAMS_LOCK:
        entry = _PROGRAMS.get(name)
    if entry is None:
        return None
    if entry["parsed"] is None:
        text = _CONTINUATION.sub(" ", entry["text_fn"]())
        # name, body, name, body, ...: a computation's instructions follow
        # its header up to the next header
        pieces = _COMPUTATION.split(text)[1:]
        bodies = {comp: tuple(n for n, _ in _OP_NAME.findall(body))
                  for comp, body in zip(pieces[::2], pieces[1::2])}
        entry["parsed"] = {
            "scopes": dict(_OP_NAME.findall(text)),
            "fusions": {n: bodies.get(comp, ())
                        for n, comp in _FUSION.findall(text)}}
    return entry["parsed"]


def program_scopes(name: str) -> "dict[str, str] | None":
    """``{HLO instruction name: op_name}`` of the program registered under
    ``name`` — ``op_name`` holds the named scopes the operation was traced
    under, as in ``jit(step)/.../kda/while/body/dot_general`` — or None
    where no such program is registered.  The first call fetches the text
    and parses it; the result is kept."""
    parsed = _parsed(name)
    return None if parsed is None else parsed["scopes"]


def program_fusions(name: str) -> "dict[str, tuple] | None":
    """``{fusion instruction: the instructions XLA fused into it}`` of the
    program registered under ``name`` (those with an ``op_name``: look each
    up in `program_scopes`), or None where no such program is registered.  A
    device trace shows a fusion as ONE operation under its root's
    ``op_name``, whatever else the compiler put inside: an optimizer's rule
    fused into the matrix product that makes its gradient runs under the
    product's name.  This is how a reader tells."""
    parsed = _parsed(name)
    return None if parsed is None else parsed["fusions"]


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is one whole component of the ``op_name`` path,
    bare or wrapped by a transformation (``transpose(jvp(kda))``)."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)",
                     op_name) is not None


# The scopes `MPI_PS.step` puts round the phases of its own program
# (`ps.py:_make_spmd_step`, `parallel/overlap.py`), in the order `step_phase`
# asks for them: a scope of the step's own beats the transformation wrappers
# round it (the bucket hooks of ``sync_mode="overlap"`` run their sums inside
# the backward), and ``ps.grad`` comes last.  The names hold a dot so that no
# model scope or flax module is called the same.  (A codec's encode and
# decode and the non-finite guard have no scope: no cell runs them, so no
# metric would read one.)
STEP_SCOPES = {"exchange": "ps.exchange", "update": "ps.update",
               "grad": "ps.grad"}
# JAX's own words in an ``op_name``: what `jax.checkpoint` calls the forward
# it runs again inside the backward, and how a transposed (backward)
# computation is wrapped.  Pinned in tier-1 against a compiled program.
_REMAT_MARK = "rematted_computation"
_TRANSPOSE_MARK = "transpose("


def step_scope(phase: str):
    """The `jax.named_scope` of one phase of the fused step (a key of
    `STEP_SCOPES`): metadata on the operations traced inside it, nothing
    else."""
    return jax.named_scope(STEP_SCOPES[phase])


def step_phase(op_name: str) -> "str | None":
    """The phase of `MPI_PS.step`'s program that an instruction with this
    ``op_name`` belongs to: ``"exchange"`` or ``"update"`` under the step's
    scope of that name; else, under ``ps.grad``, ``"remat"`` (forward work
    done a second time inside the backward), ``"backward"`` or
    ``"forward"``; None under none of them."""
    for phase, scope in STEP_SCOPES.items():
        if not in_scope(op_name, scope):
            continue
        if phase != "grad":
            return phase
        if _REMAT_MARK in op_name:
            return "remat"
        return "backward" if _TRANSPOSE_MARK in op_name else "forward"
    return None


class BoundedList(list):
    """The per-step dicts of a long run (`MPI_PS.timings`): a list in every
    way (``len``, slices, iteration) that holds at most `SPAN_LOG_CAPACITY`
    records.  An append to a full list first drops the oldest sixteenth, so
    the cost of dropping is spread over thousands of steps."""

    def append(self, record) -> None:
        if len(self) >= SPAN_LOG_CAPACITY:
            del self[:max(1, SPAN_LOG_CAPACITY // 16)]
        super().append(record)


def print_summary(timings: list[dict[str, Any]], keys=None) -> None:
    """Mean/max per metric over accumulated step dicts —
    ``print_summary`` analogue (`/root/reference/mpi_comms.py:176-184`)."""
    if not timings:
        print("(no timings)")
        return
    if keys is None:
        keys = sorted({k for t in timings for k in t})
    width = max(len(k) for k in keys)
    for k in keys:
        vals = [float(t[k]) for t in timings if k in t]
        if not vals:
            continue
        mean = sum(vals) / len(vals)
        print(f"{k:<{width}}  mean={mean:10.6f}  max={max(vals):10.6f}  "
              f"n={len(vals)}")
