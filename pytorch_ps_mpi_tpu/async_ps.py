"""Asynchronous parameter server — AsySG-InCon, TPU-native.

The reference designs (but never codes) an async PS in its README
(`/root/reference/README.md:56-77`, algorithm AsySG-InCon from
arXiv:1506.08272): rank 0 receives gradients from ``MPI.ANY_SOURCE`` until a
quota is met, **sums** them, applies one optimizer step, and re-broadcasts the
parameters with *inconsistent reads* — workers may read parameters mid-update
(`README.md:79-81` notes consistent reads would need a buffered broadcast).
The building blocks it provides are ``igather``/``irecv``
(`/root/reference/mpi_comms.py:60-117`, rank-0-only receive) and
``ibroadcast``/``irecv1`` (`mpi_comms.py:120-133`).

TPU-native redesign (the genuinely novel engineering in this port — SURVEY
§7 "hard parts"): XLA's SPMD model has no ``ANY_SOURCE``, so the async
topology is **host-driven** on the single-controller runtime.  This module
is the single-host realization (workers = local devices driven by threads);
`multihost_async` extends the same algorithm across processes/hosts with a
TCP transport — use that when ``jax.process_count() > 1``-scale deployments
(the reference's multi-node ladder rung) are the target:

* every worker is a *device* running its own jitted
  ``grad+encode`` program, driven by a host thread — JAX async dispatch means
  the thread posts work and the device runs free, the analogue of one MPI rank;
* the PS owns canonical params + optimizer state on its own device; completed
  (encoded) gradients arrive over a host queue (the ``ANY_SOURCE`` receive) as
  device-to-device transfers of the *compressed* code pytree;
* after ``quota`` gradients are in, the PS sums the decoded grads
  (``p = sum(params); step()`` in the README pseudo-code) and **publishes the
  new params leaf-by-leaf** into a shared dict. Workers snapshot that dict
  leaf-by-leaf with no lock — a worker that reads concurrently with an update
  sees a mix of old and new leaves. This is not a bug: it is precisely
  AsySG-InCon's *inconsistent read*, realized with host memory instead of an
  unbuffered ``Ibcast``.

Staleness is first-class: each gradient is tagged with the parameter version
it was computed from, and every update records the staleness distribution of
the gradients it consumed — the observability the reference's timing dicts
(`ps.py:116-148`) provide for the sync path, extended to the async one.
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np

from .errors import FleetDeadError, NotCompiledError, WorkerFailedError
from .ops.codecs import Codec, IdentityCodec, get_codec
from .parallel.mesh import default_devices
from .ps import init_ps_core
from .utils.bytes import bytes_of
from .utils.timing import BoundedList, span

Params = "OrderedDict[str, jax.Array]"

# Adaptive fill-deadline bounds: the live-p95-derived deadline never
# shrinks below this floor (a sub-millisecond deadline would close every
# fill at bare quorum on scheduler noise alone).
_ADAPTIVE_DEADLINE_FLOOR = 0.005


def make_worker_step(loss_fn: Callable, code: Codec, grad_transform=None):
    """The jitted per-worker program — grad + per-leaf encode.  Shared by
    the single-host device workers (`AsyncPS.compile_step`) and the
    multi-host TCP workers (`multihost_async.AsyncPSWorker`), so the encode
    contract cannot silently diverge between the two deployments.

    ``grad_transform`` (a gradient-tree -> gradient-tree fn) is the
    Byzantine-fault injection point (`FaultPlan.byzantine_transform`): it
    runs on the RAW gradients before encoding, so the attack rides any
    codec faithfully.  None (the default) compiles the honest program."""

    def worker_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if grad_transform is not None:
            grads = grad_transform(grads)
        codes = OrderedDict((n, code.encode(g)) for n, g in grads.items())
        return loss, codes

    return jax.jit(worker_step)


@jax.jit
def _stack_codes(*codes):
    """The fill's code trees with a leading contribution axis on every leaf,
    as ONE program (a compile per count of contributions, as `ps_apply`).
    Stacked leaf by leaf — an eager ``jnp.stack`` each, 161 of them for a
    ResNet-50 — the PS thread's dispatches queue behind the gradient program
    that a worker sharing the device has just dispatched, and the runtime
    holds the thread until that program ends: PS loop and worker then take
    turns instead of running side by side (PERF.md, Findings PR 27)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *codes)


class _Published:
    """The broadcast surface: a leaf-wise-updated params dict plus a version
    counter.  Readers take no lock (inconsistent reads by design); the version
    is bumped only after every leaf of an update has landed, so
    ``staleness = writer.version - read_version`` is a *lower bound* on how
    stale a mixed read is."""

    def __init__(self, params: Params):
        self.leaves = dict(params)
        self.version = 0

    def publish(self, new_params: Params) -> None:
        for n, p in new_params.items():   # leaf-by-leaf: mid-update readers
            self.leaves[n] = p            # see a mix of versions (InCon)
        self.version += 1

    def snapshot(self) -> tuple[Params, int]:
        v = self.version
        return OrderedDict((n, self.leaves[n]) for n in self.leaves), v


class BatchDrawer:
    """One worker's batches, drawn one iteration ahead of their use.

    A helper thread (``async-ps-worker-{rank}-draw``) calls
    ``batch_fn(rank, it)`` for ``it = 0, 1, 2, ...`` — in that order, once
    each, all from that one thread, each call inside
    ``span("async.draw", rank=rank, it=it)`` — and hands the batch to the
    worker through a hand-off that holds ONE.  So at most one finished batch
    waits while the next is being drawn: two host batches ahead of use,
    never more.  One thread, not a pool: a second would call ``batch_fn``
    out of order, and a ``batch_fn`` that wraps an iterator is a legitimate
    user.

    The worker it belongs to is its context manager: entering starts the
    thread; leaving stops it, joins it and drops the batch left waiting.
    Whatever ``batch_fn`` raises rides the hand-off in the batch's place and
    is raised by the `take` of the iteration it belongs to, on the worker's
    thread, where it would have been raised without the drawer."""

    # Seconds between two looks at a stop flag while blocked on the
    # hand-off, as `async.enqueue` polls the gradient queue.
    _POLL = 0.05

    def __init__(self, batch_fn: Callable[[int, int], Any], rank: int):
        self._batch_fn, self._rank = batch_fn, rank
        self._handoff: "queue.Queue" = queue.Queue(maxsize=1)
        self._closed = threading.Event()
        self._thread = threading.Thread(
            target=self._draw_loop, daemon=True,
            name=f"async-ps-worker-{rank}-draw")

    def __enter__(self) -> "BatchDrawer":
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._closed.set()
        self._thread.join(timeout=5.0)
        try:
            self._handoff.get_nowait()
        except queue.Empty:
            pass

    def _draw_loop(self) -> None:
        it, failed = 0, False
        while not (failed or self._closed.is_set()):
            try:
                with span("async.draw", rank=self._rank, it=it):
                    item = (self._batch_fn(self._rank, it), None)
            except BaseException as exc:   # re-raised by `take`
                item, failed = (None, exc), True
            while not self._closed.is_set():
                try:
                    self._handoff.put(item, timeout=self._POLL)
                    break
                except queue.Full:
                    pass
            del item    # the hand-off's, then the worker's: not held here
            it += 1

    def take(self, stop: threading.Event) -> "tuple[Any, bool]":
        """``(batch, ready)``: the next batch in order, and whether it was
        already waiting.  ``(None, False)`` once ``stop`` is set."""
        ready = not self._handoff.empty()   # one taker: what is there stays
        while not stop.is_set():
            try:
                batch, exc = self._handoff.get(timeout=self._POLL)
            except queue.Empty:
                continue
            if exc is not None:
                raise exc
            return batch, ready
        return None, False


class AsyncPS:
    """Host-driven asynchronous parameter server (AsySG-InCon).

    Usage::

        opt = AsyncSGD(model_named_params, lr=0.1, quota=4)
        opt.compile_step(loss_fn)                  # loss_fn(params, batch)
        history = opt.run(batch_fn, steps=500)

    ``batch_fn(rank, it) -> batch`` supplies worker ``rank``'s ``it``-th local
    batch (the analogue of each MPI rank reading its own data shard).  Each
    rank's calls come in order of ``it`` (0, 1, 2, ...), once each, all from
    one helper thread of that rank's worker (`BatchDrawer`), up to two
    iterations ahead of the gradient that uses the batch — so ``batch_fn`` may
    wrap an iterator, is drawn up to two batches past the end of a ``run``,
    and must not depend on the parameters: it runs beside the updates.

    ``quota`` is the number of gradients the PS consumes per update
    (`/root/reference/README.md:66-70` hard-codes 32); gradients left in the
    queue when a quota fills are consumed — stale — by later updates, exactly
    the inconsistency the algorithm tolerates.

    ``ps_is_worker=False`` matches the README topology (rank 0 only serves);
    with one visible device the PS and the single worker share it.
    """

    def __init__(self, named_params, *, optim: str = "sgd",
                 code: Codec | str | None = None, quota: int | None = None,
                 devices=None, ps_is_worker: bool = False,
                 staleness_weighting: bool = False,
                 max_staleness: int | None = None,
                 skip_nonfinite: bool = False,
                 aggregate: str = "mean", trim_k: int | None = None,
                 quorum: int | None = None, fill_deadline: float = 0.0,
                 anomaly_z: float | None = None,
                 adaptive_deadline: bool = False,
                 latency_weighting: bool = False,
                 credit_window: int = 0,
                 fault_plan=None, **hyper):
        from .ops.robust import ROBUST_REDUCERS, RankScoreboard
        from .utils.timing import RankLatency

        self.optim = optim
        # Robust aggregation (ops.robust): how a fill's contributions
        # combine.  "mean" is the legacy staleness-weighted sum (renormed
        # to the fill target under quorum short-fills); the others are the
        # Byzantine-robust reducers.
        if aggregate not in ROBUST_REDUCERS:
            raise ValueError(f"unknown aggregate {aggregate!r}; have "
                             f"{list(ROBUST_REDUCERS)}")
        self.aggregate = aggregate
        if trim_k is not None and trim_k < 1:
            raise ValueError(f"trim_k must be >= 1, got {trim_k}")
        self.trim_k = trim_k
        # Straggler-tolerant quorum fills: once `quorum` contributions are
        # in and `fill_deadline` seconds have passed since the fill
        # started, the update proceeds with what it has (renormalized to
        # the fill target) instead of stalling on the slowest rank.
        if quorum is not None and quorum < 1:
            raise ValueError(f"quorum must be >= 1, got {quorum}")
        if fill_deadline < 0:
            raise ValueError(
                f"fill_deadline must be >= 0, got {fill_deadline}")
        self.quorum = quorum
        self.fill_deadline = float(fill_deadline)
        # Adaptive fill-deadline (off by default): derive each fill's
        # effective deadline from the live per-rank latency p95 —
        # ``min(fill_deadline, margin * fleet_p95)`` — so the configured
        # deadline becomes a CEILING, not a constant: a fast fleet closes
        # short fills promptly while a uniformly-slow fleet stretches
        # toward the ceiling instead of tripping spurious short fills.
        if adaptive_deadline and quorum is None:
            raise ValueError(
                "adaptive_deadline derives the quorum fill-deadline from "
                "live latencies; without a quorum no fill ever closes "
                "short, so the flag would be silently inert — set quorum "
                "(and a fill_deadline ceiling) or drop it")
        self.adaptive_deadline = bool(adaptive_deadline)
        # Heterogeneous-fleet admission (off by default): contributions
        # from ranks persistently slower than the fleet median are
        # down-weighted by their latency-EMA ratio
        # (`utils.timing.RankLatency.speed_weight`) — a slow device's
        # influence decays toward its actual throughput share instead of
        # every fill stalling to keep it at parity.
        self.latency_weighting = bool(latency_weighting)
        # Per-rank anomaly scoring/quarantine (None = off, the default).
        self.anomaly_z = anomaly_z
        self._scoreboard = (RankScoreboard(anomaly_z)
                            if anomaly_z is not None else None)
        self._latency = RankLatency()
        # norm_clip's rolling median: recent admitted contribution norms.
        self._norm_window: deque = deque(maxlen=64)
        # Ranks that missed a quorum-shortened fill; their next admitted
        # gradient is the "late frame folded into a later fill".
        self._missed_ranks: set = set()
        # Non-linear reducers get their breakdown point PER CONTRIBUTOR —
        # a fast Byzantine rank must not occupy two of a 3-slot fill and
        # out-vote the trim.  With a robust reducer, each fill admits at
        # most one contribution per rank; surplus frames are held over
        # for the next fill (bounded per rank, then dropped + counted).
        self._rank_distinct = aggregate != "mean"
        self._held: list = []
        # AsySG-InCon tolerates staleness but weighs all gradients equally;
        # with weighting on, gradient i scales by 1/(1+s_i) before the sum
        # (the standard staleness-aware damping), applied to the *codes*
        # via `Codec.scale_code` so the fused decode-sum path survives.
        self.staleness_weighting = staleness_weighting
        # Bounded-staleness admission: a gradient older than this many
        # versions is dropped (counted, never applied) — AsySG's tolerance
        # has a cliff, and after a fault (worker frozen then resumed, PS
        # restarted) unbounded staleness is how runs diverge silently.
        if max_staleness is not None and max_staleness < 0:
            raise ValueError(f"max_staleness must be >= 0, got {max_staleness}")
        self.max_staleness = max_staleness
        # Non-finite quarantine, the async analogue of the sync PS's
        # skip_nonfinite consensus gate: checked per received gradient on
        # the host (`ps.tree_all_finite`), dropped + counted instead of
        # poisoning params.
        self.skip_nonfinite = skip_nonfinite
        # Bounded-queue size / advertised flow-control window (ISSUE 10):
        # in-process it bounds the gradient queue (the backpressure that
        # keeps staleness bounded); the TCP server additionally
        # advertises it as the v8 credit window.  0 = deployment default.
        if credit_window < 0:
            raise ValueError(
                f"credit_window must be >= 0, got {credit_window}")
        self.credit_window = int(credit_window)
        self.fault_plan = fault_plan
        # Overload-injector counter lock: flood/burst bumps come from
        # CONCURRENT worker threads (a burst fires on every rank at the
        # same iteration), while the base `_bump` stays lock-free for
        # the single-consumer serve loop.
        self._overload_lock = threading.Lock()
        # Admission/fault counters; merged into the run history as
        # ``history["fault_stats"]`` (the transport server extends these
        # with eviction/reconnect/wire counters).  The base `_bump` is
        # deliberately lock-free: only the serve loop mutates the dict
        # in this class (the TCP server overrides `_bump` with a locked
        # version, and the worker-side flood bump below holds
        # `_overload_lock`) — the single-writer contract the PSL8xx
        # races checker enforces.
        self.fault_stats: dict[str, Any] = {  # pslint: single-writer(serve-loop)
            "stale_dropped": 0, "nonfinite_dropped": 0,
            # Admission+aggregation subsystem counters: fills closed short
            # at quorum, straggler frames folded into a later fill,
            # contributions clipped by norm_clip, and submissions dropped
            # because their rank is quarantined.
            "quorum_fills": 0, "late_folded": 0, "robust_clipped": 0,
            "quarantined_drops": 0, "surplus_dropped": 0,
            "breakdown_floor_stalls": 0, "floor_relaxed_admits": 0,
            # Heterogeneous-fleet admission: fills whose quorum deadline
            # was tightened below the configured ceiling by the live
            # latency p95, and contributions down-weighted by the
            # latency-EMA policy.
            "deadline_adapted": 0, "latency_weighted": 0,
            # Flow-control / overload counters (ISSUE 10): transport ops
            # that blew their Deadline budget, sender-side credit stalls
            # and oldest-first data-frame sheds, frames shed pre-decode
            # by server admission control under pressure, and the
            # overload chaos injectors' own accounting (flooded/burst
            # extra frames injected, frames the slow-consumer injector
            # delayed).
            "deadline_expired": 0, "credits_stalled": 0,
            "shed_data_frames": 0, "admission_shed": 0,
            "flood_injected": 0, "burst_injected": 0, "slow_consumed": 0,
            # Byte-sentinel sanitizer (ISSUE 12, PS_BUFFER_SENTINEL=1):
            # parked-frame checksums re-verified at flush, and the
            # mutations caught.  Trips raise typed BufferMutatedError —
            # a non-zero count here means a run DIED on corruption the
            # frame CRC could never see; the counters flow in from the
            # transport sessions via the fault_snapshot merges.
            "sentinel_checks": 0, "sentinel_trips": 0,
            # Race sanitizer (ISSUE 20, PS_RACE_SANITIZER=1): session
            # holds(_lock) obligations probed at runtime, and the
            # violations caught (each also raises typed
            # RaceDetectedError — non-zero trips means a run DIED on a
            # cross-thread lockset violation the static PSL8xx pass
            # could only approximate).  Flow in from the transport
            # sessions via the fault_snapshot merges, like the sentinel.
            "race_checks": 0, "race_trips": 0,
            # Zero-copy segmented data plane (ISSUE 13, protocol v9):
            # PARM segment sets encoded (once per served version) vs
            # fanned out from the cache, scatter-gather segments handed
            # to sendmsg (server PARM replies + the sessions' data
            # sends, merged in via fault_snapshot), and GRAD/AGGR
            # decodes routed through the off-GIL decode pool.
            "parm_encodes": 0, "parm_fanout_reuse": 0,
            "parm_unchanged": 0, "segments_sent": 0,
            "decode_offloaded": 0,
            # Bucket-streamed async gradients (ISSUE 15, protocol v11):
            # bucket frames handed to the transport (sender side, merged
            # in via fault_snapshot), bucket frames folded into
            # COMPLETED per-(rank, seq) assemblies at the PS, partial
            # assemblies retired (bucket shed / connection died
            # mid-gradient — the absent gradient folds into the quorum
            # machinery like any straggler), and fused per-bucket
            # grad+encode steps run at workers.
            "buckets_sent": 0, "buckets_filled": 0,
            "bucket_partial_timeouts": 0, "fused_encodes": 0,
            # Serve tier (ISSUE 14, protocol v10): SUBS reads answered
            # (unchanged + delta), reads shed by the READ-class budget
            # (server tokens or the sender-side read gate),
            # full-payload DELT replies, the live-subscriber gauge, and
            # the inference front-end's admission accounting (requests
            # arrived / shed with a typed refusal at overload); the
            # subscriber-side session's ``reads_stalled`` merges in via
            # the fault_snapshot path like every session counter.
            "reads_served": 0, "read_shed": 0, "delta_frames": 0,
            "subs_active": 0, "reads_stalled": 0,
            "infer_requests": 0, "infer_shed": 0,
            # Compressed parameter wire (ISSUE 16, protocol v12): raw
            # f32 leaf bytes vs post-codec wire bytes per fresh PARM
            # encode (the bytes-per-version evidence — their ratio IS
            # the compression gate), delta-ring serves vs full-snapshot
            # fallbacks on the DELT path, and sync-path bucket syncs
            # that ran the fused in-graph encode twin
            # (`parallel.overlap.make_bucket_sync_fn(fused_encode=...)`).
            "parm_bytes_raw": 0, "parm_bytes_wire": 0,
            "delta_hits": 0, "delta_misses": 0,
            "fused_sync_encodes": 0}

        if devices is None:
            devices = default_devices()
        self.ps_device = devices[0]
        # Codec kernels follow the devices this PS was given (Mosaic on
        # TPUs, the jnp reference on CPU devices), not the default backend.
        self.code = get_codec(code, self.ps_device.platform)
        if len(devices) == 1:
            self.worker_devices = [devices[0]]
        else:
            self.worker_devices = list(devices) if ps_is_worker else list(devices[1:])
        self.num_workers = len(self.worker_devices)
        self.quota = int(quota) if quota is not None else self.num_workers
        if self.quota < 1:
            raise ValueError(f"quota must be >= 1, got {self.quota}")
        if self.quorum is not None and self.quorum > self.quota:
            raise ValueError(
                f"quorum ({self.quorum}) cannot exceed the quota "
                f"({self.quota}) — it is the minimum fill, not a second "
                f"target")
        # A trim/median fill below its breakdown size silently degenerates
        # to a plain mean — under exactly the conditions the robust rule
        # is sold for (a straggler shortening fills while an attacker is
        # live).  Refuse the configuration eagerly instead: trimmed_mean
        # needs every fill >= 2k+1 contributions, median >= 3.
        min_fill = {"trimmed_mean": 2 * (1 if trim_k is None else trim_k)
                    + 1, "median": 3}.get(aggregate)
        if min_fill is not None:
            floor = self.quota if self.quorum is None else self.quorum
            if floor < min_fill:
                raise ValueError(
                    f"aggregate={aggregate!r} needs every fill to keep >= "
                    f"{min_fill} contributions (2*trim_k+1 for "
                    f"trimmed_mean, 3 for median), but "
                    f"{'quorum' if self.quorum is not None else 'quota'}="
                    f"{floor} allows smaller fills, where the rule "
                    f"silently degenerates to a plain mean — raise the "
                    f"fill floor or use norm_clip, whose influence bound "
                    f"holds at any fill size")
        # The same floor is re-checked at fill time (`_shrink_floor`):
        # runtime shrinkage (transport eviction, quarantine) must not
        # quietly hand an attacker a sub-breakdown fill either.
        self._min_fill = 1 if min_fill is None else min_fill
        self._floor_binding = False
        # A fill that waits past the deadline without --quorum never
        # closes short, so a configured deadline would be silently inert
        # — refuse instead (same contract as the CLI).
        if self.fill_deadline > 0 and self.quorum is None:
            raise ValueError(
                "fill_deadline only takes effect with a quorum (fills "
                "without one always wait for the full target); set "
                "quorum or drop fill_deadline")

        self.params, self.state, self.hyper, self._update_fn = init_ps_core(
            named_params, optim, hyper,
            place=lambda x: jax.device_put(x, self.ps_device))

        self._loss_fn: Callable | None = None
        self._worker_fn = None
        self._worker_fn_byz = None
        self._apply_fn = None
        self._apply_robust_fn = None
        self._norm_fn = None
        self._itemwise = False
        self.timings: list[dict[str, float]] = BoundedList()
        # Test/diagnostic knob: workers wait for their own gradient to be
        # consumed before pulling again, making 1-worker runs deterministic
        # (sequential SGD).  Never the default — it is a barrier.
        self._lockstep = False

    # -- program construction -------------------------------------------------

    def compile_step(self, loss_fn: Callable) -> None:
        """Bind ``loss_fn(params, batch) -> loss`` and build the two jitted
        programs: the per-worker grad+encode step and the PS decode-sum+update
        step.  (Aux/BatchNorm state is a sync-PS feature; the async variant
        mirrors the reference pseudo-code, plain params only.)"""
        self._loss_fn = loss_fn

        code = self.code
        self._worker_fn = make_worker_step(loss_fn, code)
        # Byzantine injection (in-process deployment): the attacked rank
        # runs its own compiled program; TCP workers compile their own
        # transformed step from the same hook.
        self._worker_fn_byz = None
        if (self.fault_plan is not None
                and getattr(self.fault_plan, "byzantine_rank", None)
                is not None):
            self._worker_fn_byz = make_worker_step(
                loss_fn, code, self.fault_plan.byzantine_transform(
                    self.fault_plan.byzantine_rank))

        # Typed compile-time refusal: non-linear reducers (and anomaly
        # scoring, which needs per-contribution norms) require itemwise
        # decodes; a decode_sum-only codec cannot provide them.
        from .ops.robust import check_reducer_codec, robust_reduce
        self._itemwise = check_reducer_codec(
            self.aggregate, code,
            anomaly_scoring=self._scoreboard is not None)

        meta = {n: (p.shape, p.dtype) for n, p in self.params.items()}
        hyper = dict(self.hyper)
        update_fn = self._update_fn

        def ps_apply(params, state, stacked_codes, weights=None):
            # stacked_codes: every code leaf gains a leading quota dim.
            # decode_sum implements the README's `p = sum(params)` — sum, not
            # mean, matching the sync path (`/root/reference/ps.py:176`).
            # Weights are applied whenever the caller passes them (static
            # at trace time — the weight-free default path pays no extra
            # multiply): staleness damping, quorum renormalization,
            # scoreboard down-weights, latency decay, and the
            # hierarchy's contribution multiplicities all ride this one
            # scale.  (Keying on the ARGUMENT, not on the
            # staleness_weighting flag, matters: with staleness off, a
            # quorum-renormalized or contribution-weighted mean fill
            # used to silently drop its weights on this fused path.)
            from .optim.schedules import resolve_hyper

            new_params, new_state = OrderedDict(), OrderedDict()
            for n, p in params.items():
                shape, dtype = meta[n]
                codes_n = stacked_codes[n]
                if weights is not None:
                    codes_n = jax.vmap(code.scale_code)(codes_n, weights)
                d_p = code.decode_sum(codes_n, shape=shape, dtype=dtype)
                h = resolve_hyper(hyper, state[n]["step"])
                new_params[n], new_state[n] = update_fn(p, d_p, state[n], **h)
            return new_params, new_state

        self._apply_fn = jax.jit(ps_apply)

        aggregate, trim_k = self.aggregate, self.trim_k

        def decode_stack(stacked_codes, name):
            """Dense per-contribution decodes for one parameter: an
            unrolled python loop over the (small, static) contributor
            count — vmapping Pallas-backed decodes (blockq) is not
            portable, and n is at most the quota."""
            shape, dtype = meta[name]
            codes_n = stacked_codes[name]
            n_contrib = jax.tree_util.tree_leaves(codes_n)[0].shape[0]
            items = [code.decode(jax.tree.map(lambda x: x[i], codes_n),
                                 shape=shape, dtype=dtype)
                     for i in range(n_contrib)]
            return jnp.stack(items)

        def ps_apply_robust(params, state, stacked_codes, weights,
                            n_target, clip_norm):
            # The decode-then-reduce path: every contribution decoded to
            # dense, robust-reduced coordinate/norm-wise (`ops.robust`),
            # then the torch-parity update.  Recompiles per distinct
            # contributor count — bounded by quota - quorum + 1 variants.
            from .optim.schedules import resolve_hyper

            decoded = OrderedDict(
                (n, decode_stack(stacked_codes, n)) for n in params)
            reduced, info = robust_reduce(
                aggregate, decoded, weights, n_target=n_target,
                trim_k=trim_k, clip_norm=clip_norm)
            new_params, new_state = OrderedDict(), OrderedDict()
            for n, p in params.items():
                h = resolve_hyper(hyper, state[n]["step"])
                new_params[n], new_state[n] = update_fn(
                    p, reduced[n], state[n], **h)
            return new_params, new_state, info

        self._apply_robust_fn = jax.jit(ps_apply_robust)

        def contrib_norm(codes):
            """Global L2 norm of ONE submission's decoded gradient — the
            scoring probe for quarantined ranks, whose submissions are
            dropped before the stacked apply ever sees them (recovery must
            stay observable)."""
            sq = jnp.zeros((), jnp.float32)
            for n in codes:
                shape, dtype = meta[n]
                d = code.decode(codes[n], shape=shape, dtype=dtype)
                sq = sq + jnp.sum(d.astype(jnp.float32) ** 2)
            return jnp.sqrt(sq)

        self._norm_fn = jax.jit(contrib_norm)
        if self._scoreboard is not None:
            # Pre-warm NOW, on the compile path: the first quarantined
            # submission otherwise triggers this program's first compile
            # in the middle of the fill loop, concurrent with worker
            # dispatch — observed to stall the fill when workers share
            # the process (threaded test/evidence fleets).  One dummy
            # call costs milliseconds here and makes
            # the serve-loop call a pure cache hit.
            dummy = OrderedDict(
                (n, jax.tree.map(np.asarray,
                                 code.encode(jnp.zeros(p.shape, p.dtype))))
                for n, p in self.params.items())
            float(self._norm_fn(dummy))

    def _bump(self, key: str, n: int = 1) -> None:
        """Counter bump; the TCP server overrides this with a locked
        version (its conn threads write concurrently)."""
        self.fault_stats[key] += n

    # pslint: only-called-by(_fill_gradients)
    # pslint: returns-counter-keys
    def _admit(self, codes, staleness, loss) -> "str | None":
        """Admission control for one received gradient: returns None to
        admit, or the fault_stats counter key it was rejected under.
        Called only from `_fill_gradients`, the one fill loop both
        deployments share, so they cannot diverge on what they
        quarantine."""
        if (self.max_staleness is not None
                and staleness > self.max_staleness):
            return "stale_dropped"
        if self.skip_nonfinite:
            from .ps import tree_all_finite
            if not (np.isfinite(float(loss)) and tree_all_finite(codes)):
                return "nonfinite_dropped"
        return None

    def _shrink_floor(self, target: int, cause: str) -> int:
        """Clamp runtime fill-target shrinkage (eviction, quarantine) to
        the active reducer's breakdown size.  The eager constructor check
        only bounds the CONFIGURED floor; letting the fleet's decay shrink
        fills below ``2*trim_k+1`` (or 3 for median) at runtime would
        silently degenerate trimmed_mean/median to a plain mean under
        exactly the conditions the rule is configured for — a fleet loss
        while an attacker is live.  Instead the fill HOLDS at the
        breakdown size: the statistic keeps >= 2k+1 contributions, and if
        fewer ELIGIBLE distinct ranks remain than that, fills top up with
        repeat contributions from eligible ranks (`_repeat_allowed`,
        counted in ``floor_relaxed_admits``) — the excluded rank still
        contributes nothing, and an unbounded stall waiting for a rejoin
        that may never come would be a self-inflicted denial of service.
        The episode is logged once and counted in
        ``fault_stats["breakdown_floor_stalls"]`` so a floor-bound PS is
        auditable; recovery/rejoin closes the episode."""
        if target >= self._min_fill:
            self._floor_binding = False
            return target
        if not self._floor_binding:
            self._floor_binding = True
            self._bump("breakdown_floor_stalls")
            print(f"async PS: {cause} would shrink the fill target to "
                  f"{target}, below aggregate={self.aggregate!r}'s "
                  f"breakdown size {self._min_fill} — holding the fill "
                  f"at {self._min_fill} (topping up with repeat "
                  f"contributions from eligible ranks while fewer than "
                  f"{self._min_fill} remain) instead of degenerating to "
                  f"a plain mean",
                  file=sys.stderr)
        return self._min_fill

    def _fill_target(self) -> int:
        """The number of contributions a fill aims for: the quota, minus
        quarantined ranks under rank-distinct fills (a quarantined rank
        cannot contribute, so waiting for its slot would deadlock — the
        same clamp-to-the-usable-fleet rule as transport eviction), but
        never below the reducer's breakdown size (`_shrink_floor`)."""
        target = self.quota
        if self._rank_distinct and self._scoreboard is not None:
            nq = len(self._scoreboard.quarantined_ranks())
            target = self._shrink_floor(max(1, target - nq), "quarantine")
        return target

    def _eligible_rank_count(self) -> int:
        """Ranks that can legitimately contribute to a fill right now
        (the TCP server overrides this with live-fleet accounting)."""
        n = self.num_workers
        if self._scoreboard is not None:
            n -= len(self._scoreboard.quarantined_ranks())
        return max(0, n)

    # pslint: only-called-by(_fill_gradients, _take_held)
    def _repeat_allowed(self) -> bool:
        """Rank-distinct fills admit a REPEAT contribution only while the
        breakdown floor is binding and fewer eligible distinct ranks
        remain than the floor requires: the statistic must keep its
        2k+1 contributions (no silent degeneration to a mean), but a
        fill that waits for a rank that cannot come is an unbounded
        stall.  A repeat from an eligible (non-quarantined, non-evicted)
        rank keeps the excluded rank at zero influence; the residual
        exposure — an undetected second attacker occupying two slots —
        is inherent once the fleet shrinks below 2k+1 distinct ranks,
        and the episode is fully audited (`breakdown_floor_stalls`,
        `floor_relaxed_admits`)."""
        return (self._rank_distinct and self._floor_binding
                and self._eligible_rank_count() < self._min_fill)

    # pslint: only-called-by(_fill_gradients)
    def _take_held(self, ranks) -> "tuple | None":
        """Pop the first held-over frame whose rank is not yet in this
        fill's contributor set (rank-distinct fills only); under a
        binding breakdown floor with too few eligible ranks, a repeat
        frame is eligible supply too."""
        for i, item in enumerate(self._held):
            if item[2] is None or item[2] not in ranks:
                return self._held.pop(i)
        if self._held and self._repeat_allowed():
            return self._held.pop(0)
        return None

    # pslint: only-called-by(_fill_gradients)
    def _hold_surplus(self, item) -> None:
        """Park a same-rank surplus frame for the next fill; a rank may
        hold at most 2 (beyond that the oldest intent is served — newer
        frames are dropped + counted, bounding memory against a flooding
        peer)."""
        rank = item[2]
        if sum(1 for it in self._held if it[2] == rank) >= 2:
            self._bump("surplus_dropped")
        else:
            self._held.append(item)

    # -- the shared fill-admission loop ---------------------------------------

    def _fleet_ranks(self) -> "set[int]":
        """The ranks a quorum-shortened fill may have left behind (they
        get late-fold credit when their frame lands).  The TCP server
        overrides this with its live-fleet accounting."""
        return set(range(self.num_workers))

    def _drop_before_admit(self, rank) -> bool:
        """Deployment-specific pre-admission drop, checked after the
        rank-distinct gate: the TCP server drops evicted ranks' in-flight
        frames here.  Returns True when the frame was dropped (and
        counted) and must not reach `_admit`."""
        return False

    def _check_fill_starved(self, n_filled: int, t0: float) -> None:
        """Deployment-specific starvation guard, invoked whenever a
        surplus frame is held back from a rank-distinct fill.  The
        in-process deployment refuses starving configurations eagerly in
        `run` (quota > num_workers), so this is a no-op; the TCP server
        overrides it to fail loudly when the connected fleet can never
        complete the fill."""

    def _at_fill_boundary(self) -> None:
        """Deployment-specific fill-boundary hook, invoked once at the
        top of every fill — BEFORE any gradient of the next update is
        consumed, so the parameter/optimizer state is exactly "N updates
        applied".  The in-process deployment needs nothing here; the TCP
        server overrides it to honor armed coordinated-snapshot cuts
        (SNAP markers): this boundary is the only point where a
        checkpoint is provably at a whole-update cut."""

    def _fill_gradients(self, receive, drain_nowait, *, current_version,
                        base_timeout: float = 0.5, on_consumed=None):
        """Receive gradients until the fill target is met — or, with a
        quorum configured, until quorum + deadline close the fill short.
        THE single fill-admission implementation: `AsyncPS.run` and
        `AsyncPSServer.serve` both drive this helper (PR 4 shipped the
        block duplicated between them and the two copies had already
        started drifting); only the receive primitives differ.

        ``receive(timeout) -> item | None`` — one bounded receive attempt;
        returns None on a quiet interval (the quorum/deadline logic here
        decides what that means) and raises when the fleet is gone.
        ``drain_nowait() -> item | None`` — non-blocking drain once the
        fill deadline has expired.  ``current_version()`` — the published
        parameter version, for staleness accounting.  ``on_consumed(rank)``
        — called for frames consumed off the queue but never applied
        (quarantined / rejected), so lockstep workers still see their ack.

        Items are ``(codes, version, rank, loss)`` — or, from the
        hierarchy's AGG forward frames, ``(codes, version, rank, loss,
        contrib)`` where ``contrib`` is the frame's contributor
        multiplicity (how many worker gradients the pre-reduced frame
        stands for; plain frames count 1).  Returns ``(codes_list,
        stalenesses, losses, ranks, contribs, fill_target, short)``.
        """
        from .transport import Deadline

        self._at_fill_boundary()
        # The quorum fill budget is a `Deadline` (the unified budget
        # type) armed at FILL START — what --fill-deadline's help has
        # always promised.
        fill_dl = Deadline(self._effective_deadline())
        t0 = time.perf_counter()
        codes_list: list = []
        stalenesses: list = []
        losses: list = []
        ranks: list = []
        contribs: list = []
        short = False
        while len(codes_list) < self._fill_target():
            # Held-over surplus frames (rank-distinct fills) are this
            # fill's first supply.
            item = self._take_held(ranks)
            quorum_met = (self.quorum is not None
                          and len(codes_list) >= min(self.quorum,
                                                     self._fill_target()))
            if item is not None:
                pass
            elif quorum_met and fill_dl.expired():
                # Deadline expired: drain what is already queued, then
                # proceed with the contributors we have — a slow rank
                # costs a deadline, not a stall.
                item = drain_nowait()
                if item is None:
                    short = True
                    break
            else:
                timeout = base_timeout
                if quorum_met:
                    timeout = fill_dl.timeout(floor=0.001,
                                              cap=base_timeout)
                item = receive(timeout)
                if item is None:
                    continue
            codes, version, rank, loss = item[:4]
            if (self._rank_distinct and rank is not None
                    and rank in ranks):
                # One contribution per rank per fill: a fast Byzantine
                # rank must not occupy two slots of a 3-slot fill and
                # out-vote the trim (robust reducers' breakdown point is
                # per contributor).  Exception: a binding breakdown floor
                # with too few eligible ranks tops fills up with repeats
                # rather than stalling unboundedly.
                if self._repeat_allowed():
                    self._bump("floor_relaxed_admits")
                else:
                    self._hold_surplus(item)
                    self._check_fill_starved(len(codes_list), t0)
                    continue
            if self._drop_before_admit(rank):
                continue
            # Clamp: a gradient computed against a NEWER version than the
            # serving counter (possible when a resumed PS restarts from a
            # checkpoint older than its crash point) is at worst fresh.
            # Unclamped, staleness=-1 would make the 1/(1+s) staleness
            # weight divide by zero and poison the params.
            staleness = max(0, current_version() - version)
            if (self._scoreboard is not None
                    and self._scoreboard.is_quarantined(rank)):
                # Quarantined rank: drop + count, but keep SCORING its
                # submissions so recovery stays observable (reversible,
                # like transport eviction).  The probe is an intentional
                # host sync of a jitted program prewarmed in
                # `compile_step` — a compile landing mid-fill stalls
                # threaded fleets.
                self._bump("quarantined_drops")
                self._scoreboard.observe(rank, float(self._norm_fn(codes)))
                if on_consumed is not None:
                    on_consumed(rank)
                continue
            rejected = self._admit(codes, staleness, loss)
            if rejected is not None:
                self._bump(rejected)
                # The grad WAS consumed (read off the queue) — only the
                # update never sees it.
                if on_consumed is not None:
                    on_consumed(rank)
                continue
            self._latency.observe(rank)
            if rank in self._missed_ranks:
                # A straggler's frame arriving after its fill closed
                # folds into THIS fill.
                self._missed_ranks.discard(rank)
                self._bump("late_folded")
            codes_list.append(codes)
            stalenesses.append(staleness)
            losses.append(loss)
            ranks.append(rank)
            contribs.append(float(item[4]) if len(item) > 4 else 1.0)
        fill_target = self._fill_target()
        if short:
            self._bump("quorum_fills")
            self._missed_ranks |= self._fleet_ranks() - set(ranks)
        return (codes_list, stalenesses, losses, ranks, contribs,
                fill_target, short)

    def _effective_deadline(self) -> float:
        """This fill's quorum deadline: the configured ``fill_deadline``
        — or, with ``adaptive_deadline`` on, the live fleet latency p95
        times a safety margin, CLAMPED to the configured value as a
        ceiling.  The configured deadline stops being a constant and
        becomes a budget: a fast fleet closes short fills at its own
        pace (counted in ``deadline_adapted``) while a uniformly-slow
        fleet uses the whole ceiling instead of tripping spurious quorum
        short-fills every update."""
        if not self.adaptive_deadline:
            return self.fill_deadline
        p95 = self._latency.fleet_p95()
        if p95 is None:
            return self.fill_deadline  # no history yet: the ceiling
        adapted = min(self.fill_deadline,
                      max(1.5 * p95, _ADAPTIVE_DEADLINE_FLOOR))
        if adapted < self.fill_deadline:
            self._bump("deadline_adapted")
        return adapted

    def _contrib_weights(self, stalenesses, ranks,
                         contribs=None) -> np.ndarray:
        """Per-contribution damping: staleness (1/(1+s)) composed with the
        scoreboard's suspect down-weighting, the heterogeneous-fleet
        latency decay (``latency_weighting``), and — for the hierarchy's
        pre-reduced AGG frames — the contributor multiplicity (a frame
        standing for 4 worker gradients weighs 4x a plain one, so a group
        that filled short moves the root pro-rata).  Applied BEFORE the
        robust statistic (documented composition order in `ops.robust`)."""
        w = np.ones(len(stalenesses), np.float32)
        if self.staleness_weighting:
            w *= 1.0 / (1.0 + np.asarray(stalenesses, np.float32))
        if self._scoreboard is not None:
            w *= np.asarray([self._scoreboard.weight(r) for r in ranks],
                            np.float32)
        if self.latency_weighting:
            lw = np.asarray([self._latency.speed_weight(r) for r in ranks],
                            np.float32)
            slowed = int(np.sum(lw < 1.0))
            if slowed:
                self._bump("latency_weighted", slowed)
                w *= lw
        if contribs is not None:
            c = np.asarray(contribs, np.float32)
            if not np.all(c == 1.0):
                w = w * c
        return w

    def _apply_weighted(self, stacked, stalenesses, ranks, data,
                        n_target: "int | None" = None, contribs=None):
        """Run the jitted reduce+update on already-stacked codes — the one
        aggregation entry point shared by the in-process loop and the TCP
        server so the two deployments cannot diverge.  ``n_target`` is the
        fill target the contribution count renormalizes to (the effective
        quota; defaults to the configured quota); ``contribs`` the
        per-frame contributor multiplicities from the fill."""
        n = len(stalenesses)
        n_target = self.quota if n_target is None else n_target
        w = self._contrib_weights(stalenesses, ranks, contribs)
        if self.staleness_weighting:
            data["mean_weight"] = float(w.mean())
        if self._itemwise:
            # Decode-then-reduce (robust reducers / anomaly scoring).
            clip = float("nan")
            if self.aggregate == "norm_clip" and self._norm_window:
                clip = float(np.median(np.asarray(self._norm_window)))
            new_params, new_state, info = self._apply_robust_fn(
                self.params, self.state, stacked, jnp.asarray(w),
                jnp.float32(n_target), jnp.float32(clip))
            self._post_apply_scoring(ranks, info)
            return new_params, new_state
        # Legacy linear fast path (fused decode_sum): staleness damping,
        # quarantine down-weights, and the quorum renormalization all fold
        # into the per-code scale.  The default configuration (mean, no
        # weighting, full fills) still compiles the weight-free program.
        renorm = float(n_target) / n
        if renorm != 1.0:
            w = w * np.float32(renorm)
        if self.staleness_weighting or not np.all(w == 1.0):
            return self._apply_fn(self.params, self.state, stacked,
                                  jnp.asarray(w))
        return self._apply_fn(self.params, self.state, stacked)

    def _post_apply_scoring(self, ranks, info) -> None:
        """Feed the robust apply's observability outputs (per-contribution
        norms, clip count) into the counters, the norm_clip rolling
        window, and the per-rank scoreboard."""
        norms = np.asarray(info["contrib_norms"], np.float64)
        clipped = int(info["clipped"])
        if clipped:
            self._bump("robust_clipped", clipped)
        if self.aggregate == "norm_clip":
            self._norm_window.extend(float(x) for x in norms)
        if self._scoreboard is not None:
            for r, nm in zip(ranks, norms):
                if r is not None:
                    self._scoreboard.observe(r, float(nm))

    def _base_fault_snapshot(self) -> "dict[str, Any]":
        """fault_stats + the admission-audit extras (per-rank latency,
        anomaly scores/states) every deployment reports."""
        snap = {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in self.fault_stats.items()}
        lat = self._latency.snapshot()
        if lat:
            snap["rank_latency"] = lat
        if self._scoreboard is not None:
            snap.update(self._scoreboard.snapshot())
        return snap

    # -- the async loop -------------------------------------------------------

    def _worker_loop(self, rank: int, device, batch_fn, published: _Published,
                     grad_queue: "queue.Queue", stop: threading.Event,
                     consumed: list[int], errors: list):
        try:
            with BatchDrawer(batch_fn, rank) as drawer:
                self._worker_body(rank, device, drawer, published,
                                  grad_queue, stop, consumed)
        except Exception as exc:  # propagate to the PS loop, don't die silent
            errors.append((rank, exc))

    def _worker_body(self, rank: int, device, drawer: BatchDrawer,
                     published: _Published, grad_queue: "queue.Queue",
                     stop: threading.Event, consumed: list[int]):
        it = 0
        plan = self.fault_plan
        fn = self._worker_fn
        if (plan is not None and self._worker_fn_byz is not None
                and getattr(plan, "byzantine_rank", None) == rank):
            fn = self._worker_fn_byz
        while not stop.is_set():
            with span("async.worker_iter", rank=rank, it=it) as iter_span:
                if plan is not None and plan.should_slow(rank):
                    # Deterministic straggler: this rank pays the configured
                    # delay before every gradient it computes.
                    time.sleep(plan.slow_delay_s)
                with span("async.await_batch", it=it) as await_span:
                    batch, ready = drawer.take(stop)
                await_span.set(ready=ready)
                if stop.is_set():
                    break
                # The "broadcast receive": params live on the PS device;
                # placing them on the worker device is the param push (ICI
                # transfer on hardware).  Committed placement makes jit run
                # on this device.  Read only once the batch is in hand: the
                # parameters do not age while the worker waits for data.
                with span("async.snapshot"):
                    params, version = published.snapshot()
                    params = jax.device_put(params, device)
                iter_span.set(version=version)
                with span("async.put_batch"):
                    batch = jax.device_put(batch, device)
                with span("async.grad"):
                    loss, codes = fn(params, batch)
                # The "send to rank 0": move only the *encoded* grads to the
                # PS device — the compressed payload is what rides the
                # interconnect.
                with span("async.send"):
                    codes = jax.device_put(codes, self.ps_device)
                # Bounded put = MPI-send backpressure: a worker whose grad the
                # PS hasn't absorbed yet blocks here instead of racing ahead,
                # which bounds staleness at ~queue_capacity/quota updates.
                # (An unbounded queue lets staleness grow linearly and
                # training diverges.)
                item = (codes, version, rank, loss)
                extra_flood, extra_burst = (
                    plan.overload_extras(rank, it) if plan is not None
                    else (0, 0))
                retries = 0
                with span("async.enqueue") as enqueue_span:
                    for i in range(1 + extra_flood + extra_burst):
                        placed = False
                        while not stop.is_set():
                            try:
                                grad_queue.put(item, timeout=0.05)
                                placed = True
                                break
                            except queue.Full:
                                retries += 1
                        if i >= 1 and placed:
                            # Overload injectors (flood_rank / burst_at):
                            # the same gradient enqueued again as genuine
                            # extra supply.  Counted under the injector lock
                            # — worker threads bump concurrently (every rank
                            # bursts at the same iteration), and the base
                            # `_bump` is deliberately lock-free for the
                            # single-consumer serve loop.
                            key = ("flood_injected" if i <= extra_flood
                                   else "burst_injected")
                            with self._overload_lock:
                                self.fault_stats[key] += 1
                enqueue_span.set(retries=retries)
                it += 1
                if self._lockstep:
                    while consumed[rank] < it and not stop.is_set():
                        time.sleep(0)

    def run(self, batch_fn: Callable[[int, int], Any], steps: int,
            log_every: int = 0) -> dict[str, Any]:
        """Run ``steps`` PS updates; returns the training history.

        History keys: ``losses`` (mean worker loss per update), ``staleness``
        (mean gradient staleness per update), ``versions``, ``grads_consumed``,
        ``wall_time``, plus per-update timing dicts in ``self.timings``.
        """
        if self._worker_fn is None:
            raise NotCompiledError("call compile_step(loss_fn) before run()")
        if self._lockstep and self.quota > self.num_workers:
            # Each lockstep worker holds exactly one outstanding grad, so a
            # quota above the worker count can never fill — hard deadlock.
            raise ValueError(
                f"lockstep mode needs quota <= num_workers "
                f"({self.quota} > {self.num_workers})")
        if self._rank_distinct and self.quota > self.num_workers:
            # Rank-distinct fills can never gather more contributions
            # than there are ranks — hard error, not a hang.
            raise ValueError(
                f"aggregate={self.aggregate!r} admits one contribution "
                f"per rank per fill: quota {self.quota} needs at least "
                f"that many workers (have {self.num_workers})")

        published = _Published(self.params)
        # Capacity: one in-flight grad per worker beyond what an update
        # drains — or the configured credit window, whichever is larger
        # (the bounded queue IS the in-process flow-control mechanism:
        # its capacity bounds staleness, exactly what the TCP credit
        # window does on the wire).
        grad_queue: "queue.Queue" = queue.Queue(
            maxsize=max(self.quota, self.num_workers, self.credit_window))
        stop = threading.Event()
        consumed = [0] * self.num_workers
        errors: list = []

        workers = [
            threading.Thread(
                target=self._worker_loop,
                args=(r, d, batch_fn, published, grad_queue, stop, consumed,
                      errors),
                daemon=True, name=f"async-ps-worker-{r}")
            for r, d in enumerate(self.worker_devices)
        ]
        for w in workers:
            w.start()

        def raise_worker_error():
            rank, exc = errors[0]
            raise WorkerFailedError(f"async worker {rank} failed") from exc

        def receive(timeout: float = 0.5):
            """One bounded receive attempt with worker-liveness checks: a
            dead worker must surface as an error, never as a hang — and
            never be masked by surviving workers keeping the queue busy.
            Returns None on timeout (the shared fill loop's
            quorum/deadline logic decides what a quiet queue means)."""
            if errors:
                raise_worker_error()
            try:
                item = grad_queue.get(timeout=timeout)
            except queue.Empty:
                if not any(w.is_alive() for w in workers):
                    raise FleetDeadError(
                        "all async workers exited without producing "
                        "gradients")
                return None
            plan = self.fault_plan
            if plan is not None and plan.slow_consumer > 0:
                # Overload injector: the PS consumes slower than the
                # workers produce, so the bounded queue's backpressure
                # (and the counters that audit it) actually engages.
                time.sleep(plan.slow_consumer)
                self._bump("slow_consumed")
            return item

        def drain_nowait():
            try:
                return grad_queue.get_nowait()
            except queue.Empty:
                return None

        def ack_consumed(rank):
            if rank is not None:
                consumed[rank] += 1

        history: dict[str, Any] = {
            "losses": [], "staleness": [], "versions": [],
            "contributors": [], "grads_consumed": 0,
        }
        t_start = time.perf_counter()
        try:
            for update in range(steps):
                with span("async.update", update=update):
                    if (self.fault_plan is not None
                            and self.fault_plan.should_kill_ps(update)):
                        from .utils.faults import SimulatedCrash
                        raise SimulatedCrash(
                            f"FaultPlan: PS killed before update {update}")
                    data: dict[str, float] = {}
                    # --- receive until quota (the ANY_SOURCE loop), or until
                    # quorum + deadline close the fill short — the fill loop
                    # itself is `_fill_gradients`, shared with the TCP server.
                    with span("async.fill") as fill:
                        (batch_codes, stalenesses, losses, ranks, contribs,
                         fill_target, _short) = self._fill_gradients(
                            receive, drain_nowait,
                            current_version=lambda: published.version,
                            on_consumed=ack_consumed)
                    mean_stale = float(np.mean(stalenesses))
                    fill.set(n=len(batch_codes), ranks=list(ranks),
                             staleness=mean_stale)

                    # --- reduce + step (on the PS device) ------------------
                    with span("async.stack") as stack:
                        stacked = _stack_codes(*batch_codes)
                    with span("async.apply") as apply:
                        new_params, new_state = self._apply_weighted(
                            stacked, stalenesses, ranks, data,
                            n_target=fill_target, contribs=contribs)

                    # --- publish (the inconsistent-read broadcast) ---------
                    with span("async.publish") as publish:
                        self.params, self.state = new_params, new_state
                        published.publish(new_params)
                        # Acknowledge consumption only after the publish, so
                        # lockstep workers always see the post-update params.
                        for r in ranks:
                            consumed[r] += 1
                    publish.set(version=published.version)

                    with span("async.read_loss"):
                        mean_loss = float(np.mean([float(l) for l in losses]))
                    # The per-update dict is a second view of the same clock
                    # reads, not a second measurement.
                    data["comm_wait"] = fill.duration
                    data["optim_step_time"] = stack.duration + apply.duration
                    data["isend_time"] = publish.duration
                    data["msg_bytes"] = float(bytes_of(batch_codes[0]))
                    history["losses"].append(mean_loss)
                    history["staleness"].append(mean_stale)
                    history["versions"].append(published.version)
                    history["contributors"].append(list(ranks))
                    history["grads_consumed"] += len(batch_codes)
                    self.timings.append(data)
                    if log_every and (update + 1) % log_every == 0:
                        print(f"async update {update + 1:5d}  "
                              f"loss {mean_loss:.4f}"
                              f"  staleness {mean_stale:.2f}")
        finally:
            stop.set()
            for w in workers:
                w.join(timeout=5.0)
            # A late failure must not vanish with the threads — but never
            # mask an exception already propagating out of the try block.
            if errors and sys.exc_info()[0] is None:
                raise_worker_error()
            # Drop in-flight grads left behind: the run is over.
            while not grad_queue.empty():
                try:
                    grad_queue.get_nowait()
                except queue.Empty:  # pragma: no cover
                    break
        history["wall_time"] = time.perf_counter() - t_start
        history["fault_stats"] = self._base_fault_snapshot()
        return history

    # -- checkpoint / resume --------------------------------------------------

    def state_dict(self) -> dict:
        """Host-side snapshot (see `MPI_PS.state_dict`); async PS carries no
        aux state, so the entry is an empty tree for format compatibility."""
        from .optim.schedules import hyper_for_checkpoint
        host = lambda t: jax.tree.map(np.asarray, t)
        return {
            "optim": self.optim,
            "hyper": hyper_for_checkpoint(self.hyper),
            "params": host(self.params),
            "state": host(self.state),
            "aux": {},
        }

    def load_state_dict(self, sd: dict) -> None:
        if sd["optim"] != self.optim:
            raise ValueError(
                f"checkpoint is for optim={sd['optim']!r}, this is {self.optim!r}")
        if set(sd["params"]) != set(self.params):
            missing = set(self.params) ^ set(sd["params"])
            raise ValueError(f"parameter name mismatch: {sorted(missing)}")
        from .optim.schedules import hyper_from_checkpoint
        place = lambda x: jax.device_put(jnp.asarray(x), self.ps_device)
        self.hyper = hyper_from_checkpoint(sd["hyper"], self.hyper)
        self.params = OrderedDict(
            (n, place(sd["params"][n])) for n in self.params)
        self.state = OrderedDict(
            (n, jax.tree.map(place, sd["state"][n])) for n in self.params)
        # Rebind the jitted apply fn if hyper changed shape of the closure.
        if self._loss_fn is not None:
            self.compile_step(self._loss_fn)

    # -- conveniences ---------------------------------------------------------

    def named_parameters(self):
        return list(self.params.items())

    def print_summary(self):
        from .utils.timing import print_summary
        print_summary(self.timings)


class AsyncSGD(AsyncPS):
    """Async PS with the torch-parity SGD rule (`/root/reference/ps.py:195-214`)."""

    def __init__(self, named_params, **kwargs):
        kwargs["optim"] = "sgd"
        super().__init__(named_params, **kwargs)


class AsyncAdam(AsyncPS):
    """Async PS with the torch-parity Adam rule (`/root/reference/ps.py:217-261`)."""

    def __init__(self, named_params, **kwargs):
        kwargs["optim"] = "adam"
        super().__init__(named_params, **kwargs)


def dataset_batch_fn(x: np.ndarray, y: np.ndarray, batch_size: int,
                     *, seed: int = 0) -> Callable[[int, int], dict]:
    """Build a ``batch_fn`` sampling random minibatches per (rank, it) — each
    worker draws from its own deterministic stream, the analogue of per-rank
    data shards under ``mpirun``.  `AsyncPS.run` calls it in order of ``it``,
    once each, on a helper thread of the rank's worker, up to two iterations
    ahead of use; the sample depends on (seed, rank, it) alone."""
    n = x.shape[0]

    def batch_fn(rank: int, it: int) -> dict:
        # SeedSequence mixes the key entropy properly: no 2**32 overflow for
        # large seeds and no (rank, it) stream collisions.
        rng = np.random.default_rng(np.random.SeedSequence([seed, rank, it]))
        idx = rng.integers(0, n, size=batch_size)
        return {"x": x[idx], "y": y[idx]}

    return batch_fn


def lm_batch_fn(toks: np.ndarray, batch_size: int,
                *, seed: int = 0) -> Callable[[int, int], dict]:
    """`dataset_batch_fn` for token rows ``[n, S+1]``: each worker draws its
    own deterministic row sample and builds the {tokens, targets, positions}
    dict (`models.transformer.lm_batch`).  Called as `dataset_batch_fn` is:
    in order of ``it``, once each, on a helper thread of the rank's worker,
    up to two iterations ahead of use."""
    from .models.transformer import lm_batch

    n = toks.shape[0]

    def batch_fn(rank: int, it: int) -> dict:
        rng = np.random.default_rng(np.random.SeedSequence([seed, rank, it]))
        idx = rng.integers(0, n, size=batch_size)
        return lm_batch(toks[idx])

    return batch_fn
