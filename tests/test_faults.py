"""Fault tolerance under deterministic chaos (`utils.faults.FaultPlan`).

The oracles mirror the failure model the subsystem claims to survive:
a corrupted wire frame costs one gradient (counted) and nothing else; a
dead worker is evicted and the quota shrinks so the run still completes;
an injected NaN gradient is quarantined, never applied; a killed PS
resumes from its auto-checkpoint while surviving workers reconnect with
backoff.  Every scenario is seeded and in-process (worker threads, not
subprocesses) so the tier-1 lane stays fast."""

import socket
import threading

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.async_ps import dataset_batch_fn
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import (AsyncPSWorker,
                                                AsyncSGDServer,
                                                FrameCRCError, _frame_header,
                                                _recv_frame, _send_frame)
from pytorch_ps_mpi_tpu.utils.faults import (FaultPlan, SimulatedCrash,
                                             WireMangler, poison_nonfinite)


def _teacher():
    rng = np.random.RandomState(7)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _worker_thread(port, results, key, *, seed=3, batch=64, **kw):
    """Run an AsyncPSWorker in a daemon thread; outcome lands in
    ``results[key]`` (pushed count, reconnects, or the exception)."""
    x, y = _teacher()

    def go():
        try:
            w = AsyncPSWorker("127.0.0.1", port, **kw)
            pushed = w.run(mlp_loss_fn,
                           dataset_batch_fn(x, y, batch, seed=seed))
            results[key] = {"pushed": pushed, "reconnects": w.reconnects,
                            "rank": w.rank}
        except BaseException as exc:  # noqa: BLE001 - recorded for asserts
            results[key] = {"error": exc}

    t = threading.Thread(target=go, daemon=True, name=f"chaos-worker-{key}")
    t.start()
    return t


def _server(quota=1, seed=0, **kw):
    params = init_mlp(np.random.RandomState(seed), sizes=(16, 32, 4))
    srv = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                         quota=quota, **kw)
    srv.compile_step(mlp_loss_fn)
    return srv


# ---------------------------------------------------------------------------
# FaultPlan unit behavior
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic_and_json_roundtrip():
    plan = FaultPlan(seed=11, kill_worker_at={1: 3}, kill_ps_at=5,
                     nonfinite_at={(0, 2)}, corrupt_p=0.3, dup_every=4,
                     delay_p=0.1, delay_s=0.0)
    clone = FaultPlan.from_json(plan.to_json())
    assert clone == plan

    wire = _frame_header(b"x" * 64) + b"x" * 64
    seq_a = [plan.wire_mangler(0)(wire) for _ in range(32)]
    seq_b = [clone.wire_mangler(0)(wire) for _ in range(32)]
    assert seq_a == seq_b  # same seed+rank => identical fault schedule
    # A different rank draws a different (but still deterministic) stream.
    assert [plan.wire_mangler(1)(wire) for _ in range(32)] \
        == [plan.wire_mangler(1)(wire) for _ in range(32)]

    with pytest.raises(ValueError, match="unknown FaultPlan fields"):
        FaultPlan.from_json('{"no_such_knob": 1}')


def test_wire_mangler_corruption_is_payload_local():
    """A corrupted frame must still parse as a frame (length intact) and
    fail its CRC — the contract that keeps the receiver's stream aligned."""
    payload = bytes(range(256)) * 4
    wire = _frame_header(payload) + payload
    mangler = WireMangler(FaultPlan(seed=3, corrupt_every=1), rank=0)
    for _ in range(8):
        (mangled,), close = mangler(wire)
        assert not close
        assert len(mangled) == len(wire)
        assert mangled[:8] == wire[:8]  # header untouched
        assert mangled != wire

    a, b = socket.socketpair()
    try:
        a.sendall(mangled)
        with pytest.raises(FrameCRCError):
            _recv_frame(b)
    finally:
        a.close()
        b.close()


def test_wire_mangler_drop_dup_truncate():
    payload = b"payload-bytes"
    wire = _frame_header(payload) + payload
    assert WireMangler(FaultPlan(drop_every=1), 0)(wire) == ([], False)
    frames, close = WireMangler(FaultPlan(dup_every=1), 0)(wire)
    assert frames == [wire, wire] and not close
    (prefix,), close = WireMangler(FaultPlan(truncate_every=1), 0)(wire)
    assert close and 0 < len(prefix) < len(wire)


def test_poison_nonfinite_hits_first_float_leaf():
    tree = {"a": np.arange(4, dtype=np.int32),
            "b": np.ones(3, np.float32), "c": np.ones(2, np.float32)}
    out = poison_nonfinite(tree)
    assert np.isnan(out["b"][0]) and np.isfinite(out["b"][1:]).all()
    assert np.isfinite(out["c"]).all()
    np.testing.assert_array_equal(out["a"], tree["a"])
    assert np.isfinite(tree["b"]).all()  # input untouched (copy semantics)


# ---------------------------------------------------------------------------
# Admission control (bounded staleness + non-finite quarantine)
# ---------------------------------------------------------------------------

def test_admit_bounded_staleness_and_nonfinite():
    srv = _server(max_staleness=2, skip_nonfinite=True)
    try:
        codes = {n: np.asarray(p) for n, p in srv.params.items()}
        assert srv._admit(codes, 2, 0.5) is None
        assert srv._admit(codes, 3, 0.5) == "stale_dropped"
        assert srv._admit(codes, 0, float("nan")) == "nonfinite_dropped"
        bad = poison_nonfinite(codes)
        assert srv._admit(bad, 0, 0.5) == "nonfinite_dropped"
        # Quarantine gates are opt-in: a permissive server admits all.
        srv2 = _server()
        try:
            assert srv2._admit(bad, 99, float("nan")) is None
        finally:
            srv2.close()
    finally:
        srv.close()

    with pytest.raises(ValueError, match="max_staleness"):
        _server(max_staleness=-1)


def test_nonfinite_injection_quarantined_end_to_end():
    """A FaultPlan-poisoned gradient is dropped+counted by the PS and the
    run completes with finite parameters."""
    srv = _server(skip_nonfinite=True)
    results = {}
    t = _worker_thread(srv.address[1], results, "w0",
                       fault_plan=FaultPlan(nonfinite_at={(0, 1), (0, 3)}))
    steps = 6
    hist = srv.serve(steps=steps, idle_timeout=60.0)
    t.join(timeout=60)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert len(hist["losses"]) == steps
    assert hist["fault_stats"]["nonfinite_dropped"] >= 2
    for n, p in srv.params.items():
        assert np.isfinite(np.asarray(p)).all(), n


# ---------------------------------------------------------------------------
# Wire chaos against a live PS
# ---------------------------------------------------------------------------

def test_corrupt_frames_quarantined_run_completes():
    """Every other GRAD frame bit-flipped on the wire: the PS drops each
    (counted), keeps the connection, and the run still completes."""
    srv = _server()
    results = {}
    t = _worker_thread(srv.address[1], results, "w0",
                       fault_plan=FaultPlan(seed=5, corrupt_every=2))
    steps = 6
    hist = srv.serve(steps=steps, idle_timeout=60.0)
    t.join(timeout=60)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert len(hist["losses"]) == steps
    assert hist["grads_consumed"] == steps
    assert hist["fault_stats"]["crc_dropped"] >= 2
    # Dropped frames cost gradients, not the connection.
    assert hist["fault_stats"]["conn_drops"] == 0


def test_duplicate_frames_deduplicated_delays_harmless():
    """A wire-duplicated GRAD re-presents an already-seen per-rank seq: the
    PS drops the repeat (counted in ``duplicate_dropped``) instead of
    applying the same gradient twice as two fresh contributions — the
    pre-v4 behavior this test used to codify.  Delays only slow things
    down."""
    srv = _server()
    results = {}
    t = _worker_thread(srv.address[1], results, "w0",
                       fault_plan=FaultPlan(seed=6, dup_every=2,
                                            delay_every=3, delay_s=0.01))
    steps = 6
    hist = srv.serve(steps=steps, idle_timeout=60.0)
    t.join(timeout=60)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert hist["grads_consumed"] == steps
    # dup_every=2 fires on seq 0, 2, 4, ... — at least two repeats landed
    # and every one was dropped, so the PS consumed exactly one gradient
    # per worker push.
    assert hist["fault_stats"]["duplicate_dropped"] >= 2
    assert results["w0"]["pushed"] >= steps
    # Per-rank submission latency (EMA + p50/p95) is on the audit record.
    lat = hist["fault_stats"].get("rank_latency", {})
    assert 0 in lat and lat[0]["n"] >= 1 and lat[0]["p95_s"] >= 0.0


def test_truncated_frame_triggers_reconnect_and_recovery():
    """A frame truncated mid-send (the real crash shape) kills that
    connection; the worker redials with backoff, re-presents its rank, and
    finishes the run — fault_stats shows the reconnect, not an eviction."""
    srv = _server()
    results = {}
    t = _worker_thread(srv.address[1], results, "w0",
                       fault_plan=FaultPlan(seed=7, truncate_every=4),
                       reconnect_retries=8, backoff_base=0.05,
                       backoff_max=0.3)
    steps = 8
    hist = srv.serve(steps=steps, idle_timeout=60.0,
                     dead_conn_grace=5.0)
    t.join(timeout=90)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert len(hist["losses"]) == steps
    assert results["w0"]["reconnects"] >= 1
    assert hist["fault_stats"]["reconnects"] >= 1
    # Reconnects re-book the SAME rank: one worker ever, no rank churn.
    assert hist["fault_stats"]["workers_seen"] == 1


# ---------------------------------------------------------------------------
# Worker death -> eviction -> quota shrink
# ---------------------------------------------------------------------------

def test_dead_worker_evicted_quota_shrinks_run_completes():
    import time as _time

    srv = _server(quota=2)
    steps = 12
    served = {}
    st = threading.Thread(
        target=lambda: served.update(h=srv.serve(
            steps=steps, idle_timeout=60.0,
            eviction_timeout=10.0, dead_conn_grace=0.1)),
        daemon=True)
    st.start()
    # Sequential construction pins the ranks: the victim is rank 1.
    w0 = AsyncPSWorker("127.0.0.1", srv.address[1])
    w1 = AsyncPSWorker("127.0.0.1", srv.address[1],
                       fault_plan=FaultPlan(kill_worker_at={1: 3}))
    assert (w0.rank, w1.rank) == (0, 1)
    x, y = _teacher()
    results = {}

    def go(w, key, seed, slow=False):
        # The survivor is throttled so post-death serving always spans
        # many dead_conn_grace windows: without it, a warm cache lets the
        # remaining updates finish inside the grace and eviction — the
        # thing under test — never gets its chance (observed flake).
        inner = dataset_batch_fn(x, y, 64, seed=seed)

        def batch_fn(rank, it):
            if slow:
                _time.sleep(0.06)
            return inner(rank, it)

        try:
            results[key] = {"pushed": w.run(mlp_loss_fn, batch_fn)}
        except BaseException as exc:  # noqa: BLE001 - asserted below
            results[key] = {"error": exc}

    t0 = threading.Thread(target=go, args=(w0, "w0", 3, True), daemon=True)
    t1 = threading.Thread(target=go, args=(w1, "w1", 4), daemon=True)
    t0.start()
    t1.start()
    st.join(timeout=120)
    assert not st.is_alive()
    t0.join(timeout=60)
    t1.join(timeout=60)
    assert not t0.is_alive() and not t1.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert isinstance(results["w1"].get("error"), SimulatedCrash)

    hist = served["h"]
    fs = hist["fault_stats"]
    assert fs["evictions"] == 1
    assert fs["evicted_ranks"] == [1]
    assert fs["live_ranks"] == [0]
    assert fs["workers_seen"] == 2
    # Every update completed despite the mid-run death: the quota clamp
    # let post-eviction fills finish with the survivor alone.
    assert len(hist["losses"]) == steps
    assert hist["grads_consumed"] <= steps * 2


def test_wire_duplicate_frame_dropped_by_seq():
    """The satellite fix made concrete at the socket level: the SAME GRAD
    frame sent twice (what WireMangler `dup` puts on the wire) is applied
    once — the repeat is dropped by its per-rank seq and counted."""
    import time as _time

    from pytorch_ps_mpi_tpu.multihost_async import _BKT, _F64, _U64
    from pytorch_ps_mpi_tpu.native import serializer

    srv = _server()
    served = {}
    st = threading.Thread(
        target=lambda: served.update(h=srv.serve(steps=1,
                                                 idle_timeout=30.0)),
        daemon=True)
    st.start()
    sock = socket.create_connection(("127.0.0.1", srv.address[1]))
    try:
        _send_frame(sock, b"HELO\x00")
        _recv_frame(sock)  # PSA reply
        from collections import OrderedDict
        codes = OrderedDict((n, np.asarray(p))
                            for n, p in srv.params.items())
        blob = serializer.dumps(codes, level=0)
        frame = (b"GRAD" + _BKT.pack(0, 1) + _U64.pack(7)
                 + _U64.pack(0) + _F64.pack(0.5) + blob)
        _send_frame(sock, frame)
        _send_frame(sock, frame)  # the wire duplicate: identical seq
        st.join(timeout=60)
        assert not st.is_alive()
        deadline = _time.monotonic() + 10
        while (_time.monotonic() < deadline
               and srv.fault_stats["duplicate_dropped"] < 1):
            _time.sleep(0.02)  # conn thread may lag the serve loop
        assert srv.fault_stats["duplicate_dropped"] == 1
        assert served["h"]["grads_consumed"] == 1
    finally:
        sock.close()
        srv.close()


def test_quorum_eviction_interplay_and_rejoin():
    """Quorum x eviction: an evicted rank's in-flight gradient (enqueued
    before the eviction landed) must not satisfy a fill or a quorum; a
    rejoining rank re-enters the contributor set cleanly."""
    srv = _server(quota=2, quorum=1, fill_deadline=0.02)
    try:
        codes = {n: np.asarray(p) for n, p in srv.params.items()}
        assert srv._register_conn(None) == 0
        assert srv._register_conn(None) == 1
        # Rank 1's gradient is already in flight when it goes silent past
        # the eviction timeout.
        srv._net_queue.put_nowait((codes, 0, 1, 0.5))
        srv._net_queue.put_nowait((codes, 0, 0, 0.5))
        srv._last_seen[1] -= 100.0
        hist = srv.serve(steps=1, idle_timeout=20.0,
                         eviction_timeout=30.0, dead_conn_grace=2.0)
        fs = hist["fault_stats"]
        assert fs["evictions"] == 1
        assert fs["evicted_dropped"] == 1  # the in-flight grad was refused
        assert hist["contributors"] == [[0]]  # only the live rank counted

        # Rejoin: live traffic re-admits the rank (the PR 2 contract); its
        # fresh gradient then satisfies the next fill's quorum.
        srv._mark_alive(1)
        srv._net_queue.put_nowait((codes, 1, 1, 0.4))
        hist2 = srv.serve(steps=1, idle_timeout=20.0, start_step=1)
        assert 1 in hist2["contributors"][0]
        assert hist2["fault_stats"]["evicted_dropped"] == 1  # no new drops
    finally:
        srv.close()


def test_rank_distinct_fill_starvation_fails_loudly():
    """A rank-distinct reducer with no quorum and fewer distinct workers
    than the quota can never complete a fill — and because the steady
    surplus traffic keeps resetting the idle deadline, the generic
    "fleet dead" error never fires.  The fill-starvation guard must turn
    that livelock into a RuntimeError naming the cure.  (The in-process
    path refuses quota > num_workers eagerly; the server only learns the
    fleet size at runtime.)"""
    import queue as _queue
    import time as _time

    srv = _server(quota=3, aggregate="median")
    try:
        codes = {n: np.asarray(p) for n, p in srv.params.items()}
        for r in (0, 1):
            assert srv._register_conn(None) == r
        stop = threading.Event()

        def feed():
            while not stop.is_set():
                for r in (0, 1):
                    try:
                        srv._net_queue.put((codes, 0, r, 0.5),
                                           timeout=0.05)
                    except _queue.Full:
                        pass
                _time.sleep(0.01)

        t = threading.Thread(target=feed, daemon=True)
        t.start()
        try:
            with pytest.raises(RuntimeError, match="fill starved"):
                srv.serve(steps=1, idle_timeout=0.5)
        finally:
            stop.set()
            t.join(timeout=5)
    finally:
        srv.close()


def test_eviction_holds_breakdown_floor_for_trimmed_mean():
    """Transport eviction must not shrink a trimmed_mean fill below its
    2*trim_k+1 breakdown size: `_effective_quota` holds the fill there
    (counted in ``breakdown_floor_stalls``) instead of handing a live
    attacker a sub-breakdown fill where the trim degenerates to a plain
    mean.  Under "mean" (breakdown size 1) the same eviction legitimately
    shrinks the fill so the run completes on survivors."""
    srv = _server(quota=3, aggregate="trimmed_mean")
    try:
        for r in range(3):
            assert srv._register_conn(None) == r
        srv._last_seen[2] -= 100.0
        srv._evict_dead(30.0, 5.0)
        assert 2 in srv._evicted
        assert srv._effective_quota() == 3  # held, NOT 2
        assert srv.fault_stats["breakdown_floor_stalls"] == 1
        # Only 2 live ranks remain for a 3-contribution floor: fills may
        # top up with repeat contributions from the survivors instead of
        # stalling until a rejoin that may never come.
        assert srv._eligible_rank_count() == 2
        assert srv._repeat_allowed()
        # Rejoin releases the floor episode (and the relaxation with it).
        srv._mark_alive(2)
        assert srv._effective_quota() == 3
        assert not srv._floor_binding
        assert not srv._repeat_allowed()
    finally:
        srv.close()

    srv2 = _server(quota=3)  # aggregate="mean"
    try:
        for r in range(3):
            srv2._register_conn(None)
        srv2._last_seen[2] -= 100.0
        srv2._evict_dead(30.0, 5.0)
        assert srv2._effective_quota() == 2  # clamp-to-survivors stands
        assert srv2.fault_stats["breakdown_floor_stalls"] == 0
    finally:
        srv2.close()


# ---------------------------------------------------------------------------
# PS crash -> checkpoint resume -> workers reconnect
# ---------------------------------------------------------------------------

def test_ps_crash_resume_workers_reconnect(tmp_path):
    ckpt = tmp_path / "chaos.psz"
    srv1 = _server(fault_plan=FaultPlan(kill_ps_at=4))
    port = srv1.address[1]
    results = {}
    t = _worker_thread(port, results, "w0",
                       reconnect_retries=20, backoff_base=0.05,
                       backoff_max=0.5, heartbeat_interval=0.5)
    with pytest.raises(SimulatedCrash):
        srv1.serve(steps=10, idle_timeout=60.0,
                   checkpoint_path=str(ckpt), checkpoint_every=2)
    # Crash landed after the step-4 auto-checkpoint, before update 4 ran.
    assert ckpt.exists()

    # Restart on the SAME port (what a supervised relaunch does), restore
    # the snapshot, serve the remaining updates.
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    srv2 = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                          quota=1, port=port)
    srv2.compile_step(mlp_loss_fn)
    start = srv2.resume_from(str(ckpt))
    assert start == 4
    assert srv2._served_version == 4  # staleness accounting is continuous
    hist = srv2.serve(steps=10 - start, idle_timeout=60.0,
                      start_step=start)
    t.join(timeout=90)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert len(hist["losses"]) == 10 - start
    # The surviving worker rode its backoff across the restart gap.
    assert results["w0"]["reconnects"] >= 1
    assert hist["fault_stats"]["reconnects"] >= 1
    for n, p in srv2.params.items():
        assert np.isfinite(np.asarray(p)).all(), n


# ---------------------------------------------------------------------------
# Counter plumbing (satellites)
# ---------------------------------------------------------------------------

def test_evicted_rank_readmitted_when_traffic_resumes():
    """A worker paused past the eviction timeout whose connection never
    died (SIGSTOP then resume) sends no re-HELO — resumed BEAT/GRAD/PULL
    traffic itself must reverse the eviction, or the quota stays clamped
    forever and a healthy worker is reported dead."""
    srv = _server(quota=2)
    try:
        srv._register_conn(None)
        srv._register_conn(None)
        # Rank 1 goes silent past the timeout (connection still counted).
        srv._last_seen[1] -= 100.0
        srv._evict_dead(eviction_timeout=30.0, dead_conn_grace=2.0)
        assert srv._evicted == {1} and srv._live_ranks == {0}
        assert srv._effective_quota() == 1
        # Its next frame re-admits it and the quota grows back.
        srv._mark_alive(1)
        assert srv._evicted == set() and srv._live_ranks == {0, 1}
        assert srv._effective_quota() == 2
        # The eviction remains on the cumulative record.
        assert srv.fault_stats["evictions"] == 1
    finally:
        srv.close()


def test_stale_clamp_protects_staleness_weighting():
    """A gradient version NEWER than the serving counter (resume from a
    checkpoint older than the crash point) must clamp to staleness 0 —
    unclamped, the 1/(1+s) weight divides by zero at s=-1."""
    srv = _server(staleness_weighting=True)
    results = {}
    # Pretend the PS resumed from an old snapshot: workers pull version 0
    # (fresh server) but the restored counter would normally be higher;
    # simulate the inverse — push a future-dated gradient directly.
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu.multihost_async import _BKT, _F64, _U64
    from pytorch_ps_mpi_tpu.native import serializer

    # OrderedDict: a plain dict has a different treedef and would be
    # quarantined by _validate_codes instead of reaching the clamp.
    codes = OrderedDict((n, np.asarray(p)) for n, p in srv.params.items())
    blob = serializer.dumps(codes, level=0)
    t = _worker_thread(srv.address[1], results, "w0")
    # Inject one future-dated gradient via a raw authenticated peer.
    sock = socket.create_connection(("127.0.0.1", srv.address[1]))
    served = {}
    st = threading.Thread(
        target=lambda: served.update(h=srv.serve(steps=4,
                                                 idle_timeout=60.0)),
        daemon=True)
    st.start()
    _send_frame(sock, b"HELO\x00")
    _recv_frame(sock)  # PSA reply
    # v11 GRAD layout: bucket | n_buckets | seq | version | loss | blob.
    _send_frame(sock, b"GRAD" + _BKT.pack(0, 1) + _U64.pack(0)
                + _U64.pack(10 ** 6) + _F64.pack(0.5) + blob)
    st.join(timeout=120)
    assert not st.is_alive()
    sock.close()
    t.join(timeout=60)
    hist = served["h"]
    assert all(s >= 0 for s in hist["staleness"])  # clamped, not negative
    for n, p in srv.params.items():
        assert np.isfinite(np.asarray(p)).all(), n


def test_async_ps_in_process_kill_hook():
    """The single-controller AsyncPS honors kill_ps_at too (reachable via
    `--async-ps --chaos`), cleaning its worker threads up on the way out."""
    from pytorch_ps_mpi_tpu.async_ps import AsyncSGD

    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    opt = AsyncSGD(list(params.items()), lr=0.05, quota=1,
                   fault_plan=FaultPlan(kill_ps_at=2))
    opt.compile_step(mlp_loss_fn)
    x, y = _teacher()
    with pytest.raises(SimulatedCrash, match="update 2"):
        opt.run(dataset_batch_fn(x, y, 64, seed=1), steps=5)


def test_kill_ps_does_not_refire_on_resume():
    """A supervisor relaunching the IDENTICAL command line (same --chaos
    plan) with --resume lands exactly at the kill step; re-firing there
    would be an infinite crash loop.  The kill means 'die once AT step k',
    not 'die on every incarnation that reaches k'."""
    plan = FaultPlan(kill_ps_at=3)
    srv = _server(fault_plan=plan)
    results = {}
    t = _worker_thread(srv.address[1], results, "w0",
                       reconnect_retries=20, backoff_base=0.05,
                       backoff_max=0.4)
    with pytest.raises(SimulatedCrash):
        srv.serve(steps=6, idle_timeout=60.0)
    # Relaunch on the same port with the SAME plan, resumed at the kill
    # step: serves the remaining updates instead of dying again.
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    srv2 = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                          quota=1, port=srv.address[1], fault_plan=plan)
    srv2.compile_step(mlp_loss_fn)
    hist = srv2.serve(steps=3, idle_timeout=60.0, start_step=3)
    t.join(timeout=90)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert len(hist["losses"]) == 3


def test_unauthed_peer_gets_no_crc_tolerance():
    """Frame-local CRC forgiveness is for booked workers' links; a peer
    that never completed a HELO streaming bad-CRC frames must cost its
    connection immediately, not pin a handler thread forever."""
    import zlib as _zlib

    srv = _server()
    results = {}
    t = _worker_thread(srv.address[1], results, "w0")
    served = {}
    st = threading.Thread(
        target=lambda: served.update(h=srv.serve(steps=3,
                                                 idle_timeout=60.0)),
        daemon=True)
    st.start()
    stray = socket.create_connection(("127.0.0.1", srv.address[1]))
    payload = b"GRADjunk"
    bad_crc = (_zlib.crc32(payload) ^ 0xFFFF)
    import struct as _struct
    stray.sendall(_struct.pack("<II", len(payload), bad_crc) + payload)
    st.join(timeout=60)
    assert not st.is_alive()
    t.join(timeout=60)
    stray.close()
    hist = served["h"]
    assert hist["fault_stats"]["crc_dropped"] >= 1
    assert hist["fault_stats"]["conn_drops"] >= 1  # the stray was dropped


def test_resume_preserves_rank_allocation(tmp_path):
    """The auto-checkpoint carries rank-allocation state: a restarted PS
    must not mint a fresh worker the rank a survivor is about to re-book
    via prior_rank, and the idle diagnostic must not claim zero workers."""
    ckpt = tmp_path / "ranks.psz"
    srv1 = _server(fault_plan=FaultPlan(kill_ps_at=4))
    results = {}
    t = _worker_thread(srv1.address[1], results, "w0",
                       reconnect_retries=20, backoff_base=0.05,
                       backoff_max=0.4)
    with pytest.raises(SimulatedCrash):
        srv1.serve(steps=8, idle_timeout=60.0,
                   checkpoint_path=str(ckpt), checkpoint_every=2)
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    srv2 = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                          quota=1, port=srv1.address[1])
    srv2.compile_step(mlp_loss_fn)
    start = srv2.resume_from(str(ckpt))
    assert start == 4
    assert srv2._next_rank >= 1  # rank 0 stays reserved for the survivor
    assert srv2._workers_seen >= 1  # the diagnostic keeps its history
    hist = srv2.serve(steps=8 - start, idle_timeout=60.0, start_step=start)
    t.join(timeout=90)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    assert len(hist["losses"]) == 8 - start


def test_queue_full_drop_at_shutdown_is_counted():
    """The once-invisible drop: a gradient abandoned because the run ended
    while the queue was full must land in fault_stats, keyed by rank."""
    srv = _server()
    try:
        while True:  # fill the bounded queue to capacity
            try:
                srv._net_queue.put_nowait(("x", 0, None, 0.0))
            except Exception:
                break
        srv._net_stop.set()
        assert srv._enqueue_grad(("y", 0, 3, 0.0), rank=3) is False
        assert srv._enqueue_grad(("z", 0, None, 0.0), rank=None) is False
        assert srv.fault_stats["dropped_queue_full"] == {3: 1, -1: 1}
    finally:
        srv.close()


def test_accept_errors_counted_not_silent():
    """An unexpected OSError on the accept path must increment a counter
    and keep the loop serving (it used to `break` silently — a PS that
    stopped admitting workers forever with no trace)."""
    srv = _server()

    class FlakyListener:
        def __init__(self):
            self.calls = 0

        def settimeout(self, t):
            pass

        def fileno(self):
            return 99  # "still open"

        def accept(self):
            self.calls += 1
            if self.calls >= 3:
                srv._net_stop.set()
                raise socket.timeout()
            raise OSError("transient accept failure")

    real = srv._listener
    srv._listener = FlakyListener()
    try:
        t = threading.Thread(target=srv._accept_loop, daemon=True)
        t.start()
        t.join(timeout=10)
        assert not t.is_alive()
        assert srv.fault_stats["accept_errors"] == 2
    finally:
        srv._listener = real
        srv.close()


def test_format_fault_stats_renders_counters():
    from pytorch_ps_mpi_tpu.utils.timing import format_fault_stats

    assert format_fault_stats({}) == "clean"
    assert format_fault_stats({"evictions": 0, "crc_dropped": 0}) == "clean"
    s = format_fault_stats({"evictions": 1, "crc_dropped": 4,
                            "dropped_queue_full": {0: 2, 3: 1},
                            "evicted_ranks": [1]})
    assert "evictions=1" in s and "crc_dropped=4" in s
    assert "dropped_queue_full=3" in s and "evicted_ranks=[1]" in s


# ---------------------------------------------------------------------------
# CLI flag wiring
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_crash_resume_endurance(tmp_path):
    """The full supervised-relaunch workflow through the CLI, with REAL
    separate processes: --serve dies by FaultPlan mid-run (exit != 0, no
    DONE sent), CLI workers ride their reconnect backoff across the gap,
    the relaunched --serve --resume continues from the auto-checkpoint on
    the same port, and the run completes exactly the remaining updates."""
    import subprocess
    import sys as _sys

    from test_multihost_async import ChildProc, _reap_all

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    ckpt = str(tmp_path / "cli_chaos.psz")
    chaos = FaultPlan(kill_ps_at=12).to_json().replace("'", "\\'")
    base = ("'--model','mlp','--steps','30','--quota','1',"
            "'--batch-size','32','--n-examples','128'")

    server1 = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0',{base},'--save','{ckpt}',"
         f"'--checkpoint-every','4','--chaos','{chaos}'])"])
    line = server1.stdout.readline()
    assert line.startswith("serving on port "), line
    port = line.strip().rsplit(" ", 1)[1]

    workers = [ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--connect','127.0.0.1:{port}',{base},"
         "'--reconnect-retries','100'])"])
        for _ in range(2)]

    (s1_out, s1_err) = _reap_all([server1], timeout=300)[0]
    assert server1.returncode != 0  # the PS really crashed
    assert "SimulatedCrash" in s1_err, s1_err

    server2 = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','{port}',{base},'--resume','{ckpt}',"
         f"'--save','{ckpt}','--checkpoint-every','4'])"])

    outs = _reap_all([server2] + workers, timeout=300)
    (s2_out, s2_err) = outs[0]
    assert server2.returncode == 0, f"server2 failed:\n{s2_out}\n{s2_err}"
    assert "resumed from" in s2_err and "at step 12" in s2_err
    assert "done: 18 updates" in s2_err, s2_err
    for w, (w_out, w_err) in zip(workers, outs[1:]):
        assert w.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"
        assert "gradients pushed" in w_err
    # At least one worker reconnected across the crash.
    assert any("reconnect(s) to the PS" in e for _, e in outs[1:]), \
        [e for _, e in outs[1:]]


@pytest.mark.slow
def test_cli_robust_quorum_endurance():
    """Endurance chaos through the REAL CLI roles: a 3-worker fleet where
    one rank is a deterministic straggler and another pushes 100x-scaled
    Byzantine gradients; the --serve process runs trimmed_mean aggregation
    with a quorum and anomaly scoring, completes every update, and exits
    cleanly along with the honest workers."""
    import subprocess
    import sys as _sys

    from test_multihost_async import ChildProc, _reap_all

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    chaos = FaultPlan(slow_rank=2, slow_delay_s=0.4, byzantine_rank=1,
                      byzantine_mode="scale",
                      byzantine_scale=100.0).to_json().replace("'", "\\'")
    base = ("'--model','mlp','--steps','20','--batch-size','32',"
            "'--n-examples','128'")

    server = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0',{base},'--quota','3','--quorum','2',"
         # norm_clip: its influence bound holds at any fill size, so it
         # composes with a quorum of 2 (trimmed_mean would refuse: a
         # 2-contribution short fill is below its breakdown size).
         "'--fill-deadline','0.1','--aggregate','norm_clip',"
         "'--anomaly-z','4'])"])
    line = server.stdout.readline()
    assert line.startswith("serving on port "), line
    port = line.strip().rsplit(" ", 1)[1]

    workers = [ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--connect','127.0.0.1:{port}',{base},"
         f"'--chaos','{chaos}'])"])
        for _ in range(3)]

    outs = _reap_all([server] + workers, timeout=300)
    (s_out, s_err) = outs[0]
    assert server.returncode == 0, f"server failed:\n{s_out}\n{s_err}"
    assert "done: 20 updates" in s_err, s_err
    for w, (w_out, w_err) in zip(workers, outs[1:]):
        assert w.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"


def test_cli_refuses_misplaced_fault_flags():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="--max-staleness"):
        train.main(["--model", "mlp", "--max-staleness", "4", "--steps", "1"])
    with pytest.raises(SystemExit, match="--checkpoint-every"):
        train.main(["--model", "mlp", "--checkpoint-every", "2",
                    "--steps", "1"])
    with pytest.raises(SystemExit, match="--save PATH"):
        train.main(["--model", "mlp", "--serve", "0",
                    "--checkpoint-every", "2", "--steps", "1"])
    with pytest.raises(SystemExit, match="--chaos"):
        train.main(["--model", "mlp", "--chaos", "{}", "--steps", "1"])
    with pytest.raises(SystemExit, match="PS-side admission"):
        train.main(["--model", "mlp", "--connect", "127.0.0.1:1",
                    "--skip-nonfinite"])
