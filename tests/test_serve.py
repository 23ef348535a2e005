"""Serve tier (ISSUE 14): versioned snapshot subscription, READ-class
credit gating, and the continuous-batching inference front-end.

Oracles mirror the contract the serve tier claims:

* the READ gate is a SEPARATE budget in `transport.Session`: reader
  frames can never consume (or stall behind) DATA credits, and a
  closed read gate stalls-then-sheds OLDEST-FIRST with the `open_read`
  bounded-stall valve as recovery;
* `serve.Subscriber` reads a full snapshot at a consistent version,
  then conditional deltas — unchanged polls are head-only, server-side
  shed serves the cached tree, versions never rewind across failover,
  and N subscribers cost ONE encode per version (the PR 13 fanout
  cache, generalized to the read path);
* `serve.InferenceFrontend` assembles a fresh batch every decode step
  (requests join/leave at step granularity), reports per-request
  p50/p95 via the shared `RequestLatency`, sheds with typed
  `InferShedError` at overload, and hot-swaps params with zero dropped
  requests;
* every new counter is initialized, snapshot, and rendered by
  `format_fault_stats` (the established parity contract), and the CLI
  refuses the serve-tier flags on roles that would silently ignore
  them.
"""

import socket
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.async_ps import AsyncPS, dataset_batch_fn
from pytorch_ps_mpi_tpu.errors import InferShedError
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import (AsyncPSWorker,
                                                AsyncSGDServer)
from pytorch_ps_mpi_tpu.serve import (FleetSubscriber, InferenceFrontend,
                                      Subscriber)
from pytorch_ps_mpi_tpu.transport import (Deadline, READ_FRAME_KINDS,
                                          Session, recv_frame)
from pytorch_ps_mpi_tpu.utils.timing import (RankLatency, RequestLatency,
                                             format_fault_stats)

REPO = Path(__file__).resolve().parent.parent


def _teacher(seed=7):
    rng = np.random.RandomState(seed)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _server(quota=1, seed=0, **kw):
    params = init_mlp(np.random.RandomState(seed), sizes=(16, 32, 4))
    srv = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                         quota=quota, **kw)
    srv.compile_step(mlp_loss_fn)
    return srv


def _serve_bg(srv, steps, **kw):
    out = {}

    def body():
        try:
            out["hist"] = srv.serve(steps=steps, idle_timeout=60, **kw)
        except BaseException as exc:  # surfaced by the caller
            out["error"] = exc

    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t, out


def _run_worker(port, max_iters=None, **kw):
    x, y = _teacher()
    w = AsyncPSWorker("127.0.0.1", port, **kw)
    w.run(mlp_loss_fn, dataset_batch_fn(x, y, 32), max_iters=max_iters)
    return w


# ---------------------------------------------------------------------------
# the READ gate: a separate credit class in transport.Session
# ---------------------------------------------------------------------------

def test_read_kinds_are_disjoint_from_data_kinds():
    from pytorch_ps_mpi_tpu.transport import DATA_FRAME_KINDS
    assert READ_FRAME_KINDS == frozenset((b"SUBS",))
    assert not (READ_FRAME_KINDS & DATA_FRAME_KINDS)


def test_read_gate_budget_is_separate_from_data_gate():
    a, b = socket.socketpair()
    try:
        s = Session(a)
        # Exhausted DATA credits must not touch READ frames...
        s.replenish(0)
        assert s.send(b"SUBS" + b"\x00" * 8) is True
        assert recv_frame(b)[:4] == b"SUBS"
        # ...and an exhausted READ window must not touch DATA/CONTROL.
        s.replenish_read(0)
        assert s.send(b"GRAD" + b"x") is False  # data gate still closed
        s.replenish(1)
        assert s.send(b"BEAT") is True
        assert recv_frame(b) == b"GRAD" + b"x"  # flushed by replenish
        assert recv_frame(b) == b"BEAT"
        assert s.send_read(b"SUBS2345") is False  # read gate closed
        assert s.stats["reads_stalled"] == 1
    finally:
        a.close()
        b.close()


def test_read_gate_parks_then_sheds_oldest_first_and_flushes_fifo():
    a, b = socket.socketpair()
    try:
        s = Session(a, max_pending=2)
        s.replenish_read(0)
        frames = [b"SUBS" + bytes([i]) * 4 for i in range(3)]
        for f in frames:
            assert s.send_read(f) is False
        # Queue bound 2: the OLDEST parked read was shed.
        assert s.read_pending_count() == 2
        assert s.stats["read_shed"] == 1
        assert s.stats["reads_stalled"] == 3
        s.replenish_read(8)
        assert s.read_pending_count() == 0
        # FIFO flush of the two survivors (frames[1], frames[2]).
        assert recv_frame(b) == frames[1]
        assert recv_frame(b) == frames[2]
    finally:
        a.close()
        b.close()


def test_read_gate_sheds_now_on_expired_deadline():
    a, b = socket.socketpair()
    try:
        s = Session(a)
        s.replenish_read(0)
        assert s.send_read(b"SUBSxxxx", deadline=Deadline(0.0)) is False
        assert s.read_pending_count() == 0  # shed, never parked
        assert s.stats["read_shed"] == 1
    finally:
        a.close()
        b.close()


def test_open_read_valve_grants_one_probe():
    a, b = socket.socketpair()
    try:
        s = Session(a)
        s.replenish_read(0)
        assert s.send_read(b"SUBSxxxx", deadline=Deadline(0.0)) is False
        s.open_read()
        assert s.send_read(b"SUBSxxxx") is True  # the probe
        assert s.send_read(b"SUBSyyyy", deadline=Deadline(0.0)) is False
        assert recv_frame(b) == b"SUBSxxxx"
    finally:
        a.close()
        b.close()


# ---------------------------------------------------------------------------
# subscription: snapshot, deltas, unchanged short-circuits, shed, fanout
# ---------------------------------------------------------------------------

def test_subscriber_full_snapshot_then_deltas_then_done():
    srv = _server(quota=1)
    try:
        t, out = _serve_bg(srv, steps=8)
        sub = Subscriber("127.0.0.1", srv.address[1])
        v0, params0 = sub.snapshot()
        assert v0 == 0 and set(params0) == set(srv.params)
        wt = threading.Thread(target=_run_worker,
                              args=(srv.address[1],), daemon=True)
        wt.start()
        seen = [v0]
        for _ in range(600):
            version, params, changed = sub.poll()
            if changed:
                seen.append(version)
            if sub.done:
                break
            time.sleep(0.005)
        t.join(timeout=60)
        wt.join(timeout=30)
        assert "error" not in out
        assert sub.done  # the server's DONE reached the reader
        # Versions advanced monotonically, no rewind.
        assert seen == sorted(seen)
        assert sub.fault_stats["version_rewinds"] == 0
        assert sub.fault_stats["delta_frames"] >= 2
        # Unchanged polls dominate: served reads > payload frames.
        assert (sub.fault_stats["reads_served"]
                > sub.fault_stats["delta_frames"])
        fs = out["hist"]["fault_stats"]
        assert fs["reads_served"] > 0 and fs["delta_frames"] >= 2
        # The reader may or may not have dropped (DONE) by the time
        # the end-of-serve snapshot was cut — but the gauge is never
        # negative and never above the one live reader.
        assert fs["subs_active"] in (0, 1)
        sub.close()
        deadline = Deadline(5.0)
        while (srv.fault_stats["subs_active"] != 0
               and not deadline.expired()):
            time.sleep(0.02)
        assert srv.fault_stats["subs_active"] == 0
    finally:
        srv.close()


def test_unchanged_short_circuit_costs_no_encode():
    srv = _server(quota=1)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        sub = Subscriber("127.0.0.1", srv.address[1])
        v, params = sub.snapshot()
        encodes_after_first = srv.fault_stats["parm_encodes"]
        for _ in range(5):
            version, params, changed = sub.poll()
            assert not changed and version == v
        # Conditional polls at the served version never re-encode.
        assert srv.fault_stats["parm_encodes"] == encodes_after_first
        assert sub.fault_stats["reads_served"] >= 6
        assert sub.fault_stats["delta_frames"] == 1
        sub.close()
    finally:
        srv.close()


def test_sender_side_read_gate_closes_on_zeroed_window(monkeypatch):
    """Single reader, read_window=1: the first full read spends the
    token and the reply advertises 0 — the SENDER's read gate closes,
    the next forced poll sheds locally (session ``read_shed``), and the
    `open_read` valve re-probes once the budget is back."""
    from pytorch_ps_mpi_tpu import multihost_async as mh

    # Pin the time-floor refill out of the test window: the shed /
    # recovery sequence must be deterministic under suite load, not a
    # race against the 0.25 s idle-refill clock.
    monkeypatch.setattr(mh, "_READ_REFILL_S", 60.0)
    srv = _server(quota=1, read_window=1)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        sub = Subscriber("127.0.0.1", srv.address[1],
                         read_backoff=0.01)
        v, params = sub.snapshot()          # spends the one token
        version, cached, changed = sub.poll(force=True)  # gate closed
        assert not changed and cached is params  # served from cache
        snap = sub.fault_snapshot()
        assert snap["reads_stalled"] >= 1 and snap["read_shed"] >= 1
        # Grant the budget back explicitly; past the backoff the valve
        # probes and the read comes back.
        with srv._read_lock:
            srv._read_tokens = 1
        time.sleep(0.05)
        changed = False
        for _ in range(8):
            version, params2, changed = sub.poll(force=True)
            if changed:
                break
            time.sleep(0.02)
        assert changed and version == v
        sub.close()
    finally:
        srv.close()


def test_server_read_budget_sheds_a_second_reader(monkeypatch):
    """Two readers, read_window=1: reader A spends the token; reader B
    (fresh, ungated session) reaches the server inside the same refill
    window and is shed HEAD-ONLY — the server-side half of the READ
    shed, counted on both ends."""
    from pytorch_ps_mpi_tpu import multihost_async as mh

    monkeypatch.setattr(mh, "_READ_REFILL_S", 60.0)
    srv = _server(quota=1, read_window=1)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        sub_a = Subscriber("127.0.0.1", srv.address[1])
        sub_a.snapshot()                    # spends the one token
        sub_b = Subscriber("127.0.0.1", srv.address[1],
                           read_backoff=0.01)
        version, params, changed = sub_b.poll(force=True)
        assert not changed and params is None  # nothing cached yet
        assert sub_b.fault_stats["read_shed"] >= 1
        assert srv.fault_stats["read_shed"] >= 1
        # Budget granted back: the shed reader gets its snapshot (its
        # sender gate re-opens through the open_read valve).
        with srv._read_lock:
            srv._read_tokens = 1
        time.sleep(0.05)
        changed = False
        for _ in range(8):
            version, params, changed = sub_b.poll(force=True)
            if changed:
                break
            time.sleep(0.02)
        assert changed and params is not None
        sub_a.close()
        sub_b.close()
    finally:
        srv.close()


def test_subs_active_gauge_tracks_live_subscribers():
    srv = _server(quota=1)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        sub = Subscriber("127.0.0.1", srv.address[1])
        sub.snapshot()
        assert srv.fault_stats["subs_active"] == 1
        sub.close()
        deadline = Deadline(5.0)
        while (srv.fault_stats["subs_active"] != 0
               and not deadline.expired()):
            time.sleep(0.02)
        assert srv.fault_stats["subs_active"] == 0
    finally:
        srv.close()


def test_encode_once_fanout_across_many_subscribers():
    """N subscribers force-reading while training advances cost ONE
    encode per version: parm_encodes tracks versions, not versions*N."""
    srv = _server(quota=1, read_window=64)
    try:
        t, out = _serve_bg(srv, steps=6)
        subs = [Subscriber("127.0.0.1", srv.address[1])
                for _ in range(4)]
        stop = threading.Event()

        def reader(sub):
            while not stop.is_set() and not sub.done:
                try:
                    sub.poll(force=True)
                except OSError:
                    break
                time.sleep(0.002)

        threads = [threading.Thread(target=reader, args=(s,),
                                    daemon=True) for s in subs]
        for th in threads:
            th.start()
        wt = threading.Thread(target=_run_worker,
                              args=(srv.address[1],), daemon=True)
        wt.start()
        t.join(timeout=60)
        stop.set()
        for th in threads:
            th.join(timeout=10)
        wt.join(timeout=30)
        assert "error" not in out
        fs = out["hist"]["fault_stats"]
        versions = len(out["hist"]["versions"])
        reads = sum(s.fault_stats["delta_frames"] for s in subs)
        # Every full read was served, but the encode count tracks the
        # VERSION count (+1 for version 0), never the read count.
        assert fs["parm_encodes"] <= versions + 2, fs
        assert reads > fs["parm_encodes"], (reads, fs["parm_encodes"])
        for s in subs:
            s.close()
    finally:
        srv.close()


def test_plain_subscriber_refuses_fleet_shard():
    from pytorch_ps_mpi_tpu.shard import PSFleet

    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    fleet = PSFleet(list(params.items()), num_shards=2, quota=1,
                    lr=0.05, momentum=0.5)
    try:
        fleet.compile_step(mlp_loss_fn)
        for srv in fleet.servers:
            threading.Thread(target=srv._accept_loop,
                             daemon=True).start()
        with pytest.raises(ValueError, match="FleetSubscriber"):
            Subscriber("127.0.0.1", fleet.addresses[0][1])
    finally:
        fleet.close()


def test_fleet_subscriber_assembles_full_tree():
    from pytorch_ps_mpi_tpu.shard import PSFleet

    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    fleet = PSFleet(list(params.items()), num_shards=2, quota=1,
                    lr=0.05, momentum=0.5)
    try:
        fleet.compile_step(mlp_loss_fn)
        for srv in fleet.servers:
            threading.Thread(target=srv._accept_loop,
                             daemon=True).start()
        sub = FleetSubscriber(fleet.addresses)
        versions, tree = sub.snapshot()
        assert set(tree) == set(params)
        assert len(versions) == 2
        # A second conditional poll is all-unchanged.
        versions, tree2, changed = sub.poll()
        assert not changed
        sub.close()
    finally:
        fleet.close()


def test_subscriber_survives_shard_failover_without_rewind(tmp_path):
    """The hot-swap failover contract (acceptance gate c): a shard dies
    mid-run, the supervisor restores it on the same port, and the
    subscription resumes deltas with NO version rewind (the restored
    serving-version counter is continuous)."""
    from pytorch_ps_mpi_tpu.shard import PSFleet, ShardRouter
    from pytorch_ps_mpi_tpu.utils.faults import FaultPlan

    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    plan = FaultPlan(seed=0, kill_shard_at={1: 4})
    fleet = PSFleet(list(params.items()), num_shards=2, quota=1,
                    lr=0.05, momentum=0.5, fault_plan=plan)
    out = {}
    try:
        fleet.compile_step(mlp_loss_fn)
        ckpt = tmp_path / "ckpt.psz"

        def serve():
            try:
                out["hist"] = fleet.serve(
                    steps=10, checkpoint_path=str(ckpt),
                    checkpoint_every=1)
            except BaseException as exc:
                out["error"] = exc

        st = threading.Thread(target=serve, daemon=True)
        st.start()
        sub = FleetSubscriber(fleet.addresses, reconnect_retries=20,
                              backoff_max=0.5)
        x, y = _teacher()

        def worker():
            r = ShardRouter(fleet.addresses, fault_plan=None,
                            reconnect_retries=20, backoff_max=0.5)
            r.run(mlp_loss_fn, dataset_batch_fn(x, y, 32))

        wt = threading.Thread(target=worker, daemon=True)
        wt.start()
        seen_after_kill = 0
        restored = False
        for _ in range(3000):
            try:
                versions, tree, changed = sub.poll()
            except OSError:
                break
            if fleet.fault_stats.get("shard_restores", 0) >= 1:
                restored = True
                if changed:
                    seen_after_kill += 1
            if sub.done:
                break
            time.sleep(0.005)
        st.join(timeout=120)
        wt.join(timeout=60)
        assert "error" not in out, out.get("error")
        assert out["hist"]["fault_stats"]["shard_restores"] >= 1
        assert restored
        # Deltas RESUMED past the failover, and no link ever rewound.
        assert seen_after_kill >= 1
        snap = sub.fault_snapshot()
        assert snap["version_rewinds"] == 0
        sub.close()
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# the continuous-batching inference front-end
# ---------------------------------------------------------------------------

def _tiny_lm():
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm)
    model = TransformerLM(vocab_size=32, d_model=16, n_heads=2,
                          n_layers=1, d_ff=32, max_len=32)
    return model, build_lm(model, seq_len=8)


def test_infer_continuous_batching_requests_join_and_leave():
    model, params = _tiny_lm()
    fe = InferenceFrontend(model, params, max_batch=2, buf_len=16,
                           max_queue=8)
    first = [fe.submit([1, 2, 3], max_new=4) for _ in range(2)]
    fe.step()
    # A request admitted MID-RUN joins the running batch at the next
    # step — continuous batching, not run-to-completion batches.
    late = fe.submit([4, 5], max_new=2)
    fe.drain()
    for req in first:
        assert len(req.result(0)) == 4
    assert len(late.result(0)) == 2
    stats = fe.stats()
    assert stats["infer_requests"] == 3 and stats["infer_shed"] == 0
    lat = stats["request_latency"]
    assert lat["n"] == 3 and lat["p95_s"] >= lat["p50_s"] > 0


def test_infer_sheds_with_typed_error_at_overload():
    model, params = _tiny_lm()
    fe = InferenceFrontend(model, params, max_batch=1, buf_len=16,
                           max_queue=2)
    admitted = []
    shed = 0
    for i in range(6):
        try:
            admitted.append(fe.submit([1 + i % 8], max_new=2))
        except InferShedError as exc:
            shed += 1
            assert "back off" in str(exc)
    # Queue bound 2, no steps between submits: 2 admitted, 4 shed.
    assert shed == 4 and len(admitted) == 2
    fe.drain()
    for req in admitted:
        assert len(req.result(0)) == 2
    stats = fe.stats()
    assert stats["infer_shed"] == shed
    assert stats["infer_requests"] == 6


def test_infer_hot_swap_drops_no_requests():
    model, params = _tiny_lm()

    class Source:
        """A params_source stub: changes once, then holds."""

        def __init__(self):
            self.calls = 0

        def poll(self):
            self.calls += 1
            if self.calls == 2:
                import jax

                bumped = {n: np.asarray(p) + 0.01
                          for n, p in params.items()}
                return 1, bumped, True
            return 1, None, False

    src = Source()
    fe = InferenceFrontend(model, params, max_batch=2, buf_len=16,
                           max_queue=8, params_source=src)
    reqs = [fe.submit([1, 2], max_new=6) for _ in range(2)]
    fe.drain()
    # The swap landed mid-decode and every request still completed.
    assert fe.stats()["param_swaps"] == 1
    for req in reqs:
        assert len(req.result(0)) == 6


def test_nonblock_heal_keeps_poll_fast_while_ps_is_down():
    """The hot-swap path's healing policy (review finding): with
    ``nonblock_heal=True`` a dead PS costs each poll at most one
    bounded dial probe per backoff window — never the full redial
    ladder — so a decode loop polling the subscription keeps its
    per-step latency bound and keeps serving the cached snapshot."""
    srv = _server(quota=1)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        sub = Subscriber("127.0.0.1", srv.address[1],
                         nonblock_heal=True, read_backoff=0.05,
                         reconnect_retries=30)
        v, params = sub.snapshot()
    finally:
        srv.close()
    time.sleep(0.1)  # let the listener actually die
    t0 = time.perf_counter()
    for _ in range(3):
        version, cached, changed = sub.poll()
        assert not changed and cached is params  # cached snapshot
    elapsed = time.perf_counter() - t0
    # Three polls against a dead PS: each pays at most one refused
    # loopback dial (instant) — nowhere near the ~30-retry ladder.
    assert elapsed < 2.0, elapsed
    sub.close()


def test_drain_budget_failure_is_not_a_shed():
    """A blown drain() budget is a stuck engine, not admission
    overload (review finding): it must raise TimeoutError — a caller
    backing off-and-retrying on typed InferShedError must never be
    told to retry against a stuck engine."""
    model, params = _tiny_lm()
    fe = InferenceFrontend(model, params, max_batch=1, buf_len=16,
                           max_queue=2)
    fe.submit([1], max_new=2)
    with pytest.raises(TimeoutError, match="step budget"):
        fe.drain(max_steps=0)
    fe.drain()  # the real drain still finishes the request


def test_redial_resets_the_read_gate():
    """The READ window is incarnation-scoped (review finding): a zero
    window advertised by a dead server must not gate sends to its
    successor.  `_connect` — the one dial path every redial ladder and
    heal probe runs through — resets the gate exactly like it forces
    the next read full, so a failover never pays an extra
    ``read_backoff`` window (or books sheds against a server that
    never refused anything)."""
    srv = _server(quota=1)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        # read_backoff=30: the successful read below PROVES the redial
        # reset reopened the gate — the open_read valve could not have
        # fired within this test's lifetime.
        sub = Subscriber("127.0.0.1", srv.address[1], read_backoff=30.0)
        sub.snapshot()
        sub._session.replenish_read(0)  # the old incarnation's last word
        version, cached, changed = sub.poll(force=True)
        assert not changed  # gate closed: shed locally
        sub._connect()  # the redial (same path as the reconnect ladder)
        assert sub._session.read_credits() is None  # back to ungated
        version, params, changed = sub.poll(force=True)
        assert changed  # no backoff window paid, no valve needed
        assert sub.fault_stats["version_rewinds"] == 0
        sub.close()
    finally:
        srv.close()


def test_request_latency_concurrent_reads_never_crash():
    """stats()/snapshot may run from a monitoring thread while the
    engine observes (review finding): the window copies under a lock,
    so a concurrent reader never hits 'deque mutated during
    iteration'."""
    rl = RequestLatency(window=32)
    stop = threading.Event()
    errors = []

    def writer():
        i = 0
        while not stop.is_set():
            rl.observe(0.001 * (i % 7))
            i += 1

    def reader():
        try:
            for _ in range(2000):
                rl.snapshot()
                rl.percentile(95)
                rl.recent_median()
        except Exception as exc:  # pragma: no cover - the bug itself
            errors.append(exc)

    wt = threading.Thread(target=writer, daemon=True)
    rt = threading.Thread(target=reader, daemon=True)
    wt.start()
    rt.start()
    rt.join(timeout=30)
    stop.set()
    wt.join(timeout=10)
    assert not errors, errors


def test_infer_admission_validation():
    model, params = _tiny_lm()
    fe = InferenceFrontend(model, params, max_batch=1, buf_len=8,
                           max_queue=2)
    with pytest.raises(ValueError, match="empty prompt"):
        fe.submit([], max_new=2)
    with pytest.raises(ValueError, match="exceeds the decode buffer"):
        fe.submit([1] * 7, max_new=4)
    with pytest.raises(ValueError, match="max_new"):
        fe.submit([1], max_new=0)


# ---------------------------------------------------------------------------
# RequestLatency: the shared percentile engine (RankLatency unchanged)
# ---------------------------------------------------------------------------

def test_request_latency_window_and_percentiles():
    rl = RequestLatency(window=4)
    assert rl.p50() is None and rl.snapshot() == {}
    for dt in (0.1, 0.2, 0.3, 0.4):
        rl.observe(dt)
    assert rl.p50() == pytest.approx(0.25)
    assert rl.p95() == pytest.approx(0.385)
    # Rolling window: old observations age out, the count does not.
    for dt in (1.0, 1.0, 1.0, 1.0):
        rl.observe(dt)
    assert rl.p50() == pytest.approx(1.0)
    assert rl.n == 8 and len(rl) == 4
    snap = rl.snapshot()
    assert set(snap) == {"ema_s", "p50_s", "p95_s", "n"}
    # Negative spans clamp to zero (monotonic-clock hiccups).
    rl.observe(-1.0)
    assert min(rl._win) == 0.0


def test_request_latency_recent_median_ignores_one_spike():
    rl = RequestLatency(window=16)
    for _ in range(8):
        rl.observe(0.1)
    rl.observe(30.0)  # one outage spike
    assert rl.recent_median() == pytest.approx(0.1)
    assert rl.recent_median(min_obs=100) is None


def test_rank_latency_behavior_preserved_on_request_engine():
    """RankLatency now delegates to per-rank RequestLatency windows —
    its public semantics (snapshot keys, fleet_p95's median-over-ranks,
    speed_weight's floor, forget) must be unchanged."""
    rl = RankLatency(window=8)
    t = 100.0
    for i in range(6):
        rl.observe(0, t)
        rl.observe(1, t)
        t += 0.1
    # Rank 1 turns persistently slow.
    t1 = t
    for i in range(8):
        rl.observe(0, t + 0.1 * i)
        rl.observe(1, t1)
        t1 += 0.4
    snap = rl.snapshot()
    assert set(snap) == {0, 1}
    assert set(snap[0]) == {"ema_s", "p50_s", "p95_s", "n"}
    assert snap[1]["p95_s"] > snap[0]["p95_s"]
    # fleet_p95 = median over ranks; with one fast and one slow rank it
    # sits between the two per-rank p95s.
    fp = rl.fleet_p95()
    assert snap[0]["p95_s"] <= fp <= snap[1]["p95_s"]
    w = rl.speed_weight(1)
    assert 0.25 <= w < 1.0
    assert rl.speed_weight(0) == 1.0
    assert rl.speed_weight(None) == 1.0
    rl.forget(1)
    assert set(rl.snapshot()) == {0}
    assert RankLatency().fleet_p95() is None


# ---------------------------------------------------------------------------
# counter parity + render coverage (the serve-tier counters, everywhere)
# ---------------------------------------------------------------------------

SERVE_COUNTERS = ("reads_served", "read_shed", "delta_frames",
                  "subs_active", "reads_stalled", "infer_requests",
                  "infer_shed")


def test_serve_counters_key_parity_and_render():
    inproc = AsyncPS([("w", np.zeros((2,), np.float32))], quota=1)
    srv = _server(quota=1)
    try:
        for key in SERVE_COUNTERS:
            assert key in inproc.fault_stats, f"{key} not in base literal"
            assert key in srv.fault_stats
        # Every serve-tier counter (and the reader/infer-side extras)
        # renders in the one-line summary.
        model, params = _tiny_lm()
        fe = InferenceFrontend(model, params, max_queue=1)
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        sub = Subscriber("127.0.0.1", srv.address[1])
        for stats in (dict.fromkeys(SERVE_COUNTERS, 0),
                      fe.fault_stats, sub.fault_snapshot()):
            for key, value in stats.items():
                if isinstance(value, int):
                    assert format_fault_stats({key: 1}) != "clean", (
                        f"counter {key!r} invisible to "
                        f"format_fault_stats")
        # Snapshot parity: the base snapshot (with the serve keys)
        # reaches the server deployment's snapshot.
        assert set(inproc._base_fault_snapshot()) <= \
            set(srv._fault_stats_snapshot())
        sub.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# pslint drift coverage reaches the serve module
# ---------------------------------------------------------------------------

def test_drift_checker_catches_real_subscribe_frame_drift(tmp_path):
    """Tamper the real subscriber's SUBS encode literal: the drift
    checker must flag the one-sided kinds — proof the new `send_read`
    encode surface is inside the PSL301 balance, not silently out of
    scope."""
    import sys
    sys.path.insert(0, str(REPO))
    from tools.pslint.core import load_corpus, run_checkers

    src = (REPO / "pytorch_ps_mpi_tpu" / "serve"
           / "subscribe.py").read_text()
    needle = 'b"SUBS" + _U64.pack(have)'
    assert needle in src  # the encode site under test
    tampered = src.replace(needle, 'b"XUBS" + _U64.pack(have)')
    path = tmp_path / "subscribe_tampered.py"
    path.write_text(tampered)
    findings = run_checkers(load_corpus([path]))
    kinds = {(f.checker, "XUBS" in f.message) for f in findings}
    assert ("PSL301", True) in kinds, findings


# ---------------------------------------------------------------------------
# CLI refusal matrix
# ---------------------------------------------------------------------------

def test_cli_refuses_conflicting_serve_tier_roles():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="mutually exclusive"):
        train.main(["--model", "mlp", "--steps", "1",
                    "--subscribe", "127.0.0.1:1", "--serve", "0"])
    with pytest.raises(SystemExit, match="mutually exclusive"):
        train.main(["--model", "mlp", "--steps", "1",
                    "--subscribe", "127.0.0.1:1",
                    "--connect", "127.0.0.1:1"])
    with pytest.raises(SystemExit, match="in-process"):
        train.main(["--model", "mlp", "--steps", "1", "--async-ps",
                    "--subscribe", "127.0.0.1:1"])


def test_cli_refuses_infer_serve_off_the_subscription():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="snapshot subscription"):
        train.main(["--model", "transformer", "--steps", "1",
                    "--infer-serve"])
    with pytest.raises(SystemExit, match="snapshot subscription"):
        train.main(["--model", "transformer", "--steps", "1",
                    "--infer-serve", "--connect", "127.0.0.1:1"])
    with pytest.raises(SystemExit, match="model transformer"):
        train.main(["--model", "mlp", "--steps", "1",
                    "--subscribe", "127.0.0.1:1", "--infer-serve"])


def test_cli_refuses_read_window_off_serve_roles():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="read-window"):
        train.main(["--model", "mlp", "--steps", "1",
                    "--read-window", "4"])
    with pytest.raises(SystemExit, match="read-window"):
        train.main(["--model", "mlp", "--steps", "1",
                    "--connect", "127.0.0.1:1", "--read-window", "4"])
    with pytest.raises(SystemExit, match="read-window"):
        train.main(["--model", "mlp", "--steps", "1",
                    "--subscribe", "127.0.0.1:1", "--read-window", "4"])
