"""Fleet availability layer (ISSUE 7): hot-standby replication +
promotion, coordinated fleet snapshots + manifest-verified resume, and
partition-tolerant degraded mode.

The oracles mirror the subsystem's contracts: a standby tracks its
primary within the replication cadence (zero lag at the default);
promotion serves the NEXT fill with continuous versions and zero update
rewind even with ``checkpoint_every=0``; a fleet manifest refuses —
typed, never silently — skewed, partial, tampered, or wrong-plan
checkpoint sets; a black-holed link degrades (bounded, counted) instead
of dying and heals onto the SAME rank with zero churn; and every new
counter renders through the same ``format_fault_stats`` line.
In-process fleets keep the tier-1 lane fast; the real-process CLI
promotion run is ``slow``-marked.
"""

import threading
import time
from pathlib import Path

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.async_ps import AsyncPS, dataset_batch_fn
from pytorch_ps_mpi_tpu.errors import (FleetDeadError, FleetManifestError,
                                       FleetResumeSkewError)
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import (AsyncPSServer, _U64,
                                                _recv_frame, _send_frame,
                                                control_connect,
                                                request_promotion,
                                                request_snapshot)
from pytorch_ps_mpi_tpu.shard import (FleetManifest, PSFleet, ShardRouter,
                                      fleet_manifest_path)
from pytorch_ps_mpi_tpu.shard.fleet import shard_checkpoint_path
from pytorch_ps_mpi_tpu.utils.faults import FaultPlan
from pytorch_ps_mpi_tpu.utils.timing import format_fault_stats

REPO = Path(__file__).resolve().parent.parent


def _teacher():
    rng = np.random.RandomState(7)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _params(seed=0):
    return init_mlp(np.random.RandomState(seed), sizes=(16, 32, 4))


def _fleet(num_shards=2, quota=1, seed=0, **kw):
    fleet = PSFleet(list(_params(seed).items()), num_shards=num_shards,
                    quota=quota, optim="sgd", lr=0.05, momentum=0.5, **kw)
    fleet.compile_step(mlp_loss_fn)
    return fleet


def _router_thread(addresses, results, key, *, seed=3, pace=0.0, **kw):
    x, y = _teacher()

    def go():
        try:
            r = ShardRouter(addresses, **kw)
            inner = dataset_batch_fn(x, y, 64, seed=seed)

            def batch_fn(rank, it):
                if pace:
                    time.sleep(pace)
                return inner(rank, it)

            pushed = r.run(mlp_loss_fn, batch_fn)
            results[key] = {"pushed": pushed, "rank": r.rank,
                            "reconnects": r.reconnects,
                            "fault_stats": dict(r.fault_stats)}
        except BaseException as exc:  # noqa: BLE001 - asserted below
            results[key] = {"error": exc}

    t = threading.Thread(target=go, daemon=True, name=f"failover-{key}")
    t.start()
    return t


# ---------------------------------------------------------------------------
# FaultPlan: asymmetric link partitions
# ---------------------------------------------------------------------------

def test_fault_plan_partition_roundtrip_and_semantics():
    plan = FaultPlan(seed=3, partition_links=[[0, 1, 3, 9], [2, 0, 5, 7]])
    clone = FaultPlan.from_json(plan.to_json())
    assert clone == plan
    assert plan.any_async_faults() and plan.any_partitions()
    # Start-inclusive, heal-exclusive, per (rank, shard) link only.
    assert not plan.should_partition(0, 1, 2)
    assert plan.should_partition(0, 1, 3)
    assert plan.should_partition(0, 1, 8)
    assert not plan.should_partition(0, 1, 9)  # healed
    assert not plan.should_partition(1, 1, 5)  # other rank untouched
    assert not plan.should_partition(0, 0, 5)  # other shard untouched
    assert not FaultPlan().any_partitions()


# ---------------------------------------------------------------------------
# Hot-standby replication: lag bound + promotion with zero rewind
# ---------------------------------------------------------------------------

def test_replication_keeps_standby_within_cadence_bound():
    """With the default per-update cadence the standby ends AT the
    primary's step (lag 0); with replica_every=R it ends within R-1 —
    the rewind bound a promotion pays."""
    steps = 6
    for every, bound in ((1, 0), (3, 2)):
        fleet = _fleet(num_shards=2, quota=1, replicas=1,
                       replica_every=every)
        results = {}
        t = _router_thread(fleet.addresses, results, "w0")
        hist = fleet.serve(steps=steps, idle_timeout=60.0)
        t.join(timeout=60)
        assert "error" not in results["w0"], results["w0"]
        for k, sb in enumerate(fleet.standbys):
            assert sb.replica_step() is not None
            assert steps - sb.replica_step() <= bound, (every, k)
        fs = hist["fault_stats"]
        assert fs["repl_sent"] == 2 * (steps // every)
        assert fs["repl_received"] == fs["repl_sent"]
        assert fs["repl_lag"] == 0  # every sent frame was acked
        fleet.close()


def test_promotion_on_kill_zero_rewind_without_checkpointing():
    """kill_shard_at with checkpoint_every=0 (and NO checkpoint path at
    all) used to be fatal; with a hot standby the shard is promoted at
    its replicated step — zero update rewind, continuous versions, and
    updates_total still counts every incarnation exactly once (the
    restored_base absolute-assignment contract extended to
    promotions)."""
    steps, kill_at = 10, 4
    plan = FaultPlan(kill_shard_at={1: kill_at})
    fleet = _fleet(num_shards=2, quota=1, fault_plan=plan, replicas=1)
    results = {}
    t = _router_thread(fleet.addresses, results, "w0",
                       reconnect_retries=20, backoff_base=0.05,
                       backoff_max=0.5)
    hist = fleet.serve(steps=steps, idle_timeout=60.0)
    t.join(timeout=90)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    fs = hist["fault_stats"]
    assert fs["promotions"] == 1
    assert fs["shard_restores"] == 0  # no checkpoint rewind happened
    assert "promotions=1" in format_fault_stats(fs)
    # Zero rewind: the successor resumed at exactly the kill step...
    assert fleet._slots[1]["restored_base"] == kill_at
    # ...and served exactly the REMAINING updates with CONTINUOUS
    # versions (the replicated serving-version counter carried over).
    promoted_hist = hist["per_shard"][1]
    assert len(promoted_hist["losses"]) == steps - kill_at
    assert promoted_hist["versions"][0] == kill_at + 1
    assert promoted_hist["versions"][-1] == steps
    assert hist["updates_total"] == 2 * steps
    # The worker rode its reconnect backoff onto the SAME port.
    assert results["w0"]["reconnects"] >= 1
    # The successor is a PRIMARY now: it must arm SNAP cuts and
    # replicate onward (a promoted server stuck in the standby role
    # would silently end coordinated snapshots fleet-wide).
    assert fleet.servers[1]._standby is False
    assert fleet.servers[1].replica_addr is not None
    for srv in fleet.servers:
        for n, p in srv.params.items():
            assert np.isfinite(np.asarray(p)).all(), n
    fleet.close()


def test_snapshot_barrier_completes_after_promotion(tmp_path):
    """Failover and coordinated snapshots COMPOSE: a barrier pending on
    the killed incarnation is abandoned immediately (not after the whole
    patience window), and a later barrier completes with the PROMOTED
    server arming and writing its cut — the manifest ends up at a cut
    past the kill."""
    steps, kill_at = 16, 4
    ckpt = tmp_path / "fleet.psz"
    plan = FaultPlan(kill_shard_at={1: kill_at})
    fleet = _fleet(num_shards=2, quota=1, fault_plan=plan, replicas=1)
    results = {}
    t = _router_thread(fleet.addresses, results, "w0", pace=0.1,
                       reconnect_retries=20, backoff_base=0.05,
                       backoff_max=0.5)
    hist = fleet.serve(steps=steps, idle_timeout=60.0,
                       checkpoint_path=str(ckpt), snapshot_every=4)
    t.join(timeout=90)
    assert "error" not in results["w0"], results["w0"]
    assert hist["fault_stats"]["promotions"] == 1
    manifest = FleetManifest.from_json(
        Path(fleet_manifest_path(ckpt)).read_bytes())
    assert manifest.cut > kill_at
    assert manifest.skewed_entries() == []
    fleet.close()
    fresh = _fleet(num_shards=2, quota=1)
    assert fresh.resume_from(str(ckpt)) == [manifest.cut] * 2
    fresh.close()


def test_repl_fenced_after_promotion_and_refused_on_non_standby():
    """The PROM fence: a standby that has been promoted refuses further
    REPL (a zombie primary across a partition cannot write into the
    successor's state), and REPL at a non-standby is quarantined."""
    fleet = _fleet(num_shards=2, quota=1, replicas=1)
    try:
        standby = fleet.standbys[0]
        host, port = standby.address
        blob = b"\x01" * 8  # stash-only: promotion never applies it here
        sock = control_connect(host, port)
        _send_frame(sock, b"REPL" + _U64.pack(3) + blob)
        reply = _recv_frame(sock)
        assert reply[:4] == b"ACKR" and _U64.unpack_from(reply, 4)[0] == 3
        assert standby.replica_step() == 3
        assert standby.fault_stats["repl_received"] == 1
        # Fence it (digest 0: the plan digest the standby advertises is
        # its real one — use it).
        fence = control_connect(host, port)
        assert request_promotion(fence, fleet.plan.digest()) == 3
        fence.close()
        # The open replication stream is now refused: no ACKR, the
        # connection dies, and the refusal is counted.
        _send_frame(sock, b"REPL" + _U64.pack(4) + blob)
        with pytest.raises(ConnectionError):
            _recv_frame(sock)
        sock.close()
        assert standby.fault_stats["repl_refused"] == 1
        assert standby.replica_step() == 3  # the stash was not touched
        # Wrong-fleet PROM: digest mismatch drops the connection.
        bad = control_connect(host, port)
        _send_frame(bad, b"PROM" + _U64.pack(0xDEAD))
        with pytest.raises(ConnectionError):
            _recv_frame(bad)
        bad.close()
        # REPL at a PRIMARY (non-standby) is a protocol violation.
        fleet.servers[0]._start_accept_thread()  # no serve() in this test
        phost, pport = fleet.servers[0].address
        psock = control_connect("127.0.0.1", pport)
        _send_frame(psock, b"REPL" + _U64.pack(1) + blob)
        with pytest.raises(ConnectionError):
            _recv_frame(psock)
        psock.close()
        deadline = time.time() + 5
        while (fleet.servers[0].fault_stats["quarantined_frames"] < 1
               and time.time() < deadline):
            time.sleep(0.01)
        assert fleet.servers[0].fault_stats["quarantined_frames"] >= 1
    finally:
        fleet.close()


def test_control_connections_book_no_rank():
    """SNAP/PROM/REPL ride rank-less control connections: a fleet's own
    control traffic must not appear as a worker (identity, eviction,
    workers_seen)."""
    fleet = _fleet(num_shards=2, quota=1)
    try:
        fleet.servers[0]._start_accept_thread()  # no serve() in this test
        host, port = fleet.servers[0].address
        sock = control_connect("127.0.0.1", port)
        # A non-serving shard refuses to arm any cut (ack 0) — but the
        # round trip itself must work without minting a rank.
        assert request_snapshot(sock, 100) == 0
        sock.close()
        snap = fleet.servers[0]._fault_stats_snapshot()
        assert snap["workers_seen"] == 0
        assert snap["live_ranks"] == []
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# Coordinated snapshots: barrier cut + manifest round trip + refusals
# ---------------------------------------------------------------------------

def test_snapshot_barrier_cuts_one_consistent_version(tmp_path):
    steps = 16
    ckpt = tmp_path / "fleet.psz"
    fleet = _fleet(num_shards=2, quota=1)
    results = {}
    # Paced: the supervisor's barrier driver needs ticks between
    # updates — an unpaced tiny-MLP fleet can finish all 16 before the
    # first cut is proposed, and "the run ends first" is by-design.
    t = _router_thread(fleet.addresses, results, "w0", pace=0.1)
    hist = fleet.serve(steps=steps, idle_timeout=60.0,
                       checkpoint_path=str(ckpt), snapshot_every=4)
    t.join(timeout=60)
    assert "error" not in results["w0"], results["w0"]
    fs = hist["fault_stats"]
    assert fs["snapshot_barriers"] >= 2  # K shards x >= 1 barrier
    mpath = fleet_manifest_path(ckpt)
    manifest = FleetManifest.from_json(Path(mpath).read_bytes())
    assert manifest.num_shards == 2
    assert manifest.plan_digest == fleet.plan.digest()
    assert manifest.skewed_entries() == []  # one version fleet-wide
    assert all(int(e["step"]) == manifest.cut for e in manifest.shards)
    fleet.close()
    # Kill the ENTIRE fleet (objects gone) -> manifest resume lands every
    # shard at the one agreed cut.
    fresh = _fleet(num_shards=2, quota=1)
    starts = fresh.resume_from(str(ckpt))
    assert starts == [manifest.cut] * 2
    fresh.close()


def test_manifest_refusal_matrix(tmp_path):
    """Missing shard file, digest mismatch (tamper), skewed manifest
    steps, and a wrong-plan fleet — each refused with the typed error
    BEFORE any shard state is touched."""
    ckpt = tmp_path / "fleet.psz"
    fleet = _fleet(num_shards=2, quota=1)
    fleet.save_checkpoint(str(ckpt), step=5)  # quiescent cut + manifest
    fleet.close()
    mpath = Path(fleet_manifest_path(ckpt))
    pristine = mpath.read_bytes()
    shard0 = tmp_path / "fleet.shard0.psz"
    blob = shard0.read_bytes()

    def fresh(**kw):
        return _fleet(num_shards=2, quota=1, **kw)

    # Happy path first: the manifest round-trips.
    f = fresh()
    assert f.resume_from(str(ckpt)) == [5, 5]
    f.close()
    # (a) missing shard file
    shard0.unlink()
    f = fresh()
    with pytest.raises(FleetManifestError, match="missing"):
        f.resume_from(str(ckpt))
    f.close()
    # (b) digest mismatch: one flipped bit in the restored-to-be file
    shard0.write_bytes(blob[:-1] + bytes([blob[-1] ^ 1]))
    f = fresh()
    with pytest.raises(FleetManifestError, match="re-written"):
        f.resume_from(str(ckpt))
    f.close()
    shard0.write_bytes(blob)
    # (c) skewed steps inside the manifest (hand-edited / mixed barriers)
    import json
    doc = json.loads(pristine)
    doc["shards"][1]["step"] = 9
    mpath.write_text(json.dumps(doc))
    f = fresh()
    with pytest.raises(FleetResumeSkewError, match="different update"):
        f.resume_from(str(ckpt))
    f.close()
    mpath.write_bytes(pristine)
    # (d) a fleet with a DIFFERENT plan must refuse the whole manifest.
    f = fresh(rules=[("bias", 0)])
    with pytest.raises(FleetManifestError, match="split disagrees"):
        f.resume_from(str(ckpt))
    f.close()


def test_legacy_sibling_resume_detects_skew(tmp_path):
    """Without a manifest, per-shard siblings recorded at different
    steps (or a missing sibling among present ones) raise the typed
    skew error naming shards and versions; an even set still resumes
    and an absent set starts fresh."""
    ckpt = tmp_path / "fleet.psz"
    fleet = _fleet(num_shards=2, quota=1)
    # Skewed: shard 0 at step 4, shard 1 at step 6.
    fleet.servers[0]._auto_checkpoint(shard_checkpoint_path(ckpt, 0), 4)
    fleet.servers[1]._auto_checkpoint(shard_checkpoint_path(ckpt, 1), 6)
    fleet.close()

    f = _fleet(num_shards=2, quota=1)
    with pytest.raises(FleetResumeSkewError) as exc:
        f.resume_from(str(ckpt))
    assert "shard 0: step 4" in str(exc.value)
    assert "shard 1: step 6" in str(exc.value)
    # A missing sibling among present ones is maximal skew.
    Path(shard_checkpoint_path(ckpt, 1)).unlink()
    with pytest.raises(FleetResumeSkewError, match="missing"):
        f.resume_from(str(ckpt))
    # Even set: re-write shard 1 at the same step as shard 0.
    f.servers[1]._auto_checkpoint(shard_checkpoint_path(ckpt, 1), 4)
    assert f.resume_from(str(ckpt)) == [4, 4]
    f.close()
    # All absent: fresh start, no error.
    for k in range(2):
        Path(shard_checkpoint_path(ckpt, k)).unlink()
    f2 = _fleet(num_shards=2, quota=1)
    assert f2.resume_from(str(ckpt)) == [0, 0]
    f2.close()


# ---------------------------------------------------------------------------
# Partition tolerance: bounded degraded mode, heal without rank churn
# ---------------------------------------------------------------------------

def test_partition_degrades_then_heals_without_rank_churn():
    steps = 12
    # Worker rank 0 <-> shard 1 black-holed for its iterations 3..9.
    wplan = FaultPlan(partition_links=[[0, 1, 3, 9]])
    fleet = _fleet(num_shards=2, quota=2, quorum=1, fill_deadline=0.05)
    results = {}
    ts = [_router_thread(fleet.addresses, results, f"w{i}", seed=3 + i,
                         fault_plan=wplan, degraded_max=20)
          for i in range(2)]
    hist = fleet.serve(steps=steps, idle_timeout=60.0,
                       eviction_timeout=1.0)
    for t in ts:
        t.join(timeout=90)
    for key in results:
        assert "error" not in results[key], results[key]
    # Exactly one router was rank 0 and rode the partition in degraded
    # mode: pulls reused the frozen slice, pushes were dropped — both
    # counted — and NOTHING re-handshook (zero rank churn).
    partitioned = [r for r in results.values()
                   if r["fault_stats"]["degraded_pulls"] > 0]
    assert len(partitioned) == 1, results
    pfs = partitioned[0]["fault_stats"]
    assert pfs["degraded_pulls"] >= 6 - 1  # ~one per black-holed step
    assert pfs["partition_drops"] >= 1
    assert partitioned[0]["reconnects"] == 0
    assert format_fault_stats(pfs) != "clean"
    fs = hist["fault_stats"]
    assert fs["reconnects"] == 0
    assert fs["workers_seen"] == 2  # no phantom third identity, ever
    for k in ("0", "1"):
        assert fs["shards"][k]["live_ranks"] == [0, 1]
    fleet.close()


def test_partition_that_never_heals_escalates_bounded():
    """'Shard unreachable but fleet alive' is bounded: past degraded_max
    consecutive reused-slice pulls the router escalates to the typed
    partial-model refusal instead of training a frozen slice forever."""
    fleet = _fleet(num_shards=2, quota=1)
    serve_threads = [
        threading.Thread(
            target=lambda k=k: fleet._serve_shard(
                k, 500, dict(idle_timeout=30.0)),
            daemon=True)
        for k in range(2)]
    for t in serve_threads:
        t.start()
    x, y = _teacher()
    wplan = FaultPlan(partition_links=[[0, 1, 2, 10 ** 9]])
    r = ShardRouter(fleet.addresses, fault_plan=wplan, degraded_max=3)
    with pytest.raises(FleetDeadError, match="degraded-pull bound"):
        r.run(mlp_loss_fn, dataset_batch_fn(x, y, 64, seed=3))
    assert r.fault_stats["degraded_pulls"] == 4  # bound + the escalation
    fleet.close()
    for t in serve_threads:
        t.join(timeout=30)


# ---------------------------------------------------------------------------
# Observability: key parity extended to standbys; render coverage
# ---------------------------------------------------------------------------

def test_standby_snapshot_key_parity_and_render_coverage():
    """Every fleet snapshot — shards AND standbys — is a superset of the
    in-process base snapshot, and every integer counter in the
    aggregated view (including the new replication/partition/snapshot
    ones) renders via `format_fault_stats`."""
    import jax.numpy as jnp

    inproc = AsyncPS([("w", jnp.zeros((2,), jnp.float32))], quota=1)
    fleet = _fleet(num_shards=2, replicas=1)
    try:
        base_keys = set(inproc._base_fault_snapshot())
        agg = fleet.fleet_fault_stats()
        assert {"0", "1", "0:standby", "1:standby"} <= set(agg["shards"])
        for name, snap in agg["shards"].items():
            assert base_keys <= set(snap), (
                f"{name} snapshot missing base fields: "
                f"{sorted(base_keys - set(snap))}")
        counter_keys = set(fleet.fault_stats)
        for srv in fleet.servers + fleet.standbys:
            counter_keys |= set(srv.fault_stats)
        counter_keys |= {"partition_drops", "degraded_pulls"}  # router
        for key in sorted(counter_keys):
            if isinstance(agg.get(key, 0), int):
                assert format_fault_stats({key: 1}) != "clean", (
                    f"counter {key!r} is invisible to format_fault_stats")
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# pslint drift coverage reaches the v6 protocol surface
# ---------------------------------------------------------------------------

def test_drift_checker_catches_repl_frame_drift(tmp_path):
    """Tamper the REAL module's REPL encode literal: the one-sided kinds
    must fire PSL301 (the fixture proves detection on a toy; this proves
    the real replication path is actually in scope)."""
    import sys
    sys.path.insert(0, str(REPO))
    from tools.pslint.core import load_corpus, run_checkers

    src = (REPO / "pytorch_ps_mpi_tpu" / "multihost_async.py").read_text()
    needle = 'self._repl_session.send_data(\n                b"REPL"'
    assert needle in src  # the encode site under test (v8: session path)
    tampered = src.replace(
        needle, 'self._repl_session.send_data(\n                b"XEPL"')
    path = tmp_path / "multihost_tampered.py"
    path.write_text(tampered)
    findings = run_checkers(load_corpus([path]))
    kinds = {f.checker for f in findings
             if "REPL" in f.message or "XEPL" in f.message}
    assert "PSL301" in kinds, findings


def test_drift_checker_catches_promotions_counter_drift(tmp_path):
    import sys
    sys.path.insert(0, str(REPO))
    from tools.pslint.core import load_corpus, run_checkers

    src = (REPO / "pytorch_ps_mpi_tpu" / "shard" / "fleet.py").read_text()
    needle = 'self.fault_stats["promotions"] += 1'
    assert needle in src
    tampered = src.replace(needle,
                           'self.fault_stats["promotionz"] += 1')
    path = tmp_path / "fleet_tampered.py"
    path.write_text(tampered)
    findings = run_checkers(load_corpus([path]))
    assert any(f.checker == "PSL302" and "promotionz" in f.message
               for f in findings), findings


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------

def test_cli_refuses_misplaced_availability_flags():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="hot-standby"):
        train.main(["--model", "mlp", "--serve", "0", "--replicas", "1",
                    "--steps", "1"])
    with pytest.raises(SystemExit, match="0 or 1"):
        train.main(["--model", "mlp", "--serve", "0", "--shards", "2",
                    "--replicas", "2", "--steps", "1"])
    with pytest.raises(SystemExit, match="coordinated-snapshot"):
        train.main(["--model", "mlp", "--serve", "0",
                    "--snapshot-every", "5", "--steps", "1"])
    with pytest.raises(SystemExit, match="needs --save"):
        train.main(["--model", "mlp", "--serve", "0", "--shards", "2",
                    "--snapshot-every", "5", "--steps", "1"])
    # partition_links is a FLEET-worker (router) fault; everywhere else
    # the injected partition would silently never fire.
    chaos = FaultPlan(partition_links=[[0, 1, 2, 5]]).to_json()
    for role in (["--serve", "0"], ["--connect", "127.0.0.1:1"],
                 ["--async-ps"]):
        with pytest.raises(SystemExit, match="partition_links"):
            train.main(["--model", "mlp", "--chaos", chaos,
                        "--steps", "1"] + role)


def test_fleet_refuses_bad_replica_config():
    with pytest.raises(ValueError, match="replicas must be 0 or 1"):
        _fleet(num_shards=2, replicas=3)
    with pytest.raises(ValueError, match="snapshot_every needs"):
        fleet = _fleet(num_shards=2)
        try:
            fleet.serve(steps=1, snapshot_every=2)
        finally:
            fleet.close()
    with pytest.raises(ValueError, match="replica_every"):
        AsyncPSServer(list(_params().items()), quota=1, port=0,
                      replica_every=0)
    with pytest.raises(ValueError, match="chained replication"):
        AsyncPSServer(list(_params().items()), quota=1, port=0,
                      standby=True, replica_addr=("127.0.0.1", 1))


# ---------------------------------------------------------------------------
# Endurance: the real CLI roles, real processes, checkpoint_every=0
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_fleet_promotion_endurance(tmp_path):
    """--serve --shards 2 --replicas 1 with NO checkpointing at all and a
    kill_shard_at chaos plan: the standby is promoted (zero rewind), the
    workers ride their backoff, and everyone exits 0 — the run that was
    one crash from fatal before this layer."""
    import subprocess
    import sys as _sys

    from test_multihost_async import ChildProc, _reap_all

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    chaos = FaultPlan(kill_shard_at={1: 6}).to_json().replace("'", "\\'")
    base = ("'--model','mlp','--steps','16','--quota','1',"
            "'--batch-size','32','--n-examples','128'")

    server = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0','--shards','2','--replicas','1',{base},"
         f"'--chaos','{chaos}'])"])
    line = server.stdout.readline()
    assert line.startswith("serving on ports "), line
    ports = line.strip().split("ports ", 1)[1].split()
    assert len(ports) == 2
    connect = ",".join(f"127.0.0.1:{p}" for p in ports)

    workers = [ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--connect','{connect}',{base},"
         "'--reconnect-retries','100'])"])
        for _ in range(2)]

    outs = _reap_all([server] + workers, timeout=420)
    (s_out, s_err) = outs[0]
    assert server.returncode == 0, f"server failed:\n{s_out}\n{s_err}"
    assert "promoted standby for shard 1" in s_err, s_err
    assert "promotions=1" in s_err, s_err
    for w, (w_out, w_err) in zip(workers, outs[1:]):
        assert w.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"
        assert "gradients pushed" in w_err
