"""Failover evidence run — fleet availability under kill/partition chaos.

Acceptance evidence for the fleet-consistent snapshot + hot-standby
replication layer (ISSUE 7); every scenario drives the REAL multihost
TCP stack in-process (shard servers + standbys on threads,
`shard.ShardRouter` workers on threads — the SHARD_EVIDENCE harness
shape):

* ``fault_free``       — the parity baseline: K=2 fleet, 2 routers, no
                         chaos, no replication;
* ``promotion``        — a primary killed mid-run with **no
                         checkpointing at all** (``checkpoint_every=0``,
                         no path): the hot standby is PROM-fenced and
                         promoted on the primary's port within one fill
                         gap — ZERO update rewind (the successor resumes
                         at exactly the kill step), loss parity < 2x;
* ``snapshot_resume``  — coordinated SNAP barrier cuts a fleet snapshot
                         mid-run; the ENTIRE fleet is then killed and a
                         fresh fleet resumes through the
                         ``ckpt.fleet.json`` manifest: every shard at
                         the one agreed cut, restored slices
                         BITWISE-equal to the cut's files (sha256);
* ``partition_chaos``  — two links black-holed (healing mid-run) + a
                         deterministic straggler: the routers ride
                         bounded degraded mode (``degraded_pulls > 0``)
                         instead of dying with ``FleetDeadError``, and
                         tail loss stays < 2x the fault-free baseline.

Writes ``benchmarks/FAILOVER_EVIDENCE.json``.  Deterministic under
``--seed`` (fault schedules and data streams; wall-clock and exact
staleness remain host-dependent, as in any async run).

Usage: ``python benchmarks/failover_evidence.py [--save] [--seed N]``
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from pytorch_ps_mpi_tpu.async_ps import dataset_batch_fn  # noqa: E402
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn  # noqa: E402
from pytorch_ps_mpi_tpu.shard import (FleetManifest, PSFleet,  # noqa: E402
                                      ShardRouter, fleet_manifest_path)
from pytorch_ps_mpi_tpu.utils import checkpoint as ckpt_util  # noqa: E402
from pytorch_ps_mpi_tpu.utils.faults import FaultPlan  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 24
K = 2
WORKERS = 2


def _teacher(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _named_params(seed):
    return list(init_mlp(np.random.RandomState(seed),
                         sizes=(16, 32, 4)).items())


def _tail_loss(losses, k=8):
    return float(np.mean(losses[-k:]))


def _spawn(target, key, results):
    def go():
        try:
            results[key] = target()
        except BaseException as exc:  # noqa: BLE001 - recorded as evidence
            results[key] = {"error": repr(exc)}

    t = threading.Thread(target=go, daemon=True, name=f"failover-ev-{key}")
    t.start()
    return t


def _run_fleet(seed, *, steps=STEPS, fleet_kw=None, serve_kw=None,
               worker_plan=None, router_kw=None, pace=0.0):
    """One fleet run: K shards, WORKERS shard routers; returns (history,
    per-worker results, the fleet — still open, caller closes)."""
    fleet = PSFleet(_named_params(seed), num_shards=K, quota=WORKERS,
                    optim="sgd", lr=0.05, momentum=0.5,
                    **(fleet_kw or {}))
    fleet.compile_step(mlp_loss_fn)
    x, y = _teacher(7)
    results: dict = {}
    threads = []
    for i in range(WORKERS):
        def work(i=i):
            r = ShardRouter(fleet.addresses, fault_plan=worker_plan,
                            **(router_kw or {}))
            inner = dataset_batch_fn(x, y, 64, seed=seed + i)

            def batch_fn(rank, it):
                if pace:
                    time.sleep(pace)
                return inner(rank, it)

            return {"rank": r.rank,
                    "pushed": r.run(mlp_loss_fn, batch_fn),
                    "reconnects": r.reconnects,
                    "fault_stats": dict(r.fault_stats)}
        threads.append(_spawn(work, f"w{i}", results))
    hist = fleet.serve(steps=steps, idle_timeout=120.0,
                       eviction_timeout=2.0, **(serve_kw or {}))
    for t in threads:
        t.join(timeout=120)
    return hist, results, fleet


def scenario_fault_free(seed):
    hist, results, fleet = _run_fleet(seed)
    fleet.close()
    return {
        "updates_total": hist["updates_total"],
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(hist["wall_time"], 2),
        "workers_detail": results,
    }


def scenario_promotion(seed):
    """Primary kill at update 10 with NO checkpointing anywhere: only
    the hot standby stands between the fleet and ShardDeadError."""
    kill_at = 10
    plan = FaultPlan(seed=seed, kill_shard_at={1: kill_at})
    hist, results, fleet = _run_fleet(
        seed,
        fleet_kw=dict(fault_plan=plan, replicas=1),
        router_kw=dict(reconnect_retries=40, backoff_base=0.05,
                       backoff_max=0.5))
    fs = hist["fault_stats"]
    promoted_start = fleet._slots[1]["restored_base"]
    promoted_hist = hist["per_shard"][1] or {}
    fleet.close()
    return {
        "kill_shard_at": {1: kill_at},
        "checkpointing": "OFF (checkpoint_every=0, no path)",
        "promotions": fs.get("promotions", 0),
        "shard_restores": fs.get("shard_restores", 0),
        "promoted_resume_step": promoted_start,
        "rewind_updates": kill_at - promoted_start,
        "promoted_segment_versions": [
            promoted_hist.get("versions", [None])[0],
            promoted_hist.get("versions", [None])[-1]],
        "updates_total": hist["updates_total"],
        "repl_sent": fs.get("repl_sent", 0),
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(hist["wall_time"], 2),
        "workers_detail": results,
    }


def scenario_snapshot_resume(seed, tmpdir):
    """Coordinated snapshot -> kill the ENTIRE fleet -> manifest resume
    with every shard at one verified cut, bitwise-equal to the files the
    barrier wrote."""
    base = os.path.join(tmpdir, "failover_fleet.psz")
    hist, results, fleet = _run_fleet(
        seed, serve_kw=dict(checkpoint_path=base, snapshot_every=6),
        pace=0.1)
    fs = hist["fault_stats"]
    # Kill the whole fleet: every object discarded, nothing survives but
    # the snapshot files + manifest.
    fleet.close()
    del fleet
    mpath = fleet_manifest_path(base)
    with open(mpath, "rb") as f:
        manifest = FleetManifest.from_json(f.read())
    base_dir = os.path.dirname(os.path.abspath(mpath))
    digests_ok = all(
        ckpt_util.file_digest(os.path.join(base_dir, e["path"]))
        == e["sha256"] for e in manifest.shards)
    fresh = PSFleet(_named_params(seed), num_shards=K, quota=WORKERS,
                    optim="sgd", lr=0.05, momentum=0.5)
    fresh.compile_step(mlp_loss_fn)
    starts = fresh.resume_from(base)
    # Bitwise proof: every restored slice equals the cut file's arrays.
    bitwise_ok = True
    for k, srv in enumerate(fresh.servers):
        tree, _meta = ckpt_util.load(
            os.path.join(base_dir, manifest.entry(k)["path"]),
            with_meta=True)
        for name, arr in tree["params"].items():
            if not np.array_equal(np.asarray(srv.params[name]),
                                  np.asarray(arr)):
                bitwise_ok = False
    fresh.close()
    return {
        "snapshot_every": 6,
        "snapshot_barriers": fs.get("snapshot_barriers", 0),
        "manifest_cut": manifest.cut,
        "resume_steps": starts,
        "one_version_fleetwide": len(set(starts)) == 1
        and starts[0] == manifest.cut,
        "manifest_digests_verified": digests_ok,
        "restored_slices_bitwise_equal": bitwise_ok,
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(hist["wall_time"], 2),
        "workers_detail": results,
    }


def scenario_partition_chaos(seed):
    """Two links black-holed (healing mid-run) + a straggler: degraded
    mode instead of FleetDeadError, at tail-loss parity."""
    worker_plan = FaultPlan(seed=seed,
                            partition_links=[[0, 1, 4, 12], [1, 0, 6, 14]],
                            slow_rank=1, slow_delay_s=0.15)
    hist, results, fleet = _run_fleet(
        seed,
        fleet_kw=dict(quorum=1, fill_deadline=0.1),
        worker_plan=worker_plan,
        router_kw=dict(degraded_max=20))
    fs = hist["fault_stats"]
    fleet.close()
    degraded = sum(r.get("fault_stats", {}).get("degraded_pulls", 0)
                   for r in results.values() if isinstance(r, dict))
    drops = sum(r.get("fault_stats", {}).get("partition_drops", 0)
                for r in results.values() if isinstance(r, dict))
    return {
        "faults": {"partition_links": [[0, 1, 4, 12], [1, 0, 6, 14]],
                   "slow_rank": 1, "slow_delay_s": 0.15},
        "defense": {"quorum": 1, "fill_deadline": 0.1,
                    "degraded_max": 20},
        "degraded_pulls": degraded,
        "partition_drops": drops,
        "reconnects": fs.get("reconnects", 0),
        "updates_total": hist["updates_total"],
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(hist["wall_time"], 2),
        "workers_detail": results,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--save", action="store_true",
                    help="write benchmarks/FAILOVER_EVIDENCE.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import tempfile

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmpdir:
        baseline = scenario_fault_free(args.seed)
        promo = scenario_promotion(args.seed)
        snap = scenario_snapshot_resume(args.seed, tmpdir)
        chaos = scenario_partition_chaos(args.seed)
    promo_ratio = promo["final_loss"] / max(baseline["final_loss"], 1e-9)
    chaos_ratio = chaos["final_loss"] / max(baseline["final_loss"], 1e-9)
    out = {
        "seed": args.seed,
        "steps_per_scenario": STEPS,
        "scenarios": {
            "fault_free": baseline,
            "promotion": promo,
            "snapshot_resume": snap,
            "partition_chaos": chaos,
        },
        # Gate (a): promotion with ZERO update rewind and no checkpoint,
        # at loss parity < 2x.
        "promotion_zero_rewind": bool(
            promo["promotions"] == 1 and promo["rewind_updates"] == 0
            and promo["updates_total"] == K * STEPS),
        "promotion_loss_ratio_vs_fault_free": round(promo_ratio, 3),
        "promotion_loss_parity_ok": bool(promo_ratio < 2.0),
        # Gate (b): manifest resume provably at one consistent cut.
        "snapshot_consistent_cut": bool(
            snap["one_version_fleetwide"]
            and snap["manifest_digests_verified"]
            and snap["restored_slices_bitwise_equal"]),
        # Gate (c): partition+straggler completes in degraded mode.
        "partition_completed_degraded": bool(
            chaos["degraded_pulls"] > 0
            and chaos["updates_total"] == K * STEPS),
        "partition_loss_ratio_vs_fault_free": round(chaos_ratio, 3),
        "partition_loss_parity_ok": bool(chaos_ratio < 2.0),
        "total_wall_time_s": round(time.perf_counter() - t0, 2),
    }
    print(json.dumps(out, indent=1))
    if args.save:
        path = os.path.join(_HERE, "FAILOVER_EVIDENCE.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(f"wrote {path}", file=sys.stderr)
    # Hard exit: interpreter teardown against daemon worker threads that
    # are still mid-dispatch can hang or abort (the CHAOS_EVIDENCE
    # precedent) — the artifact is on disk, nothing of value is lost.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
