"""Shard evidence run — the K-shard PS fleet vs the single PS.

Acceptance evidence for the sharded parameter-server fleet (ISSUE 6):
every scenario drives the REAL multihost TCP stack in-process (shard
servers on serve threads, `shard.ShardRouter` workers on threads — the
same harness shape as CHAOS_EVIDENCE):

* ``single_ps_quota4``   — the pre-fleet operating point: one PS, quota
                           4, four plain workers (the ``multihost_cpu``
                           rung's topology);
* ``fleet_k4_throughput``— the same model, fleet of K=4 shards, quota 4,
                           four shard routers: each shard's update moves
                           1/K of the bytes, so AGGREGATE updates/sec
                           must come out >= 2x the single PS (sharding
                           parallelizes the wire bottleneck even before
                           the protocol rewrite of ROADMAP item 1);
* ``fleet_chaos``        — the chaos acceptance suite composed per
                           shard: a deterministic straggler (quorum +
                           fill-deadline short fills), a 100x-scale
                           Byzantine rank (norm_clip + anomaly
                           quarantine), and ``kill_shard_at`` (shard 1
                           dies mid-run, the fleet restores it from its
                           own auto-checkpoint while routers reconnect)
                           — at tail-loss parity < 2x vs the single PS.

Writes ``benchmarks/SHARD_EVIDENCE.json``.  Deterministic under
``--seed`` (fault schedules and data streams; wall-clock and exact
staleness remain host-dependent, as in any async run).

Usage: ``python benchmarks/shard_evidence.py [--save] [--seed N]``
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
from pytorch_ps_mpi_tpu.multihost_async import (AsyncPSWorker,  # noqa: E402
                                                AsyncSGDServer)
from pytorch_ps_mpi_tpu.shard import PSFleet, ShardRouter  # noqa: E402
from pytorch_ps_mpi_tpu.utils.faults import FaultPlan  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 30
K = 4
WORKERS = 4


def _teacher(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _named_params(seed):
    return list(init_mlp(np.random.RandomState(seed),
                         sizes=(16, 32, 4)).items())


def _tail_loss(losses, k=10):
    return float(np.mean(losses[-k:]))


def _spawn(target, key, results):
    def go():
        try:
            results[key] = target()
        except BaseException as exc:  # noqa: BLE001 - recorded as evidence
            results[key] = {"error": repr(exc)}

    t = threading.Thread(target=go, daemon=True, name=f"shard-ev-{key}")
    t.start()
    return t


def scenario_single_ps(seed):
    """The pre-fleet operating point: one PS, quota 4, four workers."""
    srv = AsyncSGDServer(_named_params(seed), lr=0.05, momentum=0.5,
                         quota=WORKERS)
    srv.compile_step(mlp_loss_fn)
    x, y = _teacher(7)
    results: dict = {}
    threads = []
    for i in range(WORKERS):
        def work(i=i):
            w = AsyncPSWorker("127.0.0.1", srv.address[1])
            return {"pushed": w.run(
                mlp_loss_fn, dataset_batch_fn(x, y, 64, seed=seed + i))}
        threads.append(_spawn(work, f"w{i}", results))
    hist = srv.serve(steps=STEPS, idle_timeout=120.0)
    for t in threads:
        t.join(timeout=120)
    wall = hist["wall_time"]
    return {
        "quota": WORKERS,
        "workers": WORKERS,
        "updates": len(hist["losses"]),
        "updates_per_sec": round(len(hist["losses"]) / wall, 3),
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(wall, 2),
        "fault_stats": hist["fault_stats"],
    }


def _run_fleet(seed, *, fleet_kw=None, serve_kw=None, worker_plans=None,
               router_kw=None):
    """One fleet run: K shards, WORKERS shard routers; returns (history,
    per-worker results)."""
    fleet = PSFleet(_named_params(seed), num_shards=K, quota=WORKERS,
                    optim="sgd", lr=0.05, momentum=0.5,
                    **(fleet_kw or {}))
    fleet.compile_step(mlp_loss_fn)
    x, y = _teacher(7)
    results: dict = {}
    threads = []
    for i in range(WORKERS):
        def work(i=i):
            plan = (worker_plans or {}).get(i)
            r = ShardRouter(fleet.addresses, fault_plan=plan,
                            **(router_kw or {}))
            return {"rank": r.rank,
                    "pushed": r.run(mlp_loss_fn,
                                    dataset_batch_fn(x, y, 64,
                                                     seed=seed + i)),
                    "reconnects": r.reconnects}
        threads.append(_spawn(work, f"w{i}", results))
    hist = fleet.serve(steps=STEPS, idle_timeout=120.0,
                       **(serve_kw or {}))
    for t in threads:
        t.join(timeout=120)
    return hist, results


def scenario_fleet_throughput(seed):
    hist, results = _run_fleet(seed)
    wall = hist["wall_time"]
    return {
        "num_shards": K,
        "quota": WORKERS,
        "workers": WORKERS,
        "updates_per_shard": STEPS,
        "aggregate_updates": hist["updates_total"],
        "aggregate_updates_per_sec": round(hist["updates_total"] / wall,
                                           3),
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(wall, 2),
        "fault_stats": {k: v for k, v in hist["fault_stats"].items()
                        if k != "shards"},
        "workers_detail": results,
    }


def scenario_fleet_chaos(seed, tmpdir):
    """Straggler + Byzantine + shard death, composed per shard."""
    ckpt = os.path.join(tmpdir, "shard_chaos.psz")
    fleet_plan = FaultPlan(seed=seed, kill_shard_at={1: 10})
    # The SAME plan goes to EVERY worker (the robust_evidence pattern):
    # ranks are minted by shard-0 connection arrival order, so keying
    # plans by thread index would only attack when scheduling happens to
    # hand thread 1 rank 1 — whichever router IS rank 1 must attack.
    worker_plan = FaultPlan(seed=seed, byzantine_rank=1,
                            byzantine_mode="scale", byzantine_scale=100.0,
                            slow_rank=2, slow_delay_s=0.2)
    hist, results = _run_fleet(
        seed,
        fleet_kw=dict(fault_plan=fleet_plan, quorum=2, fill_deadline=0.1,
                      aggregate="norm_clip", anomaly_z=4.0),
        serve_kw=dict(checkpoint_path=ckpt, checkpoint_every=5),
        worker_plans={i: worker_plan for i in range(WORKERS)},
        router_kw=dict(reconnect_retries=40, backoff_base=0.05,
                       backoff_max=0.5))
    fs = hist["fault_stats"]
    per_shard_steps = [len(h["losses"]) if h else 0
                       for h in hist["per_shard"]]
    return {
        "num_shards": K,
        "faults": {"kill_shard_at": {1: 10}, "byzantine_rank": 1,
                   "byzantine_scale": 100.0, "slow_rank": 2,
                   "slow_delay_s": 0.2},
        "defense": {"aggregate": "norm_clip", "quorum": 2,
                    "fill_deadline": 0.1, "anomaly_z": 4.0,
                    "checkpoint_every": 5},
        "steps_per_shard": per_shard_steps,
        "shard_restores": fs.get("shard_restores", 0),
        "quorum_fills": fs.get("quorum_fills", 0),
        "robust_clipped": fs.get("robust_clipped", 0),
        "reconnects": fs.get("reconnects", 0),
        "final_loss": _tail_loss(hist["losses"]),
        "wall_time_s": round(hist["wall_time"], 2),
        "fault_stats": {k: v for k, v in fs.items() if k != "shards"},
        "workers_detail": results,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--save", action="store_true",
                    help="write benchmarks/SHARD_EVIDENCE.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import tempfile

    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory() as tmpdir:
        single = scenario_single_ps(args.seed)
        fleet = scenario_fleet_throughput(args.seed)
        chaos = scenario_fleet_chaos(args.seed, tmpdir)
    speedup = (fleet["aggregate_updates_per_sec"]
               / max(single["updates_per_sec"], 1e-9))
    chaos_ratio = chaos["final_loss"] / max(single["final_loss"], 1e-9)
    out = {
        "seed": args.seed,
        "steps_per_scenario": STEPS,
        "scenarios": {
            "single_ps_quota4": single,
            "fleet_k4_throughput": fleet,
            "fleet_chaos": chaos,
        },
        # The two acceptance gates: sharding parallelizes the wire
        # bottleneck (>= 2x aggregate updates/sec at quota 4), and the
        # full chaos suite completes at tail-loss parity < 2x.
        "aggregate_updates_speedup_vs_single": round(speedup, 2),
        "speedup_ok": bool(speedup >= 2.0),
        "chaos_loss_ratio_vs_single": round(chaos_ratio, 3),
        "chaos_loss_parity_ok": bool(chaos_ratio < 2.0),
        "chaos_completed": bool(
            chaos["shard_restores"] >= 1
            and all(s > 0 for s in chaos["steps_per_shard"])),
        "total_wall_time_s": round(time.perf_counter() - t0, 2),
    }
    print(json.dumps(out, indent=1))
    if args.save:
        path = os.path.join(_HERE, "SHARD_EVIDENCE.json")
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
