"""Hierarchy evidence run — two-level fault-contained aggregation.

Acceptance evidence for the hierarchical aggregation tier (ISSUE 8):
both scenarios drive the REAL multihost TCP stack in-process (root serve
thread, `shard.hierarchy.LocalAggregator` threads, `GroupWorker`
threads — the CHAOS_EVIDENCE harness shape) with a 12-worker fleet in
G=3 groups of 4:

* ``hier_faultfree``  — the operating point: the root consumes ~G
                        pre-reduced AGGR frames per update instead of 12
                        raw gradients (the sub-linear-scaling claim),
                        with the adaptive fill-deadline tightening below
                        its configured ceiling on the fast fleet
                        (``deadline_adapted``);
* ``hier_chaos``      — the composition suite: group 0's AGGREGATOR is
                        killed mid-run with restarts disabled (its 4
                        workers fail over to DIRECT root connections —
                        ``agg_failovers`` / ``direct_fallbacks``), group
                        1 hosts a 100x-scale Byzantine rank (quarantined
                        by its GROUP scoreboard; the root scoreboard
                        must never fire — containment), and group 2
                        hosts a deterministic straggler (absorbed by
                        GROUP-level quorum + latency down-weighting,
                        ``latency_weighted``) — completing at tail-loss
                        parity < 2x vs the fault-free run.

Writes ``benchmarks/HIER_EVIDENCE.json``.  Deterministic under
``--seed`` (fault schedules and data streams; wall-clock and exact fill
timing remain host-dependent, as in any async run).

Usage: ``python benchmarks/hier_evidence.py [--save] [--seed N]``
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
from pytorch_ps_mpi_tpu.multihost_async import AsyncSGDServer  # noqa: E402
from pytorch_ps_mpi_tpu.shard import GroupWorker, Hierarchy  # noqa: E402
from pytorch_ps_mpi_tpu.utils.faults import FaultPlan  # noqa: E402
from pytorch_ps_mpi_tpu.utils.timing import format_fault_stats  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 24
GROUPS = 3
GROUP_SIZE = 4
WORKERS = GROUPS * GROUP_SIZE


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


def _run_hier(seed, *, hier_plan=None, worker_plans=None,
              max_restarts=0):
    """One hierarchical run: root PS on a thread, GROUPS aggregators,
    WORKERS GroupWorkers.  Returns (root history, tier view, per-worker
    results)."""
    # fill_deadline is the adaptive CEILING: generous on purpose — the
    # point of --adaptive-deadline is that the effective deadline tracks
    # the live fleet p95 (x1.5) underneath it, so the evidence proves
    # the adaptation engaged (deadline_adapted > 0) instead of the
    # ceiling doing the work.
    # Root anomaly threshold sits ABOVE the group's (6 vs 4): the root
    # scores pre-reduced FRAMES whose norms are legitimately
    # heterogeneous (contribution-weighted groups, latency-damped
    # stragglers, direct-fallback raw gradients), so its scoreboard is
    # the lying-AGGREGATOR backstop, not the first line — a leaked 100x
    # attack still scores z >> 6, while honest frame-mix variance stays
    # under it.
    # lr is tuned for the SUM-scale update of 12 contributions (the
    # repo's decode_sum contract: step magnitude scales with the
    # total contributor count, so a 12-worker hierarchy runs a
    # smaller lr than the quota-4 evidence rigs).
    root = AsyncSGDServer(_named_params(seed), lr=0.015, momentum=0.5,
                          quota=GROUPS, quorum=2, fill_deadline=30.0,
                          adaptive_deadline=True, anomaly_z=6.0)
    root.compile_step(mlp_loss_fn)
    out: dict = {}

    def serve():
        try:
            out["hist"] = root.serve(steps=STEPS, idle_timeout=180.0)
        except BaseException as exc:  # noqa: BLE001 - recorded as evidence
            out["error"] = exc

    rt = threading.Thread(target=serve, daemon=True, name="hier-ev-root")
    rt.start()
    hier = Hierarchy(_named_params(seed), groups=GROUPS,
                     group_size=GROUP_SIZE,
                     upstream=[("127.0.0.1", root.address[1])],
                     fault_plan=hier_plan, max_restarts=max_restarts,
                     aggregate="norm_clip", anomaly_z=4.0,
                     quorum=3, fill_deadline=30.0,
                     adaptive_deadline=True, latency_weighting=True)
    hier.compile()
    x, y = _teacher(7)
    results: dict = {}
    threads = []
    for g in range(GROUPS):
        for i in range(GROUP_SIZE):
            def work(g=g, i=i):
                plan = (worker_plans or {}).get(g)
                gw = GroupWorker(
                    hier.addresses[g][0], hier.addresses[g][1],
                    root_endpoints=[("127.0.0.1", root.address[1])],
                    group=g, fault_plan=plan, reconnect_retries=4,
                    backoff_base=0.05, backoff_max=0.3)
                try:
                    pushed = gw.run(
                        mlp_loss_fn,
                        dataset_batch_fn(x, y, 64,
                                         seed=seed + 10 * g + i))
                    return {"pushed": pushed, "rank": gw.rank,
                            "direct_rank": gw.direct_rank,
                            "stats": dict(gw.fault_stats)}
                finally:
                    gw.close()

            def go(key=f"g{g}w{i}", fn=work):
                try:
                    results[key] = fn()
                except BaseException as exc:  # noqa: BLE001 - evidence
                    results[key] = {"error": repr(exc)}

            t = threading.Thread(target=go, daemon=True,
                                 name=f"hier-ev-g{g}w{i}")
            t.start()
            threads.append(t)
    view = hier.serve(idle_timeout=180.0)
    rt.join(timeout=300)
    for t in threads:
        t.join(timeout=300)
    if "error" in out:
        raise out["error"]
    return out["hist"], view, results


def scenario_faultfree(seed):
    hist, view, results = _run_hier(seed)
    fs = hist["fault_stats"]
    tier = view["fault_stats"]
    contribs = [len(c) for c in hist["contributors"]]
    adapted = (fs.get("deadline_adapted", 0)
               + tier.get("deadline_adapted", 0))
    return {
        "workers": WORKERS, "groups": GROUPS,
        "updates": len(hist["losses"]),
        "initial_loss": float(np.mean(hist["losses"][:4])),
        "final_loss": _tail_loss(hist["losses"]),
        "mean_root_contributors_per_update": round(
            float(np.mean(contribs)), 2),
        "max_root_contributors_per_update": int(np.max(contribs)),
        "agg_frames": fs.get("agg_frames", 0),
        "deadline_adapted": adapted,
        "wall_time_s": round(hist["wall_time"], 2),
        "rendered": format_fault_stats(fs),
        "fault_stats": {k: v for k, v in fs.items() if k != "groups"},
    }


def scenario_chaos(seed):
    """Aggregator kill (-> direct fallback) x group-contained Byzantine
    x straggler, in one 12-worker G=3 run."""
    hier_plan = FaultPlan(seed=seed, kill_agg_at={0: 6})
    worker_plans = {
        1: FaultPlan(seed=seed, byzantine_rank=1,
                     byzantine_mode="scale", byzantine_scale=100.0),
        2: FaultPlan(seed=seed, slow_rank=0, slow_delay_s=0.25),
    }
    hist, view, results = _run_hier(seed, hier_plan=hier_plan,
                                    worker_plans=worker_plans,
                                    max_restarts=0)
    fs = hist["fault_stats"]
    tier = view["fault_stats"]
    g1 = tier["groups"]["1"]
    failover_stats = [results[f"g0w{i}"].get("stats", {})
                      for i in range(GROUP_SIZE)]
    return {
        "faults": {"kill_agg_at": {0: 6}, "byzantine": "group 1 local "
                   "rank 1 @ 100x", "straggler": "group 2 local rank 0 "
                   "@ 0.25s"},
        "defense": {"group_aggregate": "norm_clip", "group_anomaly_z":
                    4.0, "group_quorum": 3, "root_quorum": 2,
                    "adaptive_deadline": True, "latency_weighting": True,
                    "max_restarts": 0},
        "updates": len(hist["losses"]),
        "initial_loss": float(np.mean(hist["losses"][:4])),
        "final_loss": _tail_loss(hist["losses"]),
        "group1_quarantine_events": g1.get("quarantine_events", 0),
        "group1_quarantined_ranks": g1.get("quarantined_ranks", []),
        "root_quarantine_events": fs.get("quarantine_events", 0),
        "root_quarantined_ranks": fs.get("quarantined_ranks", []),
        "direct_fallbacks": fs.get("direct_fallbacks", 0),
        "agg_failovers": sum(s.get("agg_failovers", 0)
                             for s in failover_stats),
        "fallback_ranks": sorted(
            fs.get("groups", {}).get("0", {}).get("fallback_ranks", [])),
        "group_quorum_fills": tier.get("quorum_fills", 0),
        "latency_weighted": tier.get("latency_weighted", 0),
        "deadline_adapted": (fs.get("deadline_adapted", 0)
                             + tier.get("deadline_adapted", 0)),
        "wall_time_s": round(hist["wall_time"], 2),
        "rendered_root": format_fault_stats(fs),
        "rendered_tier": format_fault_stats(tier),
        "fault_stats": {k: v for k, v in fs.items() if k != "groups"},
        "workers_detail": results,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--save", action="store_true",
                    help="write benchmarks/HIER_EVIDENCE.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    faultfree = scenario_faultfree(args.seed)
    chaos = scenario_chaos(args.seed)
    loss_ratio = chaos["final_loss"] / max(faultfree["final_loss"], 1e-9)
    out = {
        "seed": args.seed,
        "steps_per_scenario": STEPS,
        "topology": {"workers": WORKERS, "groups": GROUPS,
                     "group_size": GROUP_SIZE, "root_quota": GROUPS},
        "scenarios": {
            "hier_faultfree": faultfree,
            "hier_chaos": chaos,
        },
        # The acceptance gates (ISSUE 8): root fill traffic is ~G frames
        # per update (not W raw gradients); the full chaos composition
        # completes at tail-loss parity < 2x; the Byzantine rank is
        # quarantined by its GROUP scoreboard with the root scoreboard
        # silent; the killed group's workers complete via DIRECT
        # fallback; and the adaptive-deadline / latency-weighting /
        # failover counters all fired and render.
        # The hierarchical trainer must actually TRAIN: the
        # fault-free run's tail loss sits below its head (an
        # upward-drifting "fault-free" baseline would make every
        # ratio gate meaningless).
        "faultfree_converged_ok": bool(
            faultfree["final_loss"] < faultfree["initial_loss"]),
        "root_traffic_ok": bool(
            faultfree["mean_root_contributors_per_update"]
            <= GROUPS + 0.5
            and faultfree["max_root_contributors_per_update"]
            < WORKERS // 2),
        "chaos_loss_ratio_vs_faultfree": round(loss_ratio, 3),
        "chaos_loss_parity_ok": bool(loss_ratio < 2.0),
        "containment_ok": bool(
            chaos["group1_quarantine_events"] >= 1
            and chaos["root_quarantine_events"] == 0),
        "failover_ok": bool(
            chaos["direct_fallbacks"] == GROUP_SIZE
            and chaos["agg_failovers"] == GROUP_SIZE
            and chaos["updates"] == STEPS),
        "adaptive_deadline_ok": bool(
            faultfree["deadline_adapted"] >= 1),
        "latency_weighted_ok": bool(chaos["latency_weighted"] >= 1),
        "counters_rendered_ok": bool(
            "direct_fallbacks=" in chaos["rendered_root"]
            and "agg_forwards=" in chaos["rendered_tier"]),
        "total_wall_time_s": round(time.perf_counter() - t0, 2),
    }
    print(json.dumps(out, indent=1, default=str))
    if args.save:
        path = os.path.join(_HERE, "HIER_EVIDENCE.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1, default=str)
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
