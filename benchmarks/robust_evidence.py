"""Robust-aggregation / quorum evidence run — ISSUE 4 acceptance.

Every scenario drives the REAL multihost TCP stack (an `AsyncSGDServer`
serving in-process, `AsyncPSWorker`s on threads) under a deterministic
`utils.faults.FaultPlan`:

* ``baseline``          — fault-free 3-worker reference: step throughput
                          and converged loss the others compare to;
* ``straggler_stall``   — one of three workers pays a deterministic
                          per-gradient delay and NO quorum is configured:
                          the fill rate drops to what the two fast ranks
                          supply (the cost being defended against);
* ``straggler_quorum``  — same straggler, quorum=2 + fill deadline: short
                          fills keep the update rate at >= 80 % of the
                          fault-free run with loss parity < 2x;
* ``byzantine_mean``    — one rank pushes 100x-scaled (finite!) gradients
                          under plain ``mean``: the run demonstrably
                          degrades (loss blows up or goes non-finite) —
                          ``skip_nonfinite`` cannot catch a finite attack;
* ``byzantine_trimmed`` — same attack under ``trimmed_mean`` + anomaly
                          quarantine: the attacker is trimmed/quarantined
                          and the run converges within 2x baseline loss;
* ``duplicate_bitwise`` — a single worker whose every 2nd GRAD frame is
                          wire-duplicated vs. a dup-free control: repeats
                          land in ``duplicate_dropped`` and the final
                          parameters are BITWISE identical.

Writes ``benchmarks/ROBUST_EVIDENCE.json``.  Deterministic under
``--seed`` (fault schedules and data streams; wall-clock throughput is
host-dependent, which is why the straggler claims are ratios against the
same-host baseline).

Usage: ``python benchmarks/robust_evidence.py [--save] [--seed N]``
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
from pytorch_ps_mpi_tpu.utils.faults import FaultPlan  # noqa: E402

_HERE = os.path.dirname(os.path.abspath(__file__))
STEPS = 30
# Straggler scenarios: every worker's gradient computation is paced at
# PACE_S (the stand-in for a real model's grad time — without it a CPU
# MLP grad is so cheap the PS, not the fleet, is the bottleneck and a
# straggler is invisible); the straggler additionally pays SLOW_DELAY_S
# per gradient via the FaultPlan injector.
PACE_S = 0.15
SLOW_DELAY_S = 1.0
FILL_DEADLINE_S = 0.05


def _teacher(seed):
    rng = np.random.RandomState(seed)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _server(seed, quota, **kw):
    params = init_mlp(np.random.RandomState(seed), sizes=(16, 32, 4))
    srv = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                         quota=quota, **kw)
    srv.compile_step(mlp_loss_fn)
    return srv


def _spawn_worker(port, seed, results, key, pace_s=0.0, **kw):
    x, y = _teacher(7)

    def go():
        try:
            inner = dataset_batch_fn(x, y, 64, seed=seed)

            def batch_fn(rank, it):
                if pace_s:
                    time.sleep(pace_s)  # models real grad-compute time
                return inner(rank, it)

            w = AsyncPSWorker("127.0.0.1", port, **kw)
            pushed = w.run(mlp_loss_fn, batch_fn)
            results[key] = {"pushed": pushed, "rank": w.rank}
        except BaseException as exc:  # noqa: BLE001 - recorded as evidence
            results[key] = {"error": repr(exc)}

    t = threading.Thread(target=go, daemon=True, name=f"robust-{key}")
    t.start()
    return t


def _tail_loss(losses, k=10):
    return float(np.mean(losses[-k:]))


def _run_fleet(seed, *, n_workers=3, plan=None, pace_s=0.0, steps=STEPS,
               **server_kw):
    srv = _server(seed, quota=n_workers, **server_kw)
    results: dict = {}
    threads = [_spawn_worker(srv.address[1], seed + i, results, f"w{i}",
                             pace_s=pace_s, fault_plan=plan)
               for i in range(n_workers)]
    t0 = time.perf_counter()
    hist = srv.serve(steps=steps, idle_timeout=120.0)
    wall = time.perf_counter() - t0
    for t in threads:
        t.join(timeout=120)
    fs = hist["fault_stats"]
    return {
        "steps_survived": len(hist["losses"]),
        "completed_all_steps": len(hist["losses"]) == steps,
        "grads_consumed": hist["grads_consumed"],
        "updates_per_sec": round(steps / wall, 2),
        "final_loss": _tail_loss(hist["losses"]),
        "final_loss_finite": bool(np.isfinite(hist["losses"]).all()),
        "fault_stats": fs,
        "workers": results,
    }, hist, srv


def scenario_warmup(seed):
    """Untimed throwaway fleet: pays the process's jit/transport warmup so
    the BASELINE measurement (first timed scenario) isn't biased slow —
    which would flatter every later throughput ratio."""
    _run_fleet(seed, steps=5)


def scenario_baseline(seed):
    out, _, _ = _run_fleet(seed, pace_s=PACE_S)
    return out


def scenario_straggler_stall(seed):
    """The undefended cost: rank 2 pays SLOW_DELAY_S extra per gradient
    and the quota must still fill to 3 — the fleet's gradient supply
    drops by the straggler's whole share."""
    plan = FaultPlan(seed=seed, slow_rank=2, slow_delay_s=SLOW_DELAY_S)
    out, _, _ = _run_fleet(seed, plan=plan, pace_s=PACE_S)
    return out


def scenario_straggler_quorum(seed):
    """The defense: same straggler, but quorum=2 + a fill deadline close
    fills short, renormalized to the fill target; the straggler's late
    frames fold into later fills instead of costing the fill its missing
    share."""
    plan = FaultPlan(seed=seed, slow_rank=2, slow_delay_s=SLOW_DELAY_S)
    out, _, _ = _run_fleet(seed, plan=plan, pace_s=PACE_S, quorum=2,
                           fill_deadline=FILL_DEADLINE_S)
    return out


def scenario_byzantine_mean(seed):
    """One of three ranks pushes 100x-scaled gradients; plain mean has
    breakdown point 0 — the attacker steers every update.  (Workers are
    paced here too: an unthrottled 4-thread fleet hammering the single
    shared CPU device can stall the runtime's transfer path — a harness
    artifact; deployed workers are separate processes.)"""
    plan = FaultPlan(seed=seed, byzantine_rank=1, byzantine_mode="scale",
                     byzantine_scale=100.0)
    out, _, _ = _run_fleet(seed, plan=plan, pace_s=0.05)
    return out


def scenario_byzantine_trimmed(seed):
    plan = FaultPlan(seed=seed, byzantine_rank=1, byzantine_mode="scale",
                     byzantine_scale=100.0)
    out, _, _ = _run_fleet(seed, plan=plan, pace_s=0.05,
                           aggregate="trimmed_mean",
                           trim_k=1, anomaly_z=4.0)
    return out


def scenario_duplicate_bitwise(seed):
    """A deterministic scripted client streams the SAME gradient sequence
    twice — once clean, once with every frame wire-duplicated: the
    per-rank seq dedup must make the server consume identical admitted
    sequences, so the final parameters are BITWISE equal.  (A live async
    worker cannot carry this oracle: AsySG's pull/push timing makes the
    gradient stream itself timing-dependent, dup or no dup — the scripted
    client isolates exactly the dedup property.)"""
    import socket as _socket
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu.multihost_async import (_BKT, _F64, _U64,
                                                    _recv_frame,
                                                    _send_frame)
    from pytorch_ps_mpi_tpu.native import serializer

    rng = np.random.default_rng(seed)
    shapes = init_mlp(np.random.RandomState(seed), sizes=(16, 32, 4))
    stream = [OrderedDict(
        (n, (0.01 * rng.standard_normal(np.shape(p))).astype(np.float32))
        for n, p in shapes.items()) for _ in range(STEPS)]

    def one(dup):
        srv = _server(seed, quota=1)
        served: dict = {}
        th = threading.Thread(
            target=lambda: served.update(h=srv.serve(steps=STEPS,
                                                     idle_timeout=120.0)),
            daemon=True)
        th.start()
        sock = _socket.create_connection(("127.0.0.1", srv.address[1]))
        try:
            _send_frame(sock, b"HELO\x00")
            _recv_frame(sock)  # PSA
            for i, tree in enumerate(stream):
                blob = serializer.dumps(tree, level=0)
                frame = (b"GRAD" + _BKT.pack(0, 1) + _U64.pack(i)
                         + _U64.pack(i) + _F64.pack(0.5) + blob)
                _send_frame(sock, frame)
                if dup:
                    _send_frame(sock, frame)  # the wire duplicate
            th.join(timeout=180)
        finally:
            sock.close()
        params = {n: np.asarray(p) for n, p in srv.params.items()}
        return params, served["h"]

    clean_params, clean_hist = one(dup=False)
    dup_params, dup_hist = one(dup=True)
    bitwise = all(np.array_equal(clean_params[n], dup_params[n])
                  for n in clean_params)
    return {
        "steps": STEPS,
        "duplicate_dropped": dup_hist["fault_stats"]["duplicate_dropped"],
        "clean_run_duplicates": clean_hist["fault_stats"][
            "duplicate_dropped"],
        "final_params_bitwise_equal": bool(bitwise),
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--save", action="store_true",
                    help="write benchmarks/ROBUST_EVIDENCE.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    scenario_warmup(args.seed)
    out = {
        "seed": args.seed,
        "steps_per_scenario": STEPS,
        "worker_pace_s": PACE_S,
        "straggler_delay_s": SLOW_DELAY_S,
        "fill_deadline_s": FILL_DEADLINE_S,
        "scenarios": {
            "baseline": scenario_baseline(args.seed),
            "straggler_stall": scenario_straggler_stall(args.seed),
            "straggler_quorum": scenario_straggler_quorum(args.seed),
            "byzantine_mean": scenario_byzantine_mean(args.seed),
            "byzantine_trimmed": scenario_byzantine_trimmed(args.seed),
            "duplicate_bitwise": scenario_duplicate_bitwise(args.seed),
        },
    }
    sc = out["scenarios"]
    base = sc["baseline"]

    # Straggler acceptance: quorum recovers >= 80 % of fault-free step
    # throughput with loss parity < 2x; the stall run documents the
    # undefended cost on the same host.
    for name in ("straggler_stall", "straggler_quorum"):
        sc[name]["throughput_vs_baseline"] = round(
            sc[name]["updates_per_sec"] / base["updates_per_sec"], 3)
        ratio = sc[name]["final_loss"] / max(base["final_loss"], 1e-9)
        sc[name]["loss_ratio_vs_baseline"] = round(ratio, 3)
    sc["straggler_quorum"]["recovers_80pct_throughput"] = bool(
        sc["straggler_quorum"]["throughput_vs_baseline"] >= 0.8)
    sc["straggler_quorum"]["loss_parity_ok"] = bool(
        sc["straggler_quorum"]["loss_ratio_vs_baseline"] < 2.0)

    # Byzantine acceptance: trimmed_mean converges within 2x baseline
    # while plain mean demonstrably degrades (non-finite or way off).
    mean_loss = sc["byzantine_mean"]["final_loss"]
    mean_degraded = (not sc["byzantine_mean"]["final_loss_finite"]
                     or mean_loss > 10.0 * max(base["final_loss"], 1e-9))
    sc["byzantine_mean"]["demonstrably_degraded"] = bool(mean_degraded)
    tr_ratio = (sc["byzantine_trimmed"]["final_loss"]
                / max(base["final_loss"], 1e-9))
    sc["byzantine_trimmed"]["loss_ratio_vs_baseline"] = round(tr_ratio, 3)
    sc["byzantine_trimmed"]["loss_parity_ok"] = bool(tr_ratio < 2.0)

    out["acceptance"] = {
        "straggler_quorum_recovers_80pct": sc["straggler_quorum"][
            "recovers_80pct_throughput"],
        "straggler_quorum_loss_parity": sc["straggler_quorum"][
            "loss_parity_ok"],
        "byzantine_mean_degrades": sc["byzantine_mean"][
            "demonstrably_degraded"],
        "byzantine_trimmed_converges": sc["byzantine_trimmed"][
            "loss_parity_ok"],
        "duplicates_dropped_bitwise": bool(
            sc["duplicate_bitwise"]["duplicate_dropped"] > 0
            and sc["duplicate_bitwise"]["final_params_bitwise_equal"]),
    }
    out["all_acceptance_met"] = all(out["acceptance"].values())
    out["total_wall_time_s"] = round(time.perf_counter() - t0, 2)

    print(json.dumps(out, indent=1))
    if args.save:
        path = os.path.join(_HERE, "ROBUST_EVIDENCE.json")
        with open(path, "w") as f:
            json.dump(out, f, indent=1)
            f.write("\n")
        print(f"wrote {path}", file=sys.stderr)
    # Hard exit: the threaded in-process fleets can leave daemon worker
    # threads mid-XLA-dispatch, and interpreter teardown against them can
    # hang (observed as a post-print hang with no Python frame) or abort.
    # The evidence is already flushed; skip teardown.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


if __name__ == "__main__":
    main()
