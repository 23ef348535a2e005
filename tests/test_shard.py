"""Sharded PS fleet (`pytorch_ps_mpi_tpu.shard`): partition plans, the
worker-side router, and the supervised K-shard fleet.

The oracles mirror the subsystem's contracts: a plan is rule-driven with
a size-balanced greedy fallback and both sides agree on it at HELO time
(digest refusal, not a shape error mid-run); one worker has ONE
fleet-wide rank on every shard; per-shard versions advance
independently; a shard killed by the chaos plan is restored from its own
auto-checkpoint while workers ride their reconnect backoff; and every
fault counter any shard carries renders through the same
``format_fault_stats`` line as a single PS.  In-process (serve threads +
router threads) so the tier-1 lane stays fast; the real-process CLI
endurance run is ``slow``-marked.
"""

import threading
from collections import OrderedDict
from pathlib import Path

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.async_ps import AsyncPS, dataset_batch_fn
from pytorch_ps_mpi_tpu.errors import ShardDeadError
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import AsyncPSWorker
from pytorch_ps_mpi_tpu.shard import (PSFleet, ShardPlan, ShardRouter,
                                      build_shard_plan,
                                      match_partition_rules)
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


def _start_accept_loops(fleet):
    """Run the shards' accept loops without serve() — enough transport
    for handshake-refusal tests (HELO/PSA/SPLN are conn-thread work)."""
    for srv in fleet.servers:
        threading.Thread(target=srv._accept_loop, daemon=True).start()


def _router_thread(addresses, results, key, *, seed=3, **kw):
    x, y = _teacher()

    def go():
        try:
            r = ShardRouter(addresses, **kw)
            pushed = r.run(mlp_loss_fn,
                           dataset_batch_fn(x, y, 64, seed=seed))
            results[key] = {"pushed": pushed, "rank": r.rank,
                            "reconnects": r.reconnects}
        except BaseException as exc:  # noqa: BLE001 - asserted below
            results[key] = {"error": exc}

    t = threading.Thread(target=go, daemon=True, name=f"router-{key}")
    t.start()
    return t


# ---------------------------------------------------------------------------
# Partition plans
# ---------------------------------------------------------------------------

def test_match_partition_rules_first_match_wins_and_validates_range():
    names = ["enc/w", "enc/b", "dec/w"]
    out = match_partition_rules([("w$", 1), ("enc", 0)], names, 2)
    # enc/w hits "w$" FIRST (ordered rules), never the later "enc" rule.
    assert out == {"enc/w": 1, "enc/b": 0, "dec/w": 1}
    # Unmatched names map to None (greedy fallback input, not an error).
    assert match_partition_rules([("nope", 0)], names, 2) \
        == {n: None for n in names}
    with pytest.raises(ValueError, match="out of range"):
        match_partition_rules([("w$", 5)], names, 2)


def test_build_shard_plan_greedy_balances_sizes():
    params = [(f"p{i}", np.zeros((s,), np.float32))
              for i, s in enumerate([512, 256, 256, 64, 32, 16])]
    plan = build_shard_plan(params, 2)
    # Largest-first onto the lightest shard: loads end up near-equal.
    assert plan.num_shards == 2
    assert max(plan.sizes) <= 2 * min(plan.sizes)
    # Deterministic: the same input yields the same plan (and digest).
    again = build_shard_plan(params, 2)
    assert again.assignment == plan.assignment
    assert again.digest() == plan.digest()
    # Canonical order preserved for reassembly.
    assert list(plan.assignment) == [n for n, _ in params]


def test_build_shard_plan_rules_plus_greedy_fallback_compose():
    params = [("a/w", np.zeros((100,), np.float32)),
              ("a/b", np.zeros((100,), np.float32)),
              ("z/big", np.zeros((1000,), np.float32))]
    # The rules pin a/* to shard 1; the greedy fallback must then put the
    # big unmatched leaf on shard 0 (the lighter one), not re-balance the
    # ruled leaves away.
    plan = build_shard_plan(params, 2, rules=[("^a/", 1)])
    assert plan.shard_of("a/w") == 1 and plan.shard_of("a/b") == 1
    assert plan.shard_of("z/big") == 0


def test_shard_plan_validation_refuses_bad_fleets():
    params = list(_params().items())
    with pytest.raises(ValueError, match="exceeds the"):
        build_shard_plan(params, len(params) + 1)
    # Rules that leave a shard empty are a misconfigured fleet.
    with pytest.raises(ValueError, match="own no parameters"):
        ShardPlan(num_shards=2,
                  assignment=OrderedDict((n, 0) for n, _ in params))
    with pytest.raises(ValueError, match="out of range"):
        ShardPlan(num_shards=2, assignment=OrderedDict([("w", 7)]))


def test_shard_plan_json_roundtrip_and_digest_sensitivity():
    plan = build_shard_plan(list(_params().items()), 2)
    clone = ShardPlan.from_json(plan.to_json())
    assert clone.assignment == plan.assignment
    assert clone.digest() == plan.digest()
    # A different split MUST hash differently (the HELO-time refusal).
    other = build_shard_plan(list(_params().items()), 2,
                             rules=[("bias", 0)])
    assert other.assignment != plan.assignment
    assert other.digest() != plan.digest()


def test_shard_checkpoint_path_siblings():
    assert shard_checkpoint_path("ckpt.psz", 3) == "ckpt.shard3.psz"
    assert shard_checkpoint_path("/tmp/a/ckpt.psz", 0) \
        == "/tmp/a/ckpt.shard0.psz"


def test_fault_plan_kill_shard_roundtrip_and_shard_view():
    plan = FaultPlan(seed=3, kill_shard_at={1: 4}, slow_rank=0,
                     slow_delay_s=0.1)
    clone = FaultPlan.from_json(plan.to_json())
    assert clone == plan
    assert plan.any_async_faults()
    assert plan.should_kill_shard(1, 4) and not plan.should_kill_shard(0, 4)
    # The shard's view: its own death becomes kill_ps_at (shard death
    # reuses the PS crash machinery); other shards see no kill; the
    # worker-side faults pass through.
    v1 = plan.shard_view(1)
    assert v1.kill_ps_at == 4 and v1.kill_shard_at == {}
    assert v1.slow_rank == 0
    assert plan.shard_view(0).kill_ps_at is None


# ---------------------------------------------------------------------------
# HELO-time agreement: shard triple + plan digest refusals
# ---------------------------------------------------------------------------

def test_plain_worker_refuses_fleet_shard():
    fleet = _fleet(num_shards=2)
    _start_accept_loops(fleet)
    try:
        with pytest.raises(ValueError, match="2-shard PS fleet"):
            AsyncPSWorker("127.0.0.1", fleet.addresses[0][1])
    finally:
        fleet.close()


def test_router_refuses_swapped_endpoints_and_wrong_count():
    fleet = _fleet(num_shards=2)
    _start_accept_loops(fleet)
    try:
        with pytest.raises(ValueError, match="endpoint order mismatch"):
            ShardRouter(list(reversed(fleet.addresses)))
        with pytest.raises(ValueError, match="every shard exactly once"):
            ShardRouter(fleet.addresses[:1])
    finally:
        fleet.close()


def test_router_refuses_plan_digest_mismatch_across_fleets():
    """Endpoints mixing two fleets whose plans split the tree
    differently must be refused at connect time — before any gradient is
    split two different ways."""
    fleet_a = _fleet(num_shards=2)
    fleet_b = _fleet(num_shards=2, rules=[("bias", 0)])
    _start_accept_loops(fleet_a)
    _start_accept_loops(fleet_b)
    try:
        mixed = [fleet_a.addresses[0], fleet_b.addresses[1]]
        with pytest.raises(ValueError, match="digest mismatch"):
            ShardRouter(mixed)
    finally:
        fleet_a.close()
        fleet_b.close()


# ---------------------------------------------------------------------------
# The fleet trains; one worker identity fleet-wide; per-shard versions
# ---------------------------------------------------------------------------

def test_fleet_trains_with_router_workers_and_pinned_identity():
    steps = 8
    fleet = _fleet(num_shards=2, quota=2)
    results = {}
    ts = [_router_thread(fleet.addresses, results, f"w{i}", seed=3 + i)
          for i in range(2)]
    hist = fleet.serve(steps=steps, idle_timeout=60.0)
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive()
    for key in ("w0", "w1"):
        assert "error" not in results[key], results[key]
        assert results[key]["pushed"] >= steps
        assert results[key]["reconnects"] == 0
    # ONE fleet-wide identity per worker: shard 0 minted ranks 0/1, every
    # shard books the same pair — eviction/seq/scoreboard accounting
    # names the same worker everywhere.
    assert sorted(results[k]["rank"] for k in results) == [0, 1]
    fs = hist["fault_stats"]
    for k in ("0", "1"):
        assert fs["shards"][k]["live_ranks"] == [0, 1]
        assert fs["shards"][k]["workers_seen"] == 2
        assert fs["shards"][k]["reconnects"] == 0  # assigned != reconnect
    # Every shard applied every update on its own version counter.
    for shard_hist in hist["per_shard"]:
        assert len(shard_hist["losses"]) == steps
        assert shard_hist["versions"][-1] == steps
        assert all(np.isfinite(shard_hist["losses"]))
    assert hist["updates_total"] == 2 * steps
    # The fleet view renders through the same one-line formatter.
    assert isinstance(format_fault_stats(fs), str)


def test_fleet_composes_quorum_per_shard_with_straggler():
    """PR 4's straggler tolerance composes per shard: a deterministically
    slow worker makes quorum fills close short on BOTH shards, and the
    run still completes every update."""
    steps = 6
    plan = FaultPlan(slow_rank=1, slow_delay_s=0.3)
    # 5 ms: on the v9 zero-copy wire the healthy worker alone can fill
    # quota=2 inside the old 50 ms deadline (cycle ~4 ms), which made
    # short fills — the scenario under test — never happen.
    fleet = _fleet(num_shards=2, quota=2, quorum=1, fill_deadline=0.005)
    results = {}
    ts = [_router_thread(fleet.addresses, results, f"w{i}", seed=3 + i,
                         fault_plan=plan)
          for i in range(2)]
    hist = fleet.serve(steps=steps, idle_timeout=60.0)
    for t in ts:
        t.join(timeout=90)
    for key in results:
        assert "error" not in results[key], results[key]
    fs = hist["fault_stats"]
    assert fs["quorum_fills"] >= 1  # aggregated across shards
    assert hist["updates_total"] == 2 * steps


# ---------------------------------------------------------------------------
# kill_shard_at: shard death -> restore from its own checkpoint
# ---------------------------------------------------------------------------

def test_kill_shard_crash_resume_workers_reconnect(tmp_path):
    steps = 10
    ckpt = tmp_path / "fleet.psz"
    plan = FaultPlan(kill_shard_at={1: 4})
    fleet = _fleet(num_shards=2, quota=1, fault_plan=plan)
    results = {}
    t = _router_thread(fleet.addresses, results, "w0",
                       reconnect_retries=20, backoff_base=0.05,
                       backoff_max=0.5)
    hist = fleet.serve(steps=steps, idle_timeout=60.0,
                       checkpoint_path=str(ckpt), checkpoint_every=2)
    t.join(timeout=90)
    assert not t.is_alive()
    assert "error" not in results["w0"], results["w0"]
    fs = hist["fault_stats"]
    assert fs["shard_restores"] == 1
    assert "shard_restores=1" in format_fault_stats(fs)
    # The worker rode its backoff across the shard restart.
    assert results["w0"]["reconnects"] >= 1
    assert fs["reconnects"] >= 1
    # Shard 1 resumed from its own step-4 auto-checkpoint and served the
    # REMAINING updates; shard 0 never blinked.
    assert len(hist["per_shard"][0]["losses"]) == steps
    assert len(hist["per_shard"][1]["losses"]) == steps - 4
    # Each shard checkpoints its own sibling.
    names = {p.name for p in tmp_path.iterdir()}
    assert {"fleet.shard0.psz", "fleet.shard1.psz"} <= names
    for srv in fleet.servers:
        for n, p in srv.params.items():
            assert np.isfinite(np.asarray(p)).all(), n


@pytest.mark.parametrize("ckpt_mode", ["none", "path_but_every_0"])
def test_kill_shard_without_live_checkpointing_fails_loudly(tmp_path,
                                                            ckpt_mode):
    """A shard death with no checkpoint to restore from — none
    configured, or a path with checkpoint_every=0 (nothing is ever
    written mid-run, so a 'restore' would silently reset the slice to
    construction-time params) — must stop the fleet with a typed error,
    not limp on K-1 shards or relaunch from scratch."""
    plan = FaultPlan(kill_shard_at={0: 1})
    fleet = _fleet(num_shards=2, quota=1, fault_plan=plan)
    results = {}
    t = _router_thread(fleet.addresses, results, "w0",
                       reconnect_retries=2, backoff_base=0.05,
                       backoff_max=0.2)
    serve_kw = {} if ckpt_mode == "none" else {
        "checkpoint_path": str(tmp_path / "f.psz")}
    with pytest.raises(ShardDeadError, match="cannot be restored"):
        fleet.serve(steps=6, idle_timeout=5.0, **serve_kw)
    fleet.close()
    t.join(timeout=60)


def test_router_refuses_to_train_partial_model():
    """A shard that becomes unreachable (reconnect budget exhausted)
    while the rest of the fleet still serves must fail the worker
    loudly: continuing would train with that slice frozen at its last
    pulled values and report success."""
    import time as _time

    from pytorch_ps_mpi_tpu.errors import FleetDeadError

    fleet = _fleet(num_shards=2, quota=1)
    results = {}
    x, y = _teacher()

    def go():
        try:
            r = ShardRouter(fleet.addresses, reconnect_retries=2,
                            backoff_base=0.02, backoff_max=0.1)
            inner = dataset_batch_fn(x, y, 64, seed=3)

            def batch_fn(rank, it):
                _time.sleep(0.05)  # keep the run alive past the close
                return inner(rank, it)

            results["out"] = r.run(mlp_loss_fn, batch_fn)
        except BaseException as exc:  # noqa: BLE001 - asserted below
            results["error"] = exc

    t = threading.Thread(target=go, daemon=True)
    serve_t = threading.Thread(
        target=lambda: fleet._serve_shard(0, 200, dict(idle_timeout=30.0)),
        daemon=True)
    serve1_t = threading.Thread(
        target=lambda: fleet._serve_shard(1, 200, dict(idle_timeout=30.0)),
        daemon=True)
    serve_t.start()
    serve1_t.start()
    t.start()
    _time.sleep(1.0)
    # Die like a real crash: the _dying latch makes pending PULLs vanish
    # with no DONE courtesy (a plain close() answers DONE, which the
    # router rightly treats as a clean per-shard shutdown).
    fleet.servers[1]._dying = True
    fleet.servers[1].close()  # shard 1 gone for good; shard 0 serves on
    t.join(timeout=60)
    assert not t.is_alive()
    fleet.close()
    serve_t.join(timeout=30)
    serve1_t.join(timeout=30)
    assert isinstance(results.get("error"), FleetDeadError), results
    assert "partial model" in str(results["error"])


# ---------------------------------------------------------------------------
# Fleet snapshot key parity + render coverage (PR 5 satellite, extended)
# ---------------------------------------------------------------------------

def test_fleet_snapshot_key_parity_and_render_coverage():
    """Every shard's fault snapshot is a superset of the in-process base
    snapshot (a field added to `_base_fault_snapshot` must reach every
    shard's history), and every integer counter in the AGGREGATED fleet
    view renders via `format_fault_stats` — a fleet counter invisible in
    the one-line summary is the PR 4 drift incident at fleet scale."""
    import jax.numpy as jnp

    inproc = AsyncPS([("w", jnp.zeros((2,), jnp.float32))], quota=1)
    fleet = _fleet(num_shards=2)
    try:
        base_keys = set(inproc._base_fault_snapshot())
        for k, srv in enumerate(fleet.servers):
            shard_keys = set(srv._fault_stats_snapshot())
            assert base_keys <= shard_keys, (
                f"shard {k} snapshot missing base fields: "
                f"{sorted(base_keys - shard_keys)}")
        agg = fleet.fleet_fault_stats()
        assert "shard_restores" in agg
        assert set(agg["shards"]) == {"0", "1"}
        # Every COUNTER in the aggregated view must render (audit fields
        # like workers_seen/live_ranks ride along but are not counters —
        # the same distinction PR 5's single-PS parity test draws).
        counter_keys = set(fleet.fault_stats)
        for srv in fleet.servers:
            counter_keys |= set(srv.fault_stats)
        for key, value in agg.items():
            if key not in counter_keys or not isinstance(value, int):
                continue
            assert format_fault_stats({key: 1}) != "clean", (
                f"fleet counter {key!r} is invisible to "
                f"format_fault_stats")
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# pslint drift coverage reaches the shard modules (not silently in scope)
# ---------------------------------------------------------------------------

def test_drift_checker_catches_real_shard_frame_drift(tmp_path):
    """Prove the PSL301 frame checker actually covers `shard/router.py`:
    tamper the real module's SPLN encode literal and the checker must
    flag the one-sided kinds.  (The untampered module is covered by the
    whole-tree lint gate.)"""
    import sys
    sys.path.insert(0, str(REPO))
    from tools.pslint.core import load_corpus, run_checkers

    src = (REPO / "pytorch_ps_mpi_tpu" / "shard" / "router.py").read_text()
    assert 'link._send(b"SPLN")' in src  # the encode site under test
    tampered = src.replace('link._send(b"SPLN")', 'link._send(b"XPLN")')
    assert tampered != src
    path = tmp_path / "router_tampered.py"
    path.write_text(tampered)
    findings = run_checkers(load_corpus([path]))
    kinds = {(f.checker, "XPLN" in f.message or "SPLN" in f.message)
             for f in findings}
    assert ("PSL301", True) in kinds, findings


def test_drift_checker_catches_shard_counter_drift(tmp_path):
    """And the PSL302 counter checker covers `shard/fleet.py`: rename the
    bump of ``shard_restores`` away from its init and the checker must
    flag the uninitialized bump."""
    import sys
    sys.path.insert(0, str(REPO))
    from tools.pslint.core import load_corpus, run_checkers

    src = (REPO / "pytorch_ps_mpi_tpu" / "shard" / "fleet.py").read_text()
    needle = 'self.fault_stats["shard_restores"] += 1'
    assert needle in src
    tampered = src.replace(needle,
                           'self.fault_stats["shard_restorez"] += 1')
    path = tmp_path / "fleet_tampered.py"
    path.write_text(tampered)
    findings = run_checkers(load_corpus([path]))
    assert any(f.checker == "PSL302" and "shard_restorez" in f.message
               for f in findings), findings


# ---------------------------------------------------------------------------
# CLI wiring
# ---------------------------------------------------------------------------

def test_cli_refuses_misplaced_shard_flags():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="--shards must be >= 1"):
        train.main(["--model", "mlp", "--serve", "0", "--shards", "0",
                    "--steps", "1"])
    with pytest.raises(SystemExit, match="sharded PS FLEET"):
        train.main(["--model", "mlp", "--shards", "2", "--steps", "1"])
    with pytest.raises(SystemExit, match="sharded PS FLEET"):
        train.main(["--model", "mlp", "--async-ps", "--shards", "2",
                    "--steps", "1"])
    with pytest.raises(SystemExit, match="PS-side"):
        train.main(["--model", "mlp", "--connect", "127.0.0.1:1",
                    "--partition-rules", "[]", "--steps", "1"])
    # A single PS has nothing to partition: rules on --serve without
    # --shards >= 2 would be silently inert.
    with pytest.raises(SystemExit, match="sharded-only"):
        train.main(["--model", "mlp", "--serve", "0",
                    "--partition-rules", "[]", "--steps", "1"])
    with pytest.raises(SystemExit, match="not valid JSON"):
        train.main(["--model", "mlp", "--serve", "0", "--shards", "2",
                    "--partition-rules", "{oops", "--steps", "1"])
    # kill_shard_at names a FLEET shard; on a plain PS (or a worker) the
    # injected death would never fire — refuse the silently-inert plan.
    chaos = FaultPlan(kill_shard_at={0: 3}).to_json()
    for role in (["--serve", "0"], ["--connect", "127.0.0.1:1"]):
        with pytest.raises(SystemExit, match="kill_shard_at"):
            train.main(["--model", "mlp", "--chaos", chaos,
                        "--steps", "1"] + role)
    # ...and the inverse: kill_ps_at on a fleet names no shard and would
    # be silently dropped by shard_view.
    with pytest.raises(SystemExit, match="kill_ps_at is ambiguous"):
        train.main(["--model", "mlp", "--serve", "0", "--shards", "2",
                    "--chaos", FaultPlan(kill_ps_at=3).to_json(),
                    "--steps", "1"])


def test_fleet_refuses_ambiguous_kill_ps_at():
    with pytest.raises(ValueError, match="kill_ps_at is ambiguous"):
        _fleet(num_shards=2, fault_plan=FaultPlan(kill_ps_at=3))


@pytest.mark.slow
def test_cli_fleet_endurance_kill_shard(tmp_path):
    """The full sharded workflow through the REAL CLI roles, separate
    processes: --serve --shards 2 with a kill_shard_at chaos plan and
    auto-checkpointing, two router workers connecting by the PORT+k
    convention; the fleet restores the dead shard from its own
    checkpoint, the workers ride their backoff, and everyone exits 0."""
    import subprocess
    import sys as _sys

    from test_multihost_async import ChildProc, _reap_all

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    ckpt = str(tmp_path / "cli_fleet.psz")
    chaos = FaultPlan(kill_shard_at={1: 6}).to_json().replace("'", "\\'")
    base = ("'--model','mlp','--steps','16','--quota','1',"
            "'--batch-size','32','--n-examples','128'")

    server = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0','--shards','2',{base},'--save','{ckpt}',"
         f"'--checkpoint-every','2','--chaos','{chaos}'])"])
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
    assert "restored shard 1" in s_err, s_err
    assert "shard_restores=1" in s_err, s_err
    for w, (w_out, w_err) in zip(workers, outs[1:]):
        assert w.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"
        assert "gradients pushed" in w_err
