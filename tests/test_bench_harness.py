"""Unit tests for the bench harness's host-side machinery: the compact
record line, the plan registry, the attention-slope guard, the bucket
planner the collectives lowering rides on — and the exit codes: a workload
that raises, or a run without the chip, must fail the process.
"""

import importlib.util
import json
import os
import sys

import numpy as np
import pytest


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_plan_buckets_groups_by_dtype_and_caps_bytes():
    from pytorch_ps_mpi_tpu.parallel.collectives import _plan_buckets

    import jax.numpy as jnp

    leaves = [jnp.zeros(100, jnp.float32),    # 400 B
              jnp.zeros(50, jnp.int32),       # 200 B
              jnp.zeros(200, jnp.float32),    # 800 B
              jnp.zeros(5000, jnp.float32),   # 20 kB > cap: own bucket
              jnp.zeros(10, jnp.float32)]     # 40 B
    plan = _plan_buckets(leaves, bucket_bytes=1500)
    # Every leaf appears exactly once.
    flat = sorted(i for b in plan for i in b)
    assert flat == [0, 1, 2, 3, 4]
    for b in plan:
        dtypes = {str(leaves[i].dtype) for i in b}
        assert len(dtypes) == 1  # same-dtype buckets only
        if len(b) > 1:  # multi-leaf buckets respect the cap
            assert sum(leaves[i].size * leaves[i].dtype.itemsize
                       for i in b) <= 1500
    # The oversized leaf is alone in its bucket.
    assert [3] in plan
    # Deterministic: same input, same plan.
    assert plan == _plan_buckets(leaves, bucket_bytes=1500)


def test_tpu_plan_workers_all_registered(bench):
    for name in bench._TPU_PLAN:
        assert name in bench._WORKERS, name
    assert "cpu_suite" in bench._WORKERS
    assert bench._CPU_WORKERS <= set(bench._WORKERS)


def _fat_artifact():
    """A maximal full record: every workload landed AND errors rode along
    — the shape whose unbounded serialization once made the printed line
    unparseable in a 2000-char tail capture."""
    wl = {"images_per_sec_per_chip": 29682.0, "mfu": 0.41, "loss": 2.1,
          "world": 1, "batch_per_chip": 4096,
          "batch_sweep": [{"batch_per_chip": b,
                           "images_per_sec_per_chip": 1.0 * b}
                          for b in (1024, 4096)]}
    extra = {"backend": "tpu", "device_kind": "TPU v5 lite", "mfu": 0.41,
             "wall_s": 1433.2, "throughput": dict(wl),
             "baseline": {"note": "n" * 400}}
    for name in ("throughput_blockq", "lm_throughput", "resnet50",
                 "async_resnet18", "attention", "kernels", "gradsync",
                 "gradsync_virtual", "multihost_cpu", "async_virtual"):
        extra[name] = {**wl, "detail": {"nested": ["z" * 50] * 20}}
    extra["errors"] = {"worker": ["tail: " + "x" * 800],
                       "probe": ["attempt: " + "y" * 500]}
    return {"metric": "resnet18_cifar10_sync_ps_throughput",
            "value": 29682.0, "unit": "images/sec/chip",
            "vs_baseline": 12.3, "extra": extra}


def test_compact_line_is_capped_and_parseable(bench):
    line = bench._compact_line(_fat_artifact(), ["/tmp/full.json"])
    assert len(line) <= bench.HEADLINE_LINE_CAP
    d = json.loads(line)
    assert d["value"] == 29682.0 and d["unit"] == "images/sec/chip"
    # The essential numbers ride in the line itself, not only the pointer.
    assert d["extra"]["throughput"]["images_per_sec_per_chip"] == 29682.0
    assert d["extra"]["full_results"] == "/tmp/full.json"
    # Error tails are truncated, never the raw multi-hundred-char dumps.
    for v in d["extra"].get("errors", {}).values():
        assert len(str(v)) <= 100


def test_compact_line_prunes_to_fit_pathological_extra(bench):
    """Even an adversarially fat artifact (huge strings in every slot that
    survives summarization) must come out under the cap and parseable."""
    full = _fat_artifact()
    full["extra"]["headline_provenance"] = "p" * 5000
    full["extra"]["errors"] = {f"k{i}": ["e" * 300] for i in range(40)}
    line = bench._compact_line(full, ["/tmp/full.json"])
    assert len(line) <= bench.HEADLINE_LINE_CAP
    assert json.loads(line)["value"] == 29682.0


def test_compact_line_empty_failure_case(bench):
    full = {"metric": "m", "value": 0.0, "unit": "u", "vs_baseline": 0.0,
            "extra": {"errors": {"harness": ["t" * 900]}}}
    line = bench._compact_line(full, [])
    assert len(line) <= bench.HEADLINE_LINE_CAP
    assert json.loads(line)["value"] == 0.0


def test_attention_slope_validity_judged_unrounded(bench):
    """bench.py attention guard: a real but tiny positive slope must not
    be flagged invalid because the 3-decimal report rounds it to 0.0 —
    and a tiny negative slope must not round into a clean-looking 0.0."""
    n_short, n_long, gn_short, gn_long = 48, 256, 16, 96

    def mk_best(fwd_slope_s, step_slope_s):
        return {("fwd", "a", n_short): 1.0,
                ("fwd", "a", n_long): 1.0 + fwd_slope_s * (n_long - n_short),
                ("step", "a", gn_short): 1.0,
                ("step", "a", gn_long): 1.0 + step_slope_s
                * (gn_long - gn_short)}

    # 0.4 us/call: rounds to 0.0 ms in the report but is VALID.
    fwd_u, step_u, ms, step_ms, _raw, bad = bench._attention_slopes(
        mk_best(4e-7, 4e-7), ["a"], n_short, n_long, gn_short, gn_long)
    assert bad == set()
    assert ms["a"] == 0.0 and step_ms["a"] == 0.0   # report rounds
    assert fwd_u["a"] > 0 and step_u["a"] > 0       # truth doesn't

    # A tiny NEGATIVE slope is invalid even though it also rounds to 0.0.
    *_only, bad = bench._attention_slopes(
        mk_best(-4e-7, 4e-7), ["a"], n_short, n_long, gn_short, gn_long)
    assert any(b.startswith("fwd:a:") for b in bad)


def test_worker_exits_nonzero_when_the_workload_raises(bench, monkeypatch,
                                                       capsys):
    """`bench.py --worker NAME`: a raising workload is exit code 5 and an
    ``ok: false`` record, never a zero exit."""
    def boom():
        raise RuntimeError("workload exploded")
    monkeypatch.setitem(bench._WORKERS, "async_virtual", boom)
    monkeypatch.setenv("XLA_FLAGS", os.environ.get("XLA_FLAGS", ""))
    with pytest.raises(SystemExit) as exc:
        bench.worker_main("async_virtual")
    assert exc.value.code == 5
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["ok"] is False and "workload exploded" in rec["error"]


def test_tpu_worker_refuses_to_run_off_the_chip(bench, capsys):
    """A TPU workload on the CPU platform is exit code 4 before the
    workload starts — no CPU number under a device metric's name."""
    with pytest.raises(SystemExit) as exc:
        bench.worker_main("kernels")
    assert exc.value.code == 4
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rec["ok"] is False and rec["probe"]["backend"] == "cpu"


def test_bench_main_exits_nonzero_without_a_chip(tmp_path):
    """`python bench.py` on the CPU: non-zero, quickly, one parseable line
    that carries the error, and nothing written into the checkout."""
    import subprocess
    import time

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = set(os.listdir(repo)) | set(
        os.listdir(os.path.join(repo, "benchmarks")))
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")], cwd=tmp_path,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    assert time.monotonic() - t0 < 60
    rec = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec["value"] == 0.0 and "tpu_plan" in rec["extra"]["errors"]
    after = set(os.listdir(repo)) | set(
        os.listdir(os.path.join(repo, "benchmarks")))
    assert after - before <= {".jax_cache"}
    assert not os.listdir(tmp_path)
