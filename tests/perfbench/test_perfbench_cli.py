"""The command itself: what it refuses, and a rehearsal of every cell on the
CPU (toy sizes, kernels under the interpreter, four virtual devices) that
runs every mode and reader and never prints a result line."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run_cli(args, cwd=ROOT, env=None, tmp=None):
    """The benchmark's command with its stderr in a file (XLA:CPU writes
    kilobytes per cache hit; an undrained pipe would block the child)."""
    err_path = os.path.join(tmp, "stderr.txt")
    with open(err_path, "w") as err:
        p = subprocess.run(
            [sys.executable, *BENCH["command"][1:], *args], cwd=cwd,
            env={**os.environ, **(env or {})}, stdout=subprocess.PIPE,
            stderr=err, text=True, timeout=600)
    with open(err_path) as f:
        return p.returncode, p.stdout, f.read()


def test_refuses_the_cpu(tmp_path):
    rc, out, err = run_cli(["--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
                           env={"JAX_PLATFORMS": "cpu"}, tmp=str(tmp_path))
    assert rc != 0 and out.strip() == ""
    assert "needs" in err and "tpu" in err


def test_refuses_a_directory_without_the_program(tmp_path):
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), alone)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, p), alone / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    rc, out, err = run_cli(["--workload", CELLS[0], "--seed", "1",
                            "--seconds", "1", "--trace", "0"],
                           cwd=str(alone), env={"JAX_PLATFORMS": "cpu"},
                           tmp=str(tmp_path))
    assert rc != 0 and out.strip() == ""
    assert "not in this checkout" in err


def test_unknown_workload_is_refused(tmp_path):
    rc, out, _ = run_cli(["--workload", "no-such-cell"], tmp=str(tmp_path))
    assert rc != 0 and out.strip() == ""


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_every_mode_and_reader_and_prints_no_result(
        cell, tmp_path):
    rc, out, err = run_cli(["--workload", cell, "--seed", "3", "--seconds",
                            "2", "--trace", "1", "--rehearse"],
                           tmp=str(tmp_path))
    assert rc == 3, err[-2000:]
    assert out.strip() == ""                    # never a result line
    line = next(l for l in err.splitlines() if "rehearsal (CPU" in l)
    result = json.loads(line[line.index("{"):])
    assert result["device"]["platform"] == "cpu"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["compiles_in_window"] == []
    assert "metrics" not in result
    got = set(result["rehearsed_on_the_cpu"])
    assert "compiles_in_window" in got
    # no device plane in a CPU trace: nothing under a device metric's name
    device_metrics = {m["name"] for m in BENCH["per_layer"]
                      if m["source"] == "device_trace"} | {"mfu_pct"}
    assert not got & device_metrics
    assert result["breakdown"] == {"device_ops": [], "idle_gaps": []}
