"""`nemo_ssd_kernel_calls_step`: its reader over traces written by hand
(events named by their HLO instruction, as the chip's trace names them),
over a trace with no call of the scan's kernels, and its entry in
`BENCHMARK.json`, found by name."""

import json
import os

import pytest

from perfbench.layer_metrics import nemo_ssd_kernel_calls_step as metric
from perfbench.trace_reduce import DeviceTrace, Op, Trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1e-3


def _obs(chips, steps=1, window_ms=200):
    """Each chip's ops are ``(name, start, end)`` in milliseconds."""
    trace = Trace(devices=[DeviceTrace(i, ops=[
        Op(name, a * MS, b * MS) for name, a, b in ops])
        for i, ops in enumerate(chips)], spans=[],
        window=(0.0, window_ms * MS))
    return {"trace": trace, "result": {"trace_steps": steps}}


def _layers(n, t0=0):
    """``n`` rematerialised Mamba-2 layers: two forwards and a backward
    each."""
    fwd = [(f"ssd_fwd.{i}", t0 + 5 * i, t0 + 5 * i + 4)
           for i in range(2 * n)]
    bwd = [(f"ssd_bwd.{i}", t0 + 100 + 10 * i, t0 + 108 + 10 * i)
           for i in range(n)]
    return fwd + bwd


OTHERS = [("fusion.1", 0, 2), ("flash_fwd.3", 2, 3), ("ssd_fwd_like.9", 3, 4),
          ("while.188", 4, 6)]


@pytest.mark.parametrize("chips,steps,want", [
    # the cell: four layers, one traced step
    ([_layers(4) + OTHERS], 1, 12.0),
    # over two traced steps
    ([_layers(4) + _layers(4, t0=200)], 2, 12.0),
    # two chips that differ: the fullest
    ([_layers(4), _layers(1) + OTHERS], 1, 12.0),
])
def test_it_counts_the_calls_a_step_on_the_fullest_chip(chips, steps, want):
    window = 200 * steps
    assert metric.read(_obs(chips, steps, window)) == pytest.approx(want)


def test_a_call_outside_the_window_is_not_read():
    late = [("ssd_bwd.99", 250, 260)]
    early = [("ssd_fwd.98", -20, -10)]
    assert metric.read(_obs([_layers(4) + late + early])) == 12


def test_it_reads_nothing_without_a_call_of_the_kernels():
    """The plain scan (the parent's program) makes no such call."""
    assert metric.read(_obs([OTHERS])) is None
    assert metric.read({"trace": None, "result": {"trace_steps": 1}}) is None
    assert metric.read(_obs([_layers(4)], steps=0)) is None


def test_the_entry_is_found_by_name():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["nemo_ssd_kernel_calls_step"] == {
        "name": "nemo_ssd_kernel_calls_step", "unit": "calls",
        "better": "lower", "source": "device_trace",
        "layer": "state-space duality scan", "moves": "samples_per_s_chip",
        "workloads": ["nemotron3-nano-sync-1chip"]}
    # the layer is the one the scan's other metrics name
    assert by_name["nemo_ssd_ms_step"]["layer"] == "state-space duality scan"
