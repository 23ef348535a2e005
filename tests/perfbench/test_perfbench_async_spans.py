"""The readers of the program's span log (`layer_metrics/async_*` over
`_async_spans`), against a log and a window written by hand, where every
answer can be counted on paper; the clock alignment of
`async_idle_worker_blocked_pct` against a synthetic trace; and the async
cell's rehearsal, which has to report the span metrics and nothing under the
device metric's name."""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

from perfbench import trace_reduce as tr
from perfbench.layer_metrics import _async_spans
from pytorch_ps_mpi_tpu.utils.timing import SpanLog

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELL = "resnet50-async-1chip"
SPAN_METRICS = [m["name"] for m in BENCH["per_layer"]
                if m["layer"] == "async in process"
                and m["name"] not in ("async_fill_ms_p50",
                                      "async_apply_ms_p50",
                                      "async_staleness_mean")]
WINDOW = (100.0, 110.0)
PS, W0, W1 = "MainThread", "async-ps-worker-0", "async-ps-worker-1"


def reader(metric):
    return importlib.import_module(f"perfbench.layer_metrics.{metric}")


class HandLog:
    """Records laid end to end: each child starts where the last one of its
    parent ended."""

    def __init__(self):
        self.log, self._ids, self._cursor = SpanLog(), iter(range(1, 999)), {}

    def add(self, name, thread, seconds, *, start=None, parent=None,
            cpu=0.0, **ids):
        if start is None:
            start = self._cursor[parent["id"]]
        record = {"name": name, "thread": thread, "start": start,
                  "end": start + seconds, "cpu": cpu, "id": next(self._ids),
                  "parent": parent and parent["id"], **ids}
        self._cursor[record["id"]] = start
        if parent:
            self._cursor[parent["id"]] = record["end"]
        self.log._append(record)
        return record

    def update(self, start, stack, length=2.0):
        u = self.add("async.update", PS, length, start=start)
        self.add("async.fill", PS, 0.1, parent=u)
        self.add("async.stack", PS, stack, parent=u, cpu=0.1)
        self.add("async.apply", PS, 0.6, parent=u, cpu=0.15)
        self.add("async.publish", PS, 0.01, parent=u, cpu=0.01)
        self.add("async.read_loss", PS, 0.2, parent=u, cpu=0.02)

    def worker_iter(self, thread, start, draw, enqueue, length=2.5):
        it = self.add("async.worker_iter", thread, length, start=start)
        self.add("async.snapshot", thread, 0.05, parent=it)
        self.add("async.draw", thread, draw, parent=it)
        self.add("async.put_batch", thread, 0.2, parent=it)
        self.add("async.grad", thread, 0.004, parent=it)
        self.add("async.send", thread, 0.001, parent=it)
        self.add("async.enqueue", thread, enqueue, parent=it)


@pytest.fixture
def hand(monkeypatch):
    """Three updates of 2 s and four worker iterations of 2.5 s inside the
    window 100..110, one update before it and one across its end."""
    h = HandLog()
    h.update(95.0, stack=1.9)                  # before the window
    for start, stack in ((100.0, 0.3), (103.0, 0.4), (106.0, 0.5)):
        h.update(start, stack)
    h.update(109.0, stack=1.0)                 # ends after the window
    for start, draw, enqueue in ((100.2, 1.0, 0.5), (102.7, 0.8, 1.0),
                                 (105.2, 0.6, 1.0)):
        h.worker_iter(W0, start, draw, enqueue)
    h.worker_iter(W1, 100.0, draw=0.8, enqueue=0.5)
    h.worker_iter(W1, 108.0, draw=2.0, enqueue=0.1)     # ends at 110.5
    monkeypatch.setattr(_async_spans, "program_log", lambda: h.log)
    return h.log


def obs(window=WINDOW, trace=None, spans=()):
    return {"result": {"window": window}, "trace": trace,
            "spans": types.SimpleNamespace(records=list(spans))}


WANT = {
    "async_stack_ms_p50": 400.0,               # of 300, 400, 500
    "async_apply_call_ms_p50": 600.0,
    "async_publish_ms_p50": 10.0,
    "async_read_loss_ms_p50": 200.0,
    # 3 updates of 2 s; their children cover 3 x 0.91 s + 1.2 s of stack
    "async_update_unspanned_pct": 100.0 * (6.0 - 3.93) / 6.0,
    # stack 1.2 + apply 1.8 + publish 0.03 + read_loss 0.6 s of wall,
    # 0.3 + 0.45 + 0.03 + 0.06 s of them on the CPU; fill is left out
    "async_ps_offcpu_pct": 100.0 * (1.0 - 0.84 / 3.63),
    "async_worker_draw_ms_p50": 800.0,         # of 1000, 800, 600, 800
    "async_worker_h2d_ms_p50": 200.0,
    "async_worker_grad_call_ms_p50": 4.0,
    # 0.5 + 1.0 + 1.0 + 0.5 s inside enqueue, of 4 iterations of 2.5 s
    "async_worker_blocked_pct": 30.0,
}


def test_the_hand_log_covers_every_span_reader():
    assert set(WANT) | {"async_idle_worker_blocked_pct"} == set(SPAN_METRICS)
    assert len(SPAN_METRICS) == 11


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_over_the_hand_log(hand, metric):
    assert reader(metric).read(obs()) == pytest.approx(WANT[metric])


@pytest.mark.parametrize("metric", sorted(WANT))
def test_reader_says_nothing_without_a_log_it_can_trust(
        hand, metric, monkeypatch):
    read = reader(metric).read
    # dropped records that are older than the window take nothing from it
    hand.dropped, hand.dropped_until = 3, 99.0
    assert read(obs()) == pytest.approx(WANT[metric])
    # ... but one that ended inside it does: the window is no longer whole
    hand.dropped_until = 100.5
    assert read(obs()) is None
    # no span in the window
    assert read(obs(window=(50.0, 60.0))) is None
    hand.clear()
    assert read(obs()) is None
    # a program from before the spans
    monkeypatch.setattr(_async_spans, "program_log", lambda: None)
    assert read(obs()) is None


def test_program_log_is_the_programs_own():
    from pytorch_ps_mpi_tpu.utils.timing import span_log
    assert _async_spans.program_log() is span_log()


# -- where the span log meets the device trace -------------------------------

OFFSET = 800.0      # profiler's clock minus perf_counter


def profiled_obs(log_records, busy=((1000, 1002), (1004, 1006), (1009, 1010)),
                 devices=True):
    """The profiled `async_run` lies at 200..210 on `perf_counter` and at
    1000..1010 in the trace, where the chip is idle 1002..1004 and
    1006..1009: 5 s."""
    h = HandLog()
    for thread, start, enqueue in log_records:
        it = h.add("async.worker_iter", thread, enqueue + 0.5, start=start)
        h.add("async.grad", thread, 0.5, parent=it)
        h.add("async.enqueue", thread, enqueue, parent=it)
    dev = tr.DeviceTrace(index=0, ops=[
        tr.Op(name=f"fusion.{i}", start=float(s), end=float(e))
        for i, (s, e) in enumerate(busy)])
    window = (200.0 + OFFSET, 210.0 + OFFSET)
    trace = tr.Trace(devices=[dev] if devices else [],
                     spans=[("async_run", *window)], window=window)
    return h.log, obs(trace=trace, spans=[("async_run", *WINDOW),
                                          ("async_run", 200.0, 210.0)])


@pytest.mark.parametrize("log_records,want", [
    # one worker inside enqueue 202..204.5: all of the first gap, none of
    # the second
    ([(W0, 201.5, 2.5)], 40.0),
    # a second worker blocked only 203..204: the chip idle AND every worker
    # blocked for 1 s of the 5
    ([(W0, 201.5, 2.5), (W1, 202.5, 1.0)], 20.0),
    # workers, but none blocked while the chip was idle
    ([(W0, 204.0, 1.5)], 0.0),
])
def test_idle_worker_blocked_aligns_the_two_clocks(
        log_records, want, monkeypatch):
    log, o = profiled_obs(log_records)
    monkeypatch.setattr(_async_spans, "program_log", lambda: log)
    got = reader("async_idle_worker_blocked_pct").read(o)
    assert got == pytest.approx(want)


def test_idle_worker_blocked_needs_a_device_plane_and_both_clocks(
        monkeypatch):
    read = reader("async_idle_worker_blocked_pct").read
    log, o = profiled_obs([(W0, 201.5, 2.5)])
    monkeypatch.setattr(_async_spans, "program_log", lambda: log)
    assert read({**o, "trace": None}) is None
    assert read(profiled_obs([(W0, 201.5, 2.5)], devices=False)[1]) is None
    # the benchmark's span is missing on one of the clocks
    assert read({**o, "spans": types.SimpleNamespace(records=[])}) is None
    # the log holds no worker in the profiled window
    log.clear()
    assert read(o) is None
    monkeypatch.setattr(_async_spans, "program_log", lambda: None)
    assert read(o) is None


def test_intersect():
    a = [(0.0, 4.0), (6.0, 9.0)]
    assert _async_spans.intersect(a, [(1.0, 2.0), (3.0, 7.0)]) == [
        (1.0, 2.0), (3.0, 4.0), (6.0, 7.0)]
    assert _async_spans.intersect(a, []) == []
    assert _async_spans.intersect([], a) == []


# -- the cell itself ---------------------------------------------------------


def test_async_rehearsal_reports_the_span_metrics_and_no_device_metric(
        tmp_path):
    err_path = tmp_path / "stderr.txt"
    with open(err_path, "w") as err:
        p = subprocess.run(
            [sys.executable, *BENCH["command"][1:], "--workload", CELL,
             "--seed", "3000000019", "--seconds", "2", "--trace", "1",
             "--rehearse"], cwd=ROOT, stdout=subprocess.PIPE, stderr=err,
            text=True, timeout=600)
    text = err_path.read_text()
    assert p.returncode == 3, text[-2000:]
    line = next(l for l in text.splitlines() if "rehearsal (CPU" in l)
    got = json.loads(line[line.index("{"):])["rehearsed_on_the_cpu"]
    assert set(WANT) <= set(got)
    assert "async_idle_worker_blocked_pct" not in got
    assert 0.0 <= got["async_update_unspanned_pct"]["value"] < 50.0
    assert 0.0 <= got["async_worker_blocked_pct"]["value"] <= 100.0
    # the two views of one measurement: the per-update dicts' stack + apply
    # against the spans' (medians of sums and sums of medians differ a little)
    both = got["async_stack_ms_p50"]["value"] \
        + got["async_apply_call_ms_p50"]["value"]
    assert both == pytest.approx(got["async_apply_ms_p50"]["value"], rel=0.5)
