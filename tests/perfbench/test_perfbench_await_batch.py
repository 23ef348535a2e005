"""`async_worker_await_batch_ms_p50`, the reader of the `async.await_batch`
span that the worker's drawer thread brought: against a log written by hand,
against a program that has no such span (the parent commit), and against the
program itself; and the readers the benchmark already had, on a log laid out
as the program now lays it (`async.draw` on the drawer's thread, under no
`async.worker_iter`)."""

import importlib
import json
import os
import types

import pytest

from perfbench.layer_metrics import _async_spans
from pytorch_ps_mpi_tpu.utils.timing import SpanLog

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
METRIC = "async_worker_await_batch_ms_p50"
WINDOW = (100.0, 110.0)
WORKER, DRAWER = "async-ps-worker-0", "async-ps-worker-0-draw"


def read(metric, window=WINDOW):
    reader = importlib.import_module(f"perfbench.layer_metrics.{metric}")
    return reader.read({"result": {"window": window}, "trace": None,
                        "spans": types.SimpleNamespace(records=[])})


def drawn_ahead_log(with_await=True):
    """Five worker iterations of 2 s from 100.5 on, the last across the
    window's end.  They wait 1.2, 1.4, 1.6, 1.8 and 2.0 s for their batch,
    then work 0.3 s and are blocked 0.1 s in `async.enqueue`.  The drawer's
    draws of 1.5 s run beside them, parent None, the first from before the
    window."""
    log, ids = SpanLog(), iter(range(1, 999))

    def add(name, thread, start, seconds, parent=None, **more):
        record = {"name": name, "thread": thread, "start": start,
                  "end": start + seconds, "cpu": 0.0, "id": next(ids),
                  "parent": parent, **more}
        log._append(record)
        return record["id"]

    for it in range(5):
        add("async.draw", DRAWER, 99.0 + 2.0 * it, 1.5, rank=0, it=it)
    for it in range(5):
        start = 100.5 + 2.0 * it
        wait = 1.2 + 0.2 * it
        parent = add("async.worker_iter", WORKER, start, 2.0, rank=0, it=it)
        if with_await:
            add("async.await_batch", WORKER, start, wait, parent, it=it,
                ready=False)
        add("async.snapshot", WORKER, start + wait, 0.1, parent)
        add("async.put_batch", WORKER, start + wait + 0.1, 0.05, parent)
        add("async.grad", WORKER, start + wait + 0.15, 0.15, parent)
        add("async.enqueue", WORKER, start + wait + 0.3, 0.1, parent,
            retries=0)
    return log


def test_the_entry_is_as_the_issue_wrote_it_but_for_its_layer():
    entry = BENCH["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "async batch drawer",
        "moves": "samples_per_s_chip", "workloads": ["resnet50-async-1chip"]}
    assert len(BENCH["per_layer"]) == 27


def test_median_of_the_waits_inside_the_window(monkeypatch):
    log = drawn_ahead_log()
    monkeypatch.setattr(_async_spans, "program_log", lambda: log)
    # four waits are whole in the window; the fifth ends at 110.5
    assert read(METRIC) == pytest.approx(1500.0)
    assert read(METRIC, window=(100.0, 106.0)) == pytest.approx(1300.0)


def test_nothing_is_said_where_nothing_can_be(monkeypatch):
    log = drawn_ahead_log()
    monkeypatch.setattr(_async_spans, "program_log", lambda: log)
    assert read(METRIC, window=(50.0, 60.0)) is None
    log.dropped, log.dropped_until = 2, 100.7     # the window is not whole
    assert read(METRIC) is None
    # a program from before the drawer: spans, but none of this name
    old = drawn_ahead_log(with_await=False)
    monkeypatch.setattr(_async_spans, "program_log", lambda: old)
    assert read(METRIC) is None
    assert read("async_worker_h2d_ms_p50") == pytest.approx(50.0)
    # a program from before the spans
    monkeypatch.setattr(_async_spans, "program_log", lambda: None)
    assert read(METRIC) is None


@pytest.mark.parametrize("metric,want", [
    # the draws that are whole in the window, whatever thread they ran on
    ("async_worker_draw_ms_p50", 1500.0),
    ("async_worker_h2d_ms_p50", 50.0),
    ("async_worker_grad_call_ms_p50", 150.0),
    # 4 x 0.1 s inside enqueue of 4 iterations of 2 s: the wait for a batch
    # is the iteration's time and not the queue's
    ("async_worker_blocked_pct", 5.0),
])
def test_the_older_readers_read_the_new_layout(monkeypatch, metric, want):
    log = drawn_ahead_log()
    monkeypatch.setattr(_async_spans, "program_log", lambda: log)
    assert read(metric) == pytest.approx(want)


def test_the_reader_reads_the_programs_own_span():
    """The name the reader looks for is the name `AsyncPS.run` writes, and
    the draw's reader still reads `batch_fn`'s own time, not a wait."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_ps_mpi_tpu import AsyncSGD
    from pytorch_ps_mpi_tpu.utils.timing import span_log

    def batch_fn(rank, it):
        time.sleep(0.02)
        return {"x": np.full((4, 3), float(it), np.float32)}

    opt = AsyncSGD([("w", np.ones((3,), np.float32))], lr=0.01, quota=1,
                   devices=[jax.devices()[0]])
    opt.compile_step(lambda p, b: jnp.mean((b["x"] @ p["w"]) ** 2))
    span_log().clear()
    start = time.perf_counter()
    opt.run(batch_fn, steps=6)
    window = (start, time.perf_counter())
    waited = read(METRIC, window=window)
    assert waited is not None and 0.0 <= waited < 1e3 * (window[1] - start)
    drew = read("async_worker_draw_ms_p50", window=window)
    assert 20.0 <= drew < 200.0
