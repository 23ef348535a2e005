"""Of the device's idle seconds in the PROFILED window, the share during which
every worker thread was inside `async.enqueue`, blocked on the bounded queue:
the chip had nothing to do and no worker could give it anything.

Where the program's spans meet the device trace.  The span log is on
`perf_counter` and the trace on the profiler's clock; the benchmark's own
`async_run` span of the profiled window is on both (`obs["spans"].records`
and `obs["trace"].spans`), and the difference of its two starts is the
offset.  A share of a window the profiler slowed (PERF.md, Findings of
PR 22, 3), unlike the other readers of the span log."""
from perfbench.layer_metrics._async_spans import intersect, window_records
from perfbench.trace_reduce import subtract, total, union

SPAN = "async_run"


def read(obs):
    trace = obs["trace"]
    if trace is None or not trace.devices:
        return None
    host = [(s, e) for n, s, e in obs["spans"].records if n == SPAN]
    profiled = [(s, e) for n, s, e in trace.spans if n == SPAN]
    if not host or not profiled:
        return None
    offset = profiled[-1][0] - host[-1][0]
    records = window_records(obs, window=host[-1])
    enqueues = {r["thread"]: [] for r in records or ()
                if r["name"] == "async.worker_iter"}
    if not enqueues:
        return None
    for r in records:
        if r["name"] == "async.enqueue" and r["thread"] in enqueues:
            enqueues[r["thread"]].append(
                (r["start"] + offset, r["end"] + offset))
    dev = min(trace.devices, key=lambda d: total(trace.busy_intervals(d)))
    idle = subtract([trace.window], trace.busy_intervals(dev))
    if not total(idle):
        return None
    blocked = idle
    for intervals in enqueues.values():
        blocked = intersect(blocked, union(intervals))
    return 100.0 * total(blocked) / total(idle)
