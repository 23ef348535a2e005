"""What the readers of the program's span log share.

The in-process `AsyncPS` puts a span round every boundary of its PS loop and
of its worker threads (`pytorch_ps_mpi_tpu.utils.timing.span`, names
`async.*`) and keeps them in a bounded log on `time.perf_counter()` — the
clock of `ctx.now` and of `harness.Spans.records`, so a reader clips the log
to `result["window"]`, the UNTRACED window that the profiler does not
distort, with no conversion.

A program that has no span log (a commit before the spans) gives every
reader None, and so does a log that holds no async span in the window or has
dropped records younger than the window's start.
"""
from perfbench.harness import percentile
from perfbench.trace_reduce import subtract

# The children of `async.update` in which the PS thread works; in
# `async.fill` it waits for a gradient, which is that span's job.
PS_WORK = ("async.stack", "async.apply", "async.publish", "async.read_loss")


def program_log():
    """The program's span log, or None where the program has none."""
    try:
        from pytorch_ps_mpi_tpu.utils.timing import span_log
    except ImportError:
        return None
    return span_log()


def seconds(record) -> float:
    return record["end"] - record["start"]


def window_records(obs, window=None):
    """The records that lie wholly inside the window (by default the
    untraced one), or None where nothing can be said."""
    log = program_log()
    if log is None:
        return None
    lo, hi = window or obs["result"]["window"]
    if log.dropped and log.dropped_until > lo:
        return None
    return log.records(since=lo, until=hi) or None


def median_ms(obs, name: str):
    """Median duration of the spans called `name`, in milliseconds."""
    records = window_records(obs)
    v = [seconds(r) for r in records or () if r["name"] == name]
    return 1e3 * percentile(v, 50) if v else None


def children_share_pct(obs, parent: str, child: "str | None" = None):
    """Of the summed duration of the `parent` spans, the percentage their
    direct children cover (only those called `child`, where given)."""
    records = window_records(obs)
    parents = {r["id"]: r for r in records or () if r["name"] == parent}
    if not parents:
        return None
    covered = sum(seconds(r) for r in records if r["parent"] in parents
                  and (child is None or r["name"] == child))
    return 100.0 * covered / sum(seconds(r) for r in parents.values())


def intersect(a, b) -> list:
    """The part of merged intervals `a` that merged intervals `b` cover."""
    return subtract(a, subtract(a, b))
