"""The `async_inprocess` mode: `AsyncSGD(...).run(batch_fn, steps)`, the
AsySG-InCon parameter server with its workers as threads of this process.
On one chip the PS and its single worker share the chip.

`run()` takes a number of updates, not seconds, and restarts its worker
threads on every call.  So after the warm-up one calibration call measures
the rate, and then ONE call sized to last `--seconds` is the window, timed
on the benchmark's own clock round that call.
"""

from __future__ import annotations

import math

from .. import data
from ..harness import percentile, reference_check


def staleness_bound(opt) -> int:
    """Most updates that can land between a worker's read of the
    parameters and the use of its gradient, with one worker: whatever the
    bounded queue holds plus the fill the PS has taken and not yet
    published, in updates of `quota` gradients, rounded up."""
    capacity = max(opt.quota, opt.num_workers, opt.credit_window)
    return math.ceil((capacity + opt.quota) / opt.quota)


def run(ctx) -> dict:
    import jax

    from pytorch_ps_mpi_tpu import async_ps
    from pytorch_ps_mpi_tpu.errors import WorkerFailedError

    cell, family, spans = ctx.cell, ctx.family, ctx.spans
    rows = cell["rows_per_chip"]

    params = family.init_params(ctx.seed)
    jax.block_until_ready(params)
    ctx.mark("parameters")
    pool = data.make_pool(cell["feed"], family.shapes, ctx.seed)
    ctx.mark("pool")
    check = reference_check(
        family, "async", params, data.fixed_sample(pool, cell["check_rows"]),
        ctx.config["check"], ctx.devices[0])
    ctx.mark("reference check")

    opt = async_ps.AsyncPS(list(params.items()), optim=cell["optim"],
                           devices=list(ctx.devices), **cell["hyper"],
                           **cell.get("ps", {}))
    del params
    opt.compile_step(family.async_loss())
    batch_fn = data.worker_batch_fn(pool, rows, ctx.seed)

    ctx.mark("optimizer built")
    warm = opt.run(batch_fn, steps=cell["warmup_updates"])
    ctx.mark("warm-up updates (compile or cache load)")
    cal = opt.run(batch_fn, steps=cell["calibration_updates"])
    rate = cell["calibration_updates"] / cal["wall_time"]
    n_warm_timings = len(opt.timings)
    ctx.setup_done()

    planned = max(cell["calibration_updates"], round(rate * ctx.seconds))
    start = ctx.now()
    worker_error = None
    try:
        with spans.span("async_run"):
            hist = opt.run(batch_fn, steps=planned)
    except WorkerFailedError as exc:
        worker_error = repr(exc.__cause__ or exc)
        hist = {"losses": [], "staleness": [], "grads_consumed": 0}
    end = ctx.now()
    timings = opt.timings[n_warm_timings:]
    traced = ctx.trace and worker_error is None
    if traced:
        with ctx.profiled(), spans.span("async_run"):
            opt.run(batch_fn, steps=cell["trace_updates"])

    ctx.sample_memory()
    losses = hist["losses"]
    failed = (planned - len(losses)) + sum(
        1 for x in losses if not math.isfinite(x))
    bound = staleness_bound(opt)
    first, tail = warm["losses"][0], losses[-max(1, len(losses) // 10):]
    falls = bool(tail) and percentile(tail, 50) < first
    in_bound = all(s <= bound for s in hist["staleness"])
    # The benchmark counts the gradients itself: every update it asked for
    # consumes `quota` of them.  The program's own count has to agree.
    gradients = len(losses) * opt.quota
    counted = hist["grads_consumed"] == gradients
    checks = {"reference": check, "loss_falls": falls,
              "staleness_in_bound": in_bound, "staleness_bound": bound,
              "grads_consumed_as_counted": counted,
              "worker_error": worker_error, "first_loss": first,
              "last_loss": tail[-1] if tail else None}
    failed_checks = [name for name, ok in (
        ("reference", check["ok"]), ("loss_falls", falls),
        ("staleness_in_bound", in_bound),
        ("grads_consumed_as_counted", counted),
        ("worker_error", worker_error is None)) if not ok]
    return {
        "attempted": planned, "failed": failed,
        "samples": gradients * rows * family.samples_per_row,
        "window": (start, end),
        "staleness": list(hist["staleness"]),
        "fill_s": [t["comm_wait"] for t in timings],
        "apply_s": [t["optim_step_time"] for t in timings],
        "trace_steps": cell["trace_updates"] if traced else 0,
        "rows_per_chip": rows,
        "checks": checks, "failed_checks": failed_checks,
    }
