"""The `sync` mode: `MPI_PS(...).compile_step(loss).step(batch)` over a mesh
of the cell's chips — every chip its own parameter server, gradients summed
over ICI inside one jitted step.

The window keeps at most two steps in flight (it blocks on step i-2 before
it dispatches step i), runs for `--seconds`, and ends in `block_until_ready`
on the last step's outputs.  Everything the program is given here is what a
user of the library gives it; the constructor's defaults stand wherever the
cell's file does not name a value.
"""

from __future__ import annotations

import math
import time

from .. import data
from ..harness import percentile, reference_check

IN_FLIGHT = 2


def _loop(opt, feed, spans, *, seconds: float = 0.0, steps: int = 0) -> dict:
    """Run steps until `seconds` have passed (or exactly `steps`), two in
    flight.  Returns start, end, the losses and each step's completion
    time on the host clock."""
    import jax

    pending, done_at = [], []
    start = time.perf_counter()
    while True:
        with spans.span("next_batch"):
            batch = next(feed)
        with spans.span("dispatch"):
            loss, _ = opt.step(batch, block=False)
        pending.append(loss)
        if len(pending) > IN_FLIGHT:
            with spans.span("wait_ready"):
                jax.block_until_ready(pending[-1 - IN_FLIGHT])
            done_at.append(time.perf_counter())
        if (steps and len(pending) >= steps) or (
                not steps and time.perf_counter() - start >= seconds):
            break
    for loss in pending[len(done_at):]:
        with spans.span("wait_ready"):
            jax.block_until_ready(loss)
        done_at.append(time.perf_counter())
    with spans.span("wait_ready"):
        jax.block_until_ready((opt.params, opt.state))
    end = time.perf_counter()
    return {"start": start, "end": end, "done_at": done_at,
            "losses": [float(x) for x in pending]}


def run(ctx) -> dict:
    import jax

    from pytorch_ps_mpi_tpu import MPI_PS
    from pytorch_ps_mpi_tpu.parallel.mesh import batch_sharded, make_ps_mesh

    cell, family, spans = ctx.cell, ctx.family, ctx.spans
    chips = len(ctx.devices)
    mesh = make_ps_mesh(devices=ctx.devices)
    rows = cell["rows_per_chip"] * chips

    params = family.init_params(ctx.seed)
    jax.block_until_ready(params)
    ctx.mark("parameters")
    pool = data.make_pool(cell["feed"], family.shapes, ctx.seed)
    ctx.mark("pool")
    check = reference_check(
        family, "sync", params, data.fixed_sample(pool, cell["check_rows"]),
        ctx.config["check"], ctx.devices[0])
    ctx.mark("reference check")

    loss_fn, has_aux = family.sync_loss()
    opt = MPI_PS(list(params.items()), optim=cell["optim"], mesh=mesh,
                 **cell["hyper"], **cell.get("ps", {}))
    del params
    opt.compile_step(loss_fn, has_aux=has_aux,
                     aux=family.aux if has_aux else None)
    ctx.mark("optimizer built")

    feed_kind = cell["feed"]["kind"]
    if feed_kind == "draw":
        feed = data.draw_stream(pool, rows, ctx.seed)
    elif feed_kind == "loader":
        feed = data.loader_stream(
            pool, rows, ctx.seed, prefetch=cell["feed"]["prefetch"],
            sharding=batch_sharded(mesh))
    else:
        raise ValueError(f"unknown feed kind {feed_kind!r}")

    # Warm-up: the first step compiles (or loads) the one step program; a
    # few more settle donation and the non-blocking path the window uses.
    warm = [opt.step(next(feed))[0]]
    ctx.mark("first step (compile or cache load)")
    warm += [opt.step(next(feed))[0] for _ in range(cell["warmup_steps"] - 1)]
    _loop(opt, feed, spans, steps=IN_FLIGHT + 1)
    n_warm_timings = len(opt.timings)
    ctx.setup_done()

    window = _loop(opt, feed, spans, seconds=ctx.seconds)
    timings = opt.timings[n_warm_timings:]
    if ctx.trace:
        with ctx.profiled():
            _loop(opt, feed, spans, steps=cell["trace_steps"])

    feed.close()
    ctx.sample_memory()
    consensus_ok = True
    if chips > 1:
        consensus_ok = bool(opt.check_consensus()["ok"])
    losses = window["losses"]
    failed = sum(1 for x in losses if not math.isfinite(x))
    tail = losses[-max(1, len(losses) // 10):]
    falls = percentile(tail, 50) < warm[0]
    steps = len(losses)
    checks = {"reference": check, "loss_falls": bool(falls),
              "consensus": consensus_ok,
              "first_loss": warm[0], "last_loss": tail[-1]}
    failed_checks = [name for name, ok in (
        ("reference", check["ok"]), ("loss_falls", falls),
        ("consensus", consensus_ok)) if not ok]
    return {
        "attempted": steps, "failed": failed,
        "samples": steps * rows * family.samples_per_row,
        "window": (window["start"], window["end"]),
        "step_done_at": window["done_at"],
        "dispatch_s": [t["isend_time"] for t in timings],
        "trace_steps": cell["trace_steps"] if ctx.trace else 0,
        "rows_per_chip": cell["rows_per_chip"],
        "checks": checks, "failed_checks": failed_checks,
    }
