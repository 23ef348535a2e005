"""Run one cell of the benchmark once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of its standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics` and `device` (and `breakdown`
when traced); everything else goes to standard error.  Exits non-zero, with
no result line, when JAX finds no TPU or fewer chips than the cell asks for,
or when the program under test is not in the checkout.

Everything that belongs to one cell, one configuration or one metric is in a
file of its own, found by name: `workloads/<cell>.json`, the configuration's
`file` in `BENCHMARK.json`, `models/<family>.py`, `modes/<mode>.py`,
`layer_metrics/<metric>.py`.

`--rehearse` runs the same path on the CPU at the configuration's toy sizes
(Pallas kernels under the interpreter, four virtual devices).  It exercises
every mode and reader, prints no result line and exits with code 3.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import math
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
REHEARSAL_EXIT = 3
GIB = float(1 << 30)


def log(*a) -> None:
    print("[perfbench]", *a, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"perfbench: no {what} named {name!r} in BENCHMARK.json")


class Context:
    """What a mode is handed: the cell, its configuration and family, the
    devices, the spans, and the two moments the harness owns — the end of
    set-up and the profiled window."""

    def __init__(self, *, cell, config, family, devices, seed, seconds,
                 trace, trace_dir, t_process):
        from perfbench.harness import CompileLog, Spans
        self.cell, self.config, self.family = cell, config, family
        self.devices, self.seed, self.seconds = devices, seed, seconds
        self.trace, self.trace_dir = trace, trace_dir
        self.spans, self.compiles = Spans(), CompileLog()
        self.now = time.perf_counter
        self._t_process = t_process
        self.setup_s = None
        self.peak_bytes = 0

    def mark(self, what: str) -> None:
        """A line on standard error saying how far set-up has come."""
        log(f"  {time.perf_counter() - self._t_process:7.2f} s  {what}")

    def setup_done(self) -> None:
        """Everything before this call is set-up: imports, parameters, the
        pool, the reference check, compilation or cache loads, warm-up."""
        self.setup_s = time.perf_counter() - self._t_process
        log(f"set-up took {self.setup_s:.2f} s")

    def sample_memory(self) -> None:
        """Called by the mode after its last step, while its optimizer is
        alive and its feed is closed: the loader's thread lets go of its
        last batch within a tenth of a second of its iterator's end, so
        after a short wait only the optimizer's arrays are in use, in every
        run alike (see `harness.peak_bytes`)."""
        from perfbench.harness import peak_bytes
        time.sleep(0.25)
        self.peak_bytes = peak_bytes(self.devices)

    @contextlib.contextmanager
    def profiled(self):
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        # Spans of the benchmark only: at the default level the TPU host
        # runtime's own threads write millions of futex events (a 360 MB
        # trace for 12 ResNet steps) and the traced steps run twice as slow.
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()


def device_line(devices, peak: int) -> dict:
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def main(argv=None) -> int:
    t_process = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = find(bench["workloads"], args.workload, "workload")
    config_entry = find(bench["configs"], entry["config"], "config")
    cell = load_json(os.path.join(HERE, "workloads", entry["name"] + ".json"))
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    chips = entry["chips"]

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
        cell = {**cell, **cell["rehearsal"]}
    sys.path.insert(0, ROOT)
    try:
        import pytorch_ps_mpi_tpu  # noqa: F401  the system under test
    except ImportError as exc:
        print(f"perfbench: the program under test is not in this checkout "
              f"({exc})", file=sys.stderr)
        return 2
    import jax

    from perfbench import harness, trace_reduce
    from pytorch_ps_mpi_tpu.utils.compile_cache import configure_compile_cache

    # Small programs (parameter initialisation, the optimizer's state) are
    # cached too, so that every run after a cell's first compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cache_dir = configure_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as exc:
        print(f"perfbench: JAX found no device: {exc}", file=sys.stderr)
        return 2
    want = "cpu" if args.rehearse else "tpu"
    if devices[0].platform != want or len(devices) < chips:
        print(f"perfbench: cell {entry['name']} needs {chips} {want} "
              f"device(s); JAX has {len(devices)} x {devices[0].platform}",
              file=sys.stderr)
        return 2
    devices = devices[:chips]
    peaks = None if args.rehearse else harness.load_peaks(
        devices[0].device_kind)
    log(f"cell {entry['name']}: {chips} x {devices[0].device_kind}, seed "
        f"{args.seed}, {args.seconds} s, trace {args.trace}, compile cache "
        f"{cache_dir}")

    family_mod = importlib.import_module(
        f"perfbench.models.{config['family']}")
    family = family_mod.build(
        config, cell, rehearse=args.rehearse,
        impl="interpret" if args.rehearse else "mosaic")
    mode = importlib.import_module(f"perfbench.modes.{cell['mode']}")
    ctx = Context(cell=cell, config=config, family=family, devices=devices,
                  seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                  trace_dir=os.path.join(ROOT, ".perfbench_trace",
                                         entry["name"]),
                  t_process=t_process)
    ctx.mark("imports, devices, family")
    result = mode.run(ctx)

    win = result["window"]
    compiled_in_window = ctx.compiles.between(*win)
    trace = None
    if result["trace_steps"]:
        t0 = time.perf_counter()
        trace = trace_reduce.reduce_trace(ctx.trace_dir)
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t0:.1f} s")
    peak = ctx.peak_bytes
    rate = result["samples"] / (win[1] - win[0]) / chips
    obs = {"result": result, "trace": trace, "spans": ctx.spans,
           "family": family, "cell": cell, "config": config, "peaks": peaks,
           "chips": chips, "samples_per_s_chip": rate,
           "compiled_in_window": compiled_in_window}
    end_to_end = {
        "samples_per_s_chip": rate,
        "peak_hbm_gib": peak / GIB,
        "setup_s": ctx.setup_s,
    }
    declared = {m["name"]: m for m in
                bench["per_layer" if args.trace else "end_to_end"]
                if entry["name"] in m.get("workloads", [entry["name"]])}
    metrics = {}
    for name, m in declared.items():
        if args.trace:
            reader = importlib.import_module(
                f"perfbench.layer_metrics.{name}")
            value = reader.read(obs)
        else:
            value = end_to_end[name]
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": float(value), "unit": m["unit"]}

    # Each mode says which of its own checks failed; the harness adds what
    # holds for every cell.
    checks = dict(result["checks"], compiles_in_window=compiled_in_window)
    correct = (result["failed"] == 0 and not compiled_in_window
               and not result["failed_checks"])
    log("checks:", json.dumps(checks, default=str),
        "failed:", result["failed_checks"])
    line = {"correct": bool(correct), "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": device_line(devices, peak), "checks": checks,
            "failed_checks": result["failed_checks"]}
    if trace is not None:
        busy = trace.busy_s()
        line["device"]["busy_s"] = sum(busy) / len(busy) if busy else 0.0
        line["device"]["window_s"] = trace.window_s
        line["breakdown"] = {"device_ops": trace.top_ops(10),
                             "idle_gaps": trace.idle_gaps(top=5)}
    if args.rehearse:
        # On standard error, and not under the name `metrics`: nothing a
        # CPU run prints can be read as a result.
        line["rehearsed_on_the_cpu"] = line.pop("metrics")
        log("rehearsal (CPU; never a measurement):",
            json.dumps(line, default=str))
        return REHEARSAL_EXIT
    print(json.dumps(line, default=str), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
