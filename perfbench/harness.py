"""What every mode of the benchmark shares: host spans on the profiler's
clock, JAX's own compile records, the device table, percentiles, and the
comparison with the plain reference that decides `correct`."""

from __future__ import annotations

import contextlib
import json
import logging
import math
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SPAN_PREFIX = "pb:"


class Spans:
    """Host spans round the benchmark's calls into the program.  Each span
    is kept on the host clock and, while a trace is being taken, also goes
    into the profiler's own trace (`jax.profiler.TraceAnnotation`), where it
    shares a clock with the device events."""

    def __init__(self):
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.records: list = []     # (name, start_s, end_s)

    @contextlib.contextmanager
    def span(self, name: str):
        start = time.perf_counter()
        with self._annotation(SPAN_PREFIX + name):
            try:
                yield
            finally:
                self.records.append((name, start, time.perf_counter()))

    def total(self, name: str, since: float, until: float) -> float:
        return sum(min(e, until) - max(s, since) for n, s, e in self.records
                   if n == name and e > since and s < until)


class CompileLog(logging.Handler):
    """Every program JAX compiles in this process, by name and time, from
    its own ``Compiling <name> ...`` log records (copied from
    `chip_smoke.py`; `jit._cache_size()` grows without any compilation and
    cannot count them)."""

    def __init__(self):
        super().__init__()
        self.records: list = []     # (perf_counter, name)
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.propagate = False
        log.addHandler(self)

    def emit(self, record) -> None:
        if str(record.msg).startswith("Compiling ") and record.args:
            self.records.append((time.perf_counter(), str(record.args[0])))

    def between(self, since: float, until: float) -> list:
        return [n for t, n in self.records if since <= t <= until]


def load_peaks(device_kind: str) -> dict:
    """The published peaks of this device.  A device that is not in the
    table is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/peaks.json "
            f"(have {sorted(table)}): add its published peaks with their "
            f"source before measuring on it")
    return table[device_kind]


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile of a non-empty sequence."""
    import numpy as np
    if not len(values):
        raise ValueError("percentile of no samples")
    return float(np.percentile(values, q))


def peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip.

    On this runtime `peak_bytes_in_use` counts live arrays only: the
    temporaries of a running program sit in a reservation of their own
    (`peak_bytes_reserved`, within 1 % of the `temp` bytes that
    `compiled.memory_analysis()` gives for the cell's largest program; my
    chip runs, PR 22).  So the peak while the window's program runs is what
    is in use now — called after the window, when only the optimizer's
    arrays are alive, which makes it the same in every run — plus the
    largest reservation; and never less than the runtime's own peak."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        running = int(stats.get("bytes_in_use", 0)) \
            + int(stats.get("peak_bytes_reserved", 0))
        peaks.append(max(running, int(stats.get("peak_bytes_in_use", 0))))
    return max(peaks)


def check_program(family, mode: str):
    """The one program of the reference check: loss and gradient of the
    plain f32 reference and of the program's own loss function on the same
    parameters and sample, reduced to five scalars."""
    import jax
    import jax.numpy as jnp

    system, reference = family.check_pair(mode)

    def sq(tree):
        return sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                   for g in jax.tree.leaves(tree))

    def both(params, sample):
        with jax.default_matmul_precision("highest"):
            ref_loss, ref_grads = jax.value_and_grad(reference)(params,
                                                                sample)
        sys_loss, sys_grads = jax.value_and_grad(system)(params, sample)
        diff = jax.tree.map(lambda a, c: a.astype(jnp.float32) - c,
                            sys_grads, ref_grads)
        return (ref_loss, sys_loss, jnp.sqrt(sq(ref_grads)),
                jnp.sqrt(sq(sys_grads)), jnp.sqrt(sq(diff)))

    return both


def reference_check(family, mode: str, params, sample: dict, tol: dict,
                    device) -> dict:
    """Loss and gradient of the program's loss function (model, dtype and
    kernels under test) against the plain f32 reference, on the same
    parameters and the same seeded sample, before the optimizer exists, so
    that no second copy of its state is ever on the chip.

    Compared: the loss, the global gradient norm, and the norm of the
    difference of the two gradients over the reference's norm — which a
    wrong kernel cannot pass by having the right size."""
    import jax

    ref_loss, sys_loss, ref_norm, sys_norm, diff_norm = (
        float(x) for x in jax.jit(check_program(family, mode))(
            params, jax.device_put(sample, device)))
    out = {
        "reference_loss": ref_loss, "system_loss": sys_loss,
        "reference_grad_norm": ref_norm, "system_grad_norm": sys_norm,
        "loss_rel_err": abs(sys_loss - ref_loss) / abs(ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / ref_norm,
        "grad_diff_rel": diff_norm / ref_norm,
    }
    out["ok"] = bool(
        all(math.isfinite(v) for v in out.values())
        and out["loss_rel_err"] <= tol["loss_rel"]
        and out["grad_norm_rel_err"] <= tol["grad_norm_rel"]
        and out["grad_diff_rel"] <= tol["grad_diff_rel"])
    return out
