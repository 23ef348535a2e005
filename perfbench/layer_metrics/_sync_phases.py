"""What the readers of the sync step's phases share.

`MPI_PS.step`'s program runs under scopes of its own (`ps.grad` round the
gradient call, `ps.exchange`, `ps.update`), and
`pytorch_ps_mpi_tpu.utils.timing.step_phase` turns an instruction's `op_name`
into one phase: the step's own scope where there is one; under `ps.grad` the
forward, the backward (`transpose(`) or the forward run a second time inside
it (`rematted_computation`); None outside them all.  The phases partition the
instructions of the compiled program's text (`program_scopes("MPI_PS.step")`,
as in `_kimi.py`).

**A fusion is one event and counts whole where XLA says its root does**,
and XLA fuses across the phases' edges: the optimizer's rule into the matrix
product that makes its gradient, rematerialised elementwise work into the
backward fusion that uses it.  So a phase's time is that of the operations
*rooted* in it, and `sync_update_ms_step` and `sync_remat_ms_step` are lower
bounds of their work.  `fused_elsewhere_ms_per_step` gives the other bound:
the time of the fusions rooted elsewhere that hold the phase's instructions
(`sync_update_fused_ms_step`, `sync_remat_fused_ms_step`).

Device time of a phase is, per chip, the **union** of the intervals of its
operations on the `XLA Ops` line clipped to the trace window (a loop is one
event and its body's events), mean over the chips, over `trace_steps`.

A program without the scopes or without `step_phase` (the parent commit), and
a trace without device planes (the CPU rehearsal), make every reader return
None.
"""
import dataclasses

from perfbench.trace_reduce import clip, subtract, total, union

PROGRAM = "MPI_PS.step"
GRAD_PHASES = ("forward", "remat", "backward")


def instruction_phases():
    """``{HLO instruction: phase}`` of the registered step program, without
    the instructions under no phase; None where nothing can be said."""
    try:
        from pytorch_ps_mpi_tpu.utils.timing import program_scopes, step_phase
    except ImportError:
        return None
    phases = {n: step_phase(op_name)
              for n, op_name in (program_scopes(PROGRAM) or {}).items()}
    return {n: p for n, p in phases.items() if p} or None


def traced_phases(obs):
    """`instruction_phases()` where the run has a device trace to read them
    against, else None."""
    trace = obs["trace"]
    if trace is None or not obs["result"]["trace_steps"] \
            or not trace.devices:
        return None
    return instruction_phases()


def intervals(trace, dev, names) -> list:
    """When an operation called one of `names` ran on this device, merged
    and clipped to the window."""
    return clip(union((o.start, o.end) for o in dev.ops if o.name in names),
                *trace.window)


def names_of(phases: dict, *wanted) -> set:
    return {n for n, p in phases.items() if p in wanted}


def ms_per_step(obs, names) -> "float | None":
    """Device milliseconds a step under the operations `names`, mean over
    the chips; None where it is nothing."""
    trace = obs["trace"]
    per_dev = [total(intervals(trace, d, names)) for d in trace.devices]
    mean = sum(per_dev) / len(per_dev)
    return 1e3 * mean / obs["result"]["trace_steps"] if mean > 0 else None


def phase_ms_per_step(obs, phase: str):
    phases = traced_phases(obs)
    if phases is None:
        return None
    return ms_per_step(obs, names_of(phases, phase))


def unscoped_pct(obs):
    """Of the device's busy time in the traced window, the share that no
    operation with a phase covers (a loop whose body's operations have one
    is covered by them), all chips together."""
    phases = traced_phases(obs)
    if phases is None:
        return None
    trace = obs["trace"]
    busy = outside = 0.0
    for d in trace.devices:
        whole = trace.busy_intervals(d)
        busy += total(whole)
        outside += total(subtract(whole, intervals(trace, d, phases)))
    return 100.0 * outside / busy if busy > 0 else None


def fused_elsewhere_ms_per_step(obs, phase: str):
    """Device milliseconds a step in fusions that are rooted in another
    phase and hold instructions of this one, mean over the chips: what the
    phase's own number leaves out; 0 where the phase's own number is the
    whole.  None where the program has no instruction of the phase, or
    cannot say what it fused (`utils.timing.program_fusions`; the parent
    commit has none)."""
    phases = traced_phases(obs)
    if phases is None or phase not in phases.values():
        return None
    try:
        from pytorch_ps_mpi_tpu.utils.timing import program_fusions
    except ImportError:
        return None
    held = {fusion for fusion, body in program_fusions(PROGRAM).items()
            if phases.get(fusion) != phase
            and any(phases.get(n) == phase for n in body)}
    return ms_per_step(obs, held) or 0.0


def scoped_collectives(trace, dev, phases) -> list:
    """When a collective **under `ps.exchange`** was under way on this
    device, an asynchronous pair counted from its start to its done
    (`Trace.collective_intervals` on the operations of the phase
    `exchange` alone: a collective that lost the scope is not seen)."""
    names = names_of(phases, "exchange")
    mine = dataclasses.replace(
        dev, ops=[o for o in dev.ops if o.name in names],
        async_ops=[o for o in dev.async_ops if o.name in names])
    return trace.collective_intervals(mine)


def exchange_intervals(trace, dev, phases) -> list:
    """When this device was in the phase `exchange`: the operations under
    `ps.exchange` and nothing else — packing, unpacking and the collectives
    themselves, an asynchronous pair counted from its start to its done."""
    return union(intervals(trace, dev, names_of(phases, "exchange"))
                 + scoped_collectives(trace, dev, phases))


def step_starts(trace, dev, steps: int) -> "list | None":
    """The moments the traced steps start on this device: the recurrences of
    the first operation of the window that runs exactly once a step (an
    instruction outside a loop does; the window opens on an idle chip)."""
    lo, hi = trace.window
    ops = sorted((o for o in dev.ops if lo <= o.start < hi),
                 key=lambda o: o.start)
    counts: dict = {}
    for o in ops:
        counts[o.name] = counts.get(o.name, 0) + 1
    for o in ops:
        if counts[o.name] == steps:
            return [p.start for p in ops if p.name == o.name]
    return None


def bwd_after_exchange_start_pct(obs):
    """Per traced step, of the `backward` + `remat` device time the share
    that lies after the start of the step's first collective under
    `ps.exchange`; mean over the steps, on the chip where it is lowest."""
    phases = traced_phases(obs)
    if phases is None:
        return None
    trace, steps = obs["trace"], obs["result"]["trace_steps"]
    back = names_of(phases, "backward", "remat")
    worst = None
    for d in trace.devices:
        starts = step_starts(trace, d, steps)
        if starts is None:
            return None
        backward = intervals(trace, d, back)
        collectives = scoped_collectives(trace, d, phases)
        shares = []
        for lo, hi in zip(starts, starts[1:] + [trace.window[1]]):
            mine = clip(backward, lo, hi)
            first = clip(collectives, lo, hi)
            if not mine or not first:
                continue
            shares.append(total(clip(mine, first[0][0], hi)) / total(mine))
        if not shares:
            return None
        share = 100.0 * sum(shares) / len(shares)
        worst = share if worst is None else min(worst, share)
    return worst
