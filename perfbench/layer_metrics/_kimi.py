"""What the `kimi_linear` readers share: device time under one of the
program's named scopes, and the expert load the steps logged.

**Scopes.**  The trace names a device operation by its HLO instruction
(`fusion.412`); the `jax.named_scope` it was traced under is in the
instruction's `op_name`, which only the compiled program's text carries.
`MPI_PS.step` compiles its program ahead of the first call and registers
the compiled program's text (`utils.timing.program_scopes`); the first
reader that asks has it parsed, after the window.  An
operation belongs to a scope when the scope's name is a whole component of
its `op_name`; a fusion belongs where XLA says its root does.  Loops show
both as one event and as their body's events, so time is the union of the
intervals, not their sum.

**Load.**  `MPI_PS.step` appends each step's `aux["counters"]` to
`utils.timing.counter_log()` as device arrays; here they are fetched, after
the window.  The log has no clock but it is in step order, and the window's
steps are the `attempted` before the last `trace_steps`.  On several chips
a counter is the mean over the chips.

A program without these (the parent commit) makes every reader return None.
"""

from perfbench.trace_reduce import clip, total, union

PROGRAM = "MPI_PS.step"


def scope_seconds_per_step(obs, scope: str):
    trace, steps = obs["trace"], obs["result"]["trace_steps"]
    if trace is None or not steps or not trace.devices:
        return None
    try:
        from pytorch_ps_mpi_tpu.utils.timing import in_scope, program_scopes
    except ImportError:
        return None
    scopes = program_scopes(PROGRAM)
    if not scopes:
        return None
    names = {n for n, op_name in scopes.items() if in_scope(op_name, scope)}
    per_dev = [total(clip(union((o.start, o.end) for o in d.ops
                                if o.name in names), *trace.window))
               for d in trace.devices]
    mean = sum(per_dev) / len(per_dev)
    return mean / steps if mean > 0 else None


def expert_load(obs):
    """``[window steps, MoE layers, held experts + 1]``: assignments on each
    held expert and their sum, or None."""
    try:
        from pytorch_ps_mpi_tpu.utils.timing import counter_log
    except ImportError:
        return None
    import jax
    import numpy as np
    loads = [r["values"]["moe_load"] for r in counter_log().records(PROGRAM)
             if "moe_load" in r["values"]]
    end = len(loads) - obs["result"]["trace_steps"]
    loads = loads[max(0, end - obs["result"]["attempted"]):end]
    return np.stack(jax.device_get(loads)) if loads else None


def routed_here_pct(obs):
    """Per window step, the share of the `tokens * top_k` assignments a MoE
    layer makes that landed on a held expert, mean over the layers."""
    load = expert_load(obs)
    if load is None:
        return None
    s = obs["family"].s
    made = obs["result"]["rows_per_chip"] * obs["family"].seq_len * s["top_k"]
    return 100.0 * load[..., -1].mean(axis=-1) / made
