"""Calls of the state-space duality scan's kernels a traced step, on the
chip that made the most: the events of the device's `XLA Ops` line whose
HLO instruction is named after `ssd_fwd` or `ssd_bwd` (`ssd_fwd.<n>`:
`ops/flash_attention.py:_named` gives the Mosaic call that name) and that
overlap the traced window, over the traced steps.  By name, as
`flash_fwd_calls_step` counts the flash forward.

Each Mamba-2 layer of a rematerialised step calls the forward kernel twice
(forward, rematerialised forward) and the backward kernel once: 12 calls
in the four layers of `nemotron3-nano-sync-1chip`.  A program that runs
the scan in plain `jax.numpy` makes no such call, and the reader returns
None."""

KERNELS = ("ssd_fwd", "ssd_bwd")


def is_call(op) -> bool:
    return any(op.name == k or op.name.startswith(k + ".") for k in KERNELS)


def read(obs):
    trace, steps = obs["trace"], obs["result"]["trace_steps"]
    if trace is None or not steps or not trace.devices:
        return None
    lo, hi = trace.window
    most = max(sum(1 for o in d.ops if is_call(o) and o.end > lo
                   and o.start < hi)
               for d in trace.devices)
    return most / steps if most else None
