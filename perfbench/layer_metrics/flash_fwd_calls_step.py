"""Calls of the flash forward kernel a traced step, on the chip that made
the most: the events of the device's `XLA Ops` line whose HLO instruction
is named after the kernel (`flash_fwd.<n>`: `ops/flash_attention.py:_named`
gives the Mosaic call that name) and that overlap the traced window, over
the traced steps.  By name and not by scope: the scopes round a flash call
also hold its transposes and padding; and not by the kernel's metadata,
which the reads of the call's outputs carry too.

An attention layer under a bare `nn.remat` calls the kernel twice a step,
once in the forward and once in the rematerialised forward; one whose
remat keeps the kernel's output and row statistics calls it once.  A trace
without such a call reads None."""

KERNEL = "flash_fwd"


def is_call(op) -> bool:
    return op.name == KERNEL or op.name.startswith(KERNEL + ".")


def read(obs):
    trace, steps = obs["trace"], obs["result"]["trace_steps"]
    if trace is None or not steps or not trace.devices:
        return None
    lo, hi = trace.window
    most = max(sum(1 for o in d.ops if is_call(o) and o.end > lo
                   and o.start < hi)
               for d in trace.devices)
    return most / steps if most else None
