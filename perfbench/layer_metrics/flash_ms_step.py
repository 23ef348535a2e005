"""Device time per step inside the flash attention kernels (`_fwd_kernel`,
`_bwd_dkdv_kernel`, `_bwd_dq_kernel`), mean over the chips: the step's
Mosaic custom calls, which the trace cannot yet tell apart by name."""


def seconds_per_step(obs):
    trace, steps = obs["trace"], obs["result"]["trace_steps"]
    work = obs["family"].kernel_work(obs["result"]["rows_per_chip"])
    if trace is None or not steps or "flash" not in work or not trace.devices:
        return None, None
    per_dev = trace.kernel_seconds(work["flash"]["match"])
    mean = sum(per_dev) / len(per_dev)
    return (mean / steps if mean > 0 else None), work["flash"]


def read(obs):
    s, _ = seconds_per_step(obs)
    return None if s is None else 1e3 * s
