"""Device time per step during which a collective (all-reduce,
reduce-scatter, all-gather, collective-permute) was under way, on the chip
where that is longest.  From the device trace; absent where the trace holds
no collective (one chip)."""


def read(obs):
    trace, steps = obs["trace"], obs["result"]["trace_steps"]
    if trace is None or not steps or not trace.devices:
        return None
    worst = max(trace.collective_s())
    return 1e3 * worst / steps if worst > 0 else None
