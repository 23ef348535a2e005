"""Share of a step during which no operation ran on the chip, mean over the
chips: 1 - (device time per step in the trace) / (time per step in the
untraced window).

Not the idle share of the traced window itself: the profiler slows the copy
of a large input batch to the device, so the traced steps of a cell that
feeds 154 MB a step run three times slower than the same steps untraced,
while the durations of the device's operations stay the same (PERF.md,
Findings of PR 22).  The traced window's own busy and window seconds are in
the result line's `device`."""


def idle_pcts(obs):
    """Per chip, or None where there is no device trace."""
    trace, r = obs["trace"], obs["result"]
    if trace is None or not trace.devices or not r["trace_steps"]:
        return None
    period = (r["window"][1] - r["window"][0]) / r["attempted"]
    return [100.0 * (1.0 - busy / r["trace_steps"] / period)
            for busy in trace.busy_s()]


def read(obs):
    idle = idle_pcts(obs)
    return None if idle is None else sum(idle) / len(idle)
