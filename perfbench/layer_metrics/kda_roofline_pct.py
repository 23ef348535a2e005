"""Least time the chip could take for the KDA recurrence of one step (the
larger of its token-by-token FLOPs over the bf16 peak and the bytes of its
inputs, outputs and their gradients over the HBM peak:
`models/kimi_linear.py:kda_work`, `peaks.json`) over the time spent under
the `kda` scope."""
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.layer_metrics.kda_ms_step import seconds_per_step


def read(obs):
    s, work = seconds_per_step(obs)
    if s is None or obs["peaks"] is None:
        return None
    return 100.0 * least_seconds(work, obs["peaks"])[0] / s
