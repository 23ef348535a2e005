"""Least time the chip could take for the MLA layers' flash kernels of one
step, at a q / k width of 192 and a v width of 128
(`models/kimi_linear.py:flash_work`, `peaks.json`), over the time they
took."""
from perfbench.layer_metrics.flash_ms_step import seconds_per_step
from perfbench.layer_metrics.flash_roofline_pct import least_seconds


def read(obs):
    s, work = seconds_per_step(obs)
    if s is None or obs["peaks"] is None:
        return None
    return 100.0 * least_seconds(work, obs["peaks"])[0] / s
