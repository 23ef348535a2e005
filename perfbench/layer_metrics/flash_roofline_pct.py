"""Least time the chip could take for the flash kernels' work of one step
(the larger of FLOPs over the bf16 peak and bytes over the HBM peak, from
`models/gpt2.py:flash_work` and `peaks.json`) over the time they took."""
from perfbench.layer_metrics.flash_ms_step import seconds_per_step


def least_seconds(work: dict, peaks: dict):
    by_flops = work["flops"] / peaks["bf16_flops"]
    by_bytes = work["bytes"] / peaks["hbm_bytes_per_s"]
    return max(by_flops, by_bytes), \
        ("compute" if by_flops >= by_bytes else "memory")


def read(obs):
    s, work = seconds_per_step(obs)
    if s is None or obs["peaks"] is None:
        return None
    return 100.0 * least_seconds(work, obs["peaks"])[0] / s
