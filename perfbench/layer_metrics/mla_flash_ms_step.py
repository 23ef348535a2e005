"""Device time per step inside the flash attention kernels of the MLA
layers (`flash_fwd`, `flash_bwd_dkdv`, `flash_bwd_dq`), matched by the
kernel name each Mosaic call carries
(`models/kimi_linear.py:flash_work`'s `match`); mean over the chips."""
from perfbench.layer_metrics.flash_ms_step import seconds_per_step


def read(obs):
    s, _ = seconds_per_step(obs)
    return None if s is None else 1e3 * s
