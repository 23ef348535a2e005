"""Least time the chip could take for the state-space duality scan of one
step (the larger of its chunked products' FLOPs over the bf16 peak and the
bytes of its inputs, outputs, their gradients and the chunk states over the
HBM peak: `models/nemotron_h.py:ssd_work`, `peaks.json`) over the time
spent under the `ssd` scope; the convention of `phi_ssm_roofline_pct`."""
from perfbench.layer_metrics._sambay import roofline_pct


def read(obs):
    return roofline_pct(obs, "ssd")
