"""Least time the chip could take for the selective scan of one step (the
larger of its token-by-token FLOPs over the bf16 peak and the bytes of its
inputs, outputs and their gradients over the HBM peak:
`models/sambay.py:ssm_work`, `peaks.json`; the bytes bound it) over the time
spent under the `ssm` scope."""
from perfbench.layer_metrics._sambay import roofline_pct


def read(obs):
    return roofline_pct(obs, "ssm")
