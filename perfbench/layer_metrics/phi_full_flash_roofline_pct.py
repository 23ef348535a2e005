"""Least time the chip could take for the attention of the full layer and
the cross layer of one step, at the true widths 64 / 128 over the causal
half of the square (`models/sambay.py:full_flash_work`, `peaks.json`), over
the time spent under the `full_attn` scope."""
from perfbench.layer_metrics._sambay import roofline_pct


def read(obs):
    return roofline_pct(obs, "full_flash")
