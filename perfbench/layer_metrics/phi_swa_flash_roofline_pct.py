"""Least time the chip could take for the window layer's attention of one
step, at the true widths 64 / 128 over the band's `S W - W^2 / 2` pairs a
head (`models/sambay.py:swa_flash_work`, `peaks.json`), over the time spent
under the `swa` scope."""
from perfbench.layer_metrics._sambay import roofline_pct


def read(obs):
    return roofline_pct(obs, "swa_flash")
