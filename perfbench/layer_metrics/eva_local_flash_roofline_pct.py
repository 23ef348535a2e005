"""Least time the chip could take for the local part of EvaByte's attention
of one step, at the true width 128 over the windows' causal pairs
(`models/evabyte.py:eva_local_flash_work`, `peaks.json`), over the time spent
under the `eva_local` scope."""
from perfbench.layer_metrics._sambay import roofline_pct


def read(obs):
    return roofline_pct(obs, "eva_local_flash")
