"""Least time the chip could take for EvaByte's attention of one step, both
key sets in one pass at the true width 128 over the local and the summaries'
pairs (`models/evabyte.py:eva_attn_work`, `peaks.json`; forward and backward,
the rematerialised forward not counted), over the time spent under the
`eva_attn` scope."""
from perfbench.layer_metrics._sambay import roofline_pct


def read(obs):
    return roofline_pct(obs, "eva_attn")
