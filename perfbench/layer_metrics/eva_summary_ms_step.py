"""Device time per step under the program's `eva_summary` scope: the learnt
pooling of every 16 keys and values into one summary a head, of the four
layers, forward, rematerialised forward and backward; mean over the chips.
Plain `jax.numpy`, no kernel of its own, so it has no roofline share
(`models/evabyte.py:eva_summary_work` gives its least work all the same)."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "eva_summary")
