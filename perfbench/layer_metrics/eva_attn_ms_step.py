"""Device time per step under the program's `eva_attn` scope: EvaByte's
two-set softmax whole — the flash calls over the windows (`eva_local`, inside
it), the summaries' part window by window and the join on the row statistics
— of the four layers, forward, rematerialised forward and backward; mean
over the chips.  The pooling (`eva_summary`) and the rotation (`rope`) lie
outside it."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "eva_attn")
