"""Share of the token-expert assignments that landed on an expert held
here, mean over the MoE layers and the window's steps: `tokens * top_k` are
made a layer, and under uniform routing `held / experts` of them come here
(8 / 256 = 3.125 %)."""
from perfbench.layer_metrics._kimi import routed_here_pct


def read(obs):
    share = routed_here_pct(obs)
    return None if share is None else float(share.mean())
