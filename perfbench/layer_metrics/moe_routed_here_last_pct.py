"""`moe_routed_here_pct` at the window's last step (see
`moe_routed_here_first_pct`)."""
from perfbench.layer_metrics._kimi import routed_here_pct


def read(obs):
    share = routed_here_pct(obs)
    return None if share is None else float(share[-1])
