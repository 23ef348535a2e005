"""`moe_routed_here_pct` at the window's first step: the router's gradient
sees only the experts held here, so the share drifts upward while the cell
trains; first and last say by how much inside one window."""
from perfbench.layer_metrics._kimi import routed_here_pct


def read(obs):
    share = routed_here_pct(obs)
    return None if share is None else float(share[0])
