"""`device_idle_pct` of the chip that was idle longest."""
from perfbench.layer_metrics.device_idle_pct import idle_pcts


def read(obs):
    idle = idle_pcts(obs)
    return None if idle is None else max(idle)
