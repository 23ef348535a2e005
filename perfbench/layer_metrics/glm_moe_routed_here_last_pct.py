"""`moe_routed_here_last_pct` for `glm47-flash-sync-1chip`: the share at
the window's last step."""
from perfbench.layer_metrics.moe_routed_here_last_pct import read  # noqa: F401
