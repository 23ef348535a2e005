"""`moe_routed_here_first_pct` for `glm47-flash-sync-1chip`: the share at
the window's first step; the router's gradient sees only the experts held
here, so the share drifts upward while the cell trains."""
from perfbench.layer_metrics.moe_routed_here_first_pct import read  # noqa: F401
