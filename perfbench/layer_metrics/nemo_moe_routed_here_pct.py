"""`moe_routed_here_pct` for `nemotron3-nano-sync-1chip`: share of the
`tokens * top_k` assignments a layer makes that landed on an expert held
here, mean over the four expert layers and the window's steps; under
uniform routing `held / experts` = 8 / 128 = 6.25 %."""
from perfbench.layer_metrics.moe_routed_here_pct import read  # noqa: F401
