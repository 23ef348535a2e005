"""`input_wait_pct` for `kimi-linear-sync-1chip` (see `kimi_step_ms_p50`):
the share of the window spent drawing the next batch from the pool."""
from perfbench.layer_metrics.input_wait_pct import read  # noqa: F401
