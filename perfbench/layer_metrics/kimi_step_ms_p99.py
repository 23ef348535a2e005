"""`step_ms_p99` for `kimi-linear-sync-1chip` (see `kimi_step_ms_p50`):
with ~30 steps a window it is the slowest step, which is what shows a stall
that the mean rate hides."""
from perfbench.layer_metrics.step_ms_p99 import read  # noqa: F401
