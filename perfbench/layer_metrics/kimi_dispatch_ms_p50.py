"""`dispatch_ms_p50` for `kimi-linear-sync-1chip` (see
`kimi_step_ms_p50`): the host time of one `MPI_PS.step` call, the counters'
hand-over to the counter log included."""
from perfbench.layer_metrics.dispatch_ms_p50 import read  # noqa: F401
