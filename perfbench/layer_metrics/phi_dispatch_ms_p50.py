"""`dispatch_ms_p50` for `phi4flash-sync-1chip`: the same reader under a name
of this cell's, because the accepted metric lists its `workloads` and a PR
that adds a cell may not extend that list (PERF.md section 7: fold them
together in the next `benchmark` PR)."""
from perfbench.layer_metrics.dispatch_ms_p50 import read  # noqa: F401
