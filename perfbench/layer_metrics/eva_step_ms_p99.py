"""`step_ms_p99` for `evabyte-sync-1chip`: the same reader under a name of
this cell's, because the accepted metric lists its `workloads` and this PR
was asked for doubles (PERF.md section 7: fold them together in the next
`benchmark` PR)."""
from perfbench.layer_metrics.step_ms_p99 import read  # noqa: F401
