"""`sync_bwd_ms_step` for `evabyte-sync-1chip`: the same reader of the
step's phases (`_sync_phases.py`) under a name of this cell's, because the
accepted metric lists its `workloads` (PERF.md section 7: fold them together
in the next `benchmark` PR)."""
from perfbench.layer_metrics.sync_bwd_ms_step import read  # noqa: F401
