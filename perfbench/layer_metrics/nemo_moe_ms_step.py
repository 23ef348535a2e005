"""`moe_ms_step` for `nemotron3-nano-sync-1chip`: device time per step
under the program's `moe` scope, the four relu² expert layers whole
(router, sort, grouped products, combine, shared expert)."""
from perfbench.layer_metrics.moe_ms_step import read  # noqa: F401
