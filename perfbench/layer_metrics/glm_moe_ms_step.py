"""`moe_ms_step` for `glm47-flash-sync-1chip`: device time per step under
the program's `moe` scope, all five expert layers (four of the main model,
the MTP block's, which lies under `mtp` too: `_glm.py`)."""
from perfbench.layer_metrics.moe_ms_step import read  # noqa: F401
