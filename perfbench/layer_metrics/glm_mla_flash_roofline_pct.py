"""`mla_flash_roofline_pct` for `glm47-flash-sync-1chip`: least time the
chip could take for the flash kernels of one step at `[20 * rows, 8192,
256 / 256]`, six layers, causal (`models/glm_moe.py:flash_work`, counted
from the shape whatever tiles run it; `peaks.json`), over the time they
took."""
from perfbench.layer_metrics.mla_flash_roofline_pct import read  # noqa: F401
