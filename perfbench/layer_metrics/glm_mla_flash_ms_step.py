"""`mla_flash_ms_step` for `glm47-flash-sync-1chip`: device time per step
inside the flash kernels of the six MLA layers, the MTP block's among them
(`flash_fwd`, `flash_bwd_dkdv`, `flash_bwd_dq`, by the kernel name each
Mosaic call carries: `models/glm_moe.py:flash_work`'s `match`)."""
from perfbench.layer_metrics.mla_flash_ms_step import read  # noqa: F401
