"""Device time per step under the program's `mtp` scope: everything of the
multi-token-prediction module (`eh_proj`, its block — attention and expert
layer, which `glm_mla_flash_ms_step` and `glm_moe_ms_step` count too, see
`_glm.py` —, its norm, its pass through the shared head and its
cross-entropy), forward, rematerialised forward and backward; mean over the
chips."""
from perfbench.layer_metrics._kimi import scope_seconds_per_step
from perfbench.models.glm_moe import MTP_SCOPE


def read(obs):
    s = scope_seconds_per_step(obs, MTP_SCOPE)
    return None if s is None else 1e3 * s
