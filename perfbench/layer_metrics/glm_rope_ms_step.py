"""Device time per step under the program's `rope` scope: the rotation of
q's rope columns and of the shared rope key in the six MLA layers, forward,
rematerialised forward and backward; mean over the chips."""
from perfbench.layer_metrics._kimi import scope_seconds_per_step
from perfbench.models.glm_moe import ROPE_SCOPE


def read(obs):
    s = scope_seconds_per_step(obs, ROPE_SCOPE)
    return None if s is None else 1e3 * s
