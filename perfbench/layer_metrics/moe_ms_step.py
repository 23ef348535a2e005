"""Device time per step under the program's `moe` scope: router, sorting
and grouping, the held experts' matrix products, combine and the shared
expert, forward, rematerialised forward and backward; mean over the chips."""
from perfbench.layer_metrics._kimi import scope_seconds_per_step
from perfbench.models.kimi_linear import MOE_SCOPE


def read(obs):
    s = scope_seconds_per_step(obs, MOE_SCOPE)
    return None if s is None else 1e3 * s
