"""Device time per step under the program's `diff` scope: what follows the
attention call in the three differential layers (``o1 - lam o2``, the
128-wide RMSNorm, the scaling by ``1 - lam_init``), forward, rematerialised
forward and backward; mean over the chips."""
from perfbench.layer_metrics._sambay import scope_ms
from perfbench.models.sambay import DIFF_SCOPE


def read(obs):
    return scope_ms(obs, DIFF_SCOPE)
