"""Device time per step under the program's `full_attn` scope: the
attention calls of the full layer and of the cross layer (flash kernels at
64 / 128, causal, and the transposes and padding round them: `_sambay.py`),
forward, rematerialised forward and backward; mean over the chips."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "full_flash")
