"""Device time per step under the program's `swa` scope: the window
layer's attention call (flash kernels at 64 / 128 under `window=512`, and
the transposes and padding round them: `_sambay.py`), forward,
rematerialised forward and backward; mean over the chips."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "swa_flash")
