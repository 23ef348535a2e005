"""Device time per step under the program's `ssm` scope: the selective
state-space scan of the two Mamba layers (`ops/selective_scan.py`), forward,
rematerialised forward and backward, apart from the layers' projections,
convolution and gate; mean over the chips."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "ssm")
