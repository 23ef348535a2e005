"""Device time per step under the program's `ssd` scope: the state-space
duality scan of the four Mamba-2 layers of `nemotron3-nano-sync-1chip`
(`ops/ssd.py`), forward, rematerialised forward and backward, apart from
the layers' projections, convolution and gated norm; mean over the chips."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "ssd")
