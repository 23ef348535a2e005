"""Device time per step under the program's `mamba` scope: the four Mamba-2
mixers whole (their projections, convolution, scan and gated norm),
forward, rematerialised forward and backward; mean over the chips.  The
`ssd` scope lies inside it: `nemo_ssd_ms_step` is part of this."""
from perfbench.layer_metrics._sambay import scope_ms
from perfbench.models.nemotron_h import MAMBA_SCOPE


def read(obs):
    return scope_ms(obs, MAMBA_SCOPE)
