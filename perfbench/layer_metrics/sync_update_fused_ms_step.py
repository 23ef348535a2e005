"""Device time a step in fusions rooted in another phase that hold
instructions of the phase `update` (XLA fuses the optimizer's rule into the
matrix product that makes its gradient, and the event counts under
`backward`); mean over the chips.  The update's work lies between
`sync_update_ms_step` and that plus this."""
from perfbench.layer_metrics._sync_phases import fused_elsewhere_ms_per_step


def read(obs):
    return fused_elsewhere_ms_per_step(obs, "update")
