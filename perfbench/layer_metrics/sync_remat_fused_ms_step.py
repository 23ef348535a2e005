"""Device time a step in fusions rooted in another phase that hold
instructions of the phase `remat` (XLA fuses rematerialised elementwise work
into the backward operation that uses it, and the event counts under
`backward`); mean over the chips.  The rematerialised forward lies between
`sync_remat_ms_step` and that plus this.  Absent where the cell's loss has
no checkpoint."""
from perfbench.layer_metrics._sync_phases import fused_elsewhere_ms_per_step


def read(obs):
    return fused_elsewhere_ms_per_step(obs, "remat")
