"""Device time a step of the operations **rooted** in the phase `update`
(`ps.update`: the clip, the optimizer's rule over every parameter, the EMA);
mean over the chips.  A lower bound of the update's work, and one that moves
with XLA's fusion choices: where the compiler fuses the rule into the matrix
product that makes its gradient, the fusion counts under `backward`
(`sync_update_fused_ms_step` has the time of such fusions).  Not "the optimizer's
share" on its own."""
from perfbench.layer_metrics._sync_phases import phase_ms_per_step


def read(obs):
    return phase_ms_per_step(obs, "update")
