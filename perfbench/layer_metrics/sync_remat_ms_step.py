"""Device time a step in the phase `remat`: forward work done a second time
inside the backward (`rematted_computation` under `ps.grad`: `jax.checkpoint`
/ `nn.remat`); mean over the chips.  Absent where the cell's loss has no
checkpoint.  A lower bound: rematerialised elementwise work that XLA fuses
into the backward operation that uses it counts under `backward`
(`sync_remat_fused_ms_step` has the time of such fusions)."""
from perfbench.layer_metrics._sync_phases import phase_ms_per_step


def read(obs):
    return phase_ms_per_step(obs, "remat")
