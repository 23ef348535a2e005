"""Device time a step in the phase `backward`: the transposed operations under
`ps.grad`, the rematerialised forward apart (`sync_remat_ms_step`) and, in
`sync_mode="overlap"`, the bucket hooks' sums apart (`exchange`); mean over
the chips."""
from perfbench.layer_metrics._sync_phases import phase_ms_per_step


def read(obs):
    return phase_ms_per_step(obs, "backward")
