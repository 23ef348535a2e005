"""Device time a step in the phase `forward` of `MPI_PS.step`'s program: the
operations under `ps.grad` that are neither the backward nor a forward run a
second time (see `_sync_phases.py`); mean over the chips."""
from perfbench.layer_metrics._sync_phases import phase_ms_per_step


def read(obs):
    return phase_ms_per_step(obs, "forward")
