"""The coverage of the phase split: of the device's busy time in the traced
window, the share under no phase of `MPI_PS.step`'s program — operations
outside every `ps.*` scope, instructions without an `op_name`, and
instructions the registered text does not hold."""
from perfbench.layer_metrics._sync_phases import unscoped_pct


def read(obs):
    return unscoped_pct(obs)
