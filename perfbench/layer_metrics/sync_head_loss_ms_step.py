"""Device time a step under the models' `head_loss` scope (final norm, head,
cross-entropy), forward, rematerialised forward and backward together; mean
over the chips.  A mechanism's share, like `glm_mtp_ms_step`: its operations
lie in the phases too, so never add it to them.  (GLM's `mtp` module passes
through the shared head under `head_loss` as well.)  Only the operations that
also have a phase under `ps.grad` count, so a program without the step's
scopes gives None."""
from perfbench.layer_metrics._sync_phases import (GRAD_PHASES, PROGRAM,
                                                  ms_per_step, names_of,
                                                  traced_phases)

SCOPE = "head_loss"


def read(obs):
    phases = traced_phases(obs)
    if phases is None:
        return None
    from pytorch_ps_mpi_tpu.utils.timing import in_scope, program_scopes
    scopes = program_scopes(PROGRAM)
    names = {n for n in names_of(phases, *GRAD_PHASES)
             if in_scope(scopes[n], SCOPE)}
    return ms_per_step(obs, names)
