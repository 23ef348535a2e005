"""What the `glm_moe` readers add to `_kimi.py`'s (device time under a
scope, the expert load): a scalar counter of the window's steps.

**How the scopes nest** (`models/glm_moe.py`).  The MTP module is a whole
block of its own, so its operations carry two of the names the readers
look for: its flash calls count in `glm_mla_flash_ms_step` (matched by
kernel name, all six layers) and lie under `mtp` too; its expert layer
lies under `moe` (all five layers, `glm_moe_ms_step`) and under `mtp`; its
rotations under `rope` and `mtp`.  `glm_mtp_ms_step` is therefore not to be
added to the other three: it is the module's share of the step, they are
the mechanisms' shares.
"""

from perfbench.layer_metrics._kimi import PROGRAM


def window_counter(obs, name: str):
    """``[window steps]`` of the scalar counter ``name`` that
    `MPI_PS.step` logged (`utils.timing.counter_log()`, in step order: the
    window's steps are the `attempted` before the last `trace_steps`), or
    None where the program logs none."""
    try:
        from pytorch_ps_mpi_tpu.utils.timing import counter_log
    except ImportError:
        return None
    import jax
    import numpy as np
    values = [r["values"][name] for r in counter_log().records(PROGRAM)
              if name in r["values"]]
    end = len(values) - obs["result"]["trace_steps"]
    values = values[max(0, end - obs["result"]["attempted"]):end]
    return np.asarray(jax.device_get(values), np.float64) if values else None
