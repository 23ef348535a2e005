"""What the `sambay` readers share: device time under one of the program's
named scopes (`_kimi.py`), as milliseconds a step and as a share of the
least time `models/sambay.py`'s work functions give that scope.

**How the scopes lie** (`models/sambay.py` of the program).  ``ssm`` is the
selective scan alone, both Mamba layers, forward, rematerialised forward
and backward: not the layer's projections, convolution or gate.  ``swa`` is
the window layer's attention call and ``full_attn`` those of the full layer
and the cross layer: the flash kernels and what the call does round them
(the heads' transposes, the padding of the 64-wide q / k to 128 lanes, the
backward's row sums), so the two `*_flash_*` metrics are read by scope and
not by kernel name, which the three layers share.  ``diff`` is what follows
each of the three calls (subtraction, 128-wide RMSNorm, scaling) and
``gmu`` the gated memory unit whole (two matrix products and the gate
between them, which XLA fuses into them).  None nests in another.

A program without these scopes makes every reader return None.
"""

from perfbench.layer_metrics._kimi import scope_seconds_per_step
from perfbench.layer_metrics.flash_roofline_pct import least_seconds


def scope_ms(obs, scope: str):
    s = scope_seconds_per_step(obs, scope)
    return None if s is None else 1e3 * s


def work_of(obs, kernel: str):
    """The family's least work of ``kernel`` a step, or None where the
    family names none."""
    kernel_work = getattr(obs["family"], "kernel_work", None)
    if kernel_work is None:
        return None
    return kernel_work(obs["result"]["rows_per_chip"]).get(kernel)


def work_ms(obs, kernel: str):
    work = work_of(obs, kernel)
    return None if work is None else scope_ms(obs, work["scope"])


def roofline_pct(obs, kernel: str):
    """Least time the chip could take for ``kernel``'s work (the larger of
    FLOPs over the bf16 peak and bytes over the HBM peak) over the time
    spent under its scope."""
    work = work_of(obs, kernel)
    if work is None or obs["peaks"] is None:
        return None
    s = scope_seconds_per_step(obs, work["scope"])
    if s is None:
        return None
    return 100.0 * least_seconds(work, obs["peaks"])[0] / s
