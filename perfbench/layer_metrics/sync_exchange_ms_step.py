"""Device time a step in the phase `exchange`, on the chip where it is
longest (as `collective_ms_step`): the operations under `ps.exchange` alone,
the collectives **and** the packing and unpacking of the buckets round them.
It is read off the scope, not off the collectives' opcodes, so that it is no
less than `collective_ms_step` only if every collective kept the scope
through XLA's passes."""
from perfbench.layer_metrics._sync_phases import (exchange_intervals,
                                                  traced_phases)
from perfbench.trace_reduce import total


def read(obs):
    phases = traced_phases(obs)
    if phases is None:
        return None
    trace = obs["trace"]
    worst = max(total(exchange_intervals(trace, d, phases))
                for d in trace.devices)
    return 1e3 * worst / obs["result"]["trace_steps"] if worst > 0 else None
