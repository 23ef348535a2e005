"""Device time per step under the program's `kda` scope: the KDA recurrence
(`ops/kda.py`), forward, rematerialised forward and backward, apart from
the layer's projections, convolutions and gates; mean over the chips."""
from perfbench.layer_metrics._kimi import scope_seconds_per_step


def seconds_per_step(obs):
    work = obs["family"].kernel_work(obs["result"]["rows_per_chip"])
    if "kda" not in work:
        return None, None
    return scope_seconds_per_step(obs, work["kda"]["scope"]), work["kda"]


def read(obs):
    s, _ = seconds_per_step(obs)
    return None if s is None else 1e3 * s
