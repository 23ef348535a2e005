"""Median host time to dispatch one step: the program's own `isend_time`
(`MPI_PS.timings`), a host span round the call of the jitted step."""
from perfbench.harness import percentile


def read(obs):
    v = obs["result"].get("dispatch_s") or []
    return 1e3 * percentile(v, 50) if v else None
