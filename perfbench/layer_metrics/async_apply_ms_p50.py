"""Median host time of stack + apply in the PS loop: the program's
`optim_step_time` (`AsyncPS.timings`), a host span round asynchronously
dispatched work, not device time."""
from perfbench.harness import percentile


def read(obs):
    v = obs["result"].get("apply_s") or []
    return 1e3 * percentile(v, 50) if v else None
