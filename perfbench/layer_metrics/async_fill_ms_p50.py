"""Median host time the PS loop waited to fill its quota of gradients: the
program's `comm_wait` (`AsyncPS.timings`), a host span, not device time."""
from perfbench.harness import percentile


def read(obs):
    v = obs["result"].get("fill_s") or []
    return 1e3 * percentile(v, 50) if v else None
