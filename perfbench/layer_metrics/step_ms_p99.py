"""99th percentile of the time between completions of successive steps in
the window.  With fewer than a thousand steps it is close to the slowest
step; the sample count is the cell's `attempted` less one."""
from perfbench.harness import percentile
from perfbench.layer_metrics.step_ms_p50 import intervals_ms


def read(obs):
    v = intervals_ms(obs)
    return percentile(v, 99) if v else None
