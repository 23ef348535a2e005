"""Median time between the completions of successive steps in the window,
two steps in flight (host clock at `block_until_ready`)."""
from perfbench.harness import percentile


def intervals_ms(obs):
    done = obs["result"].get("step_done_at") or []
    return [1e3 * (b - a) for a, b in zip(done, done[1:])]


def read(obs):
    v = intervals_ms(obs)
    return percentile(v, 50) if v else None
