"""Mean over the window's steps and the four Mamba-2 layers of the share
of a head's state that crosses one whole 128-token chunk, ``exp(sum_chunk
dt A)`` averaged over heads and chunks, from the `ssd_carry` counter the
step logs: how much the carried state, and so the pass between chunks,
still weighs."""
from perfbench.layer_metrics._glm import window_counter


def read(obs):
    carry = window_counter(obs, "ssd_carry")
    return None if carry is None else float(carry.mean())
