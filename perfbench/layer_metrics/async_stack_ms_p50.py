"""Median host time of the eager `jnp.stack` over the code leaves in the PS
loop: the program's `async.stack` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.stack")
