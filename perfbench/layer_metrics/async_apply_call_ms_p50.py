"""Median host time of the call of `_apply_weighted` in the PS loop (the
dispatch of the jitted reduce and update, and whatever the runtime makes the
caller wait for): the program's `async.apply` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.apply")
