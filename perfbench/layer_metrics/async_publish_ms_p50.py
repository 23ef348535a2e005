"""Median host time of publishing the new parameters and acknowledging the
gradients consumed: the program's `async.publish` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.publish")
