"""Median host time a worker thread spent in `batch_fn`, drawing its next
batch on the host: the program's `async.draw` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.draw")
