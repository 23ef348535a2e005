"""Median host time of a worker thread's `device_put` of its batch: the
program's `async.put_batch` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.put_batch")
