"""Median host time of a worker thread's call of the jitted `worker_step`
(the dispatch, not the gradient program's device time): the program's
`async.grad` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.grad")
