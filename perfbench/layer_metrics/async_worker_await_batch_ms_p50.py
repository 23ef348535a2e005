"""Median host time a worker thread waited for its next batch from its
drawer thread, which draws one iteration ahead: the program's
`async.await_batch` span.  Near zero where the batch was ready when the
worker asked; near the draw's own time where the drawer paces the worker."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.await_batch")
