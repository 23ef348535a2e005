"""Share of the worker threads' iterations (`async.worker_iter`, all workers,
summed over the window) spent inside `async.enqueue`: how long a finished
gradient waits for room in the bounded queue."""
from perfbench.layer_metrics._async_spans import children_share_pct


def read(obs):
    return children_share_pct(obs, "async.worker_iter", "async.enqueue")
