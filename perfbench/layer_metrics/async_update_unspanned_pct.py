"""Self time of the PS loop's `async.update` spans (their duration minus
what their children `async.fill`, `.stack`, `.apply`, `.publish` and
`.read_loss` cover), in percent of their duration, summed over the window:
the spans add up, or they do not."""
from perfbench.layer_metrics._async_spans import children_share_pct


def read(obs):
    covered = children_share_pct(obs, "async.update")
    return None if covered is None else 100.0 - covered
