"""Median duration of the program's `sync.shard_batch` spans in the untraced
window: `MPI_PS._shard_batch`, the batch's `device_put` onto the mesh, the
part of `sync.step` before the dispatch that `dispatch_ms_p50` does not
see."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "sync.shard_batch")
