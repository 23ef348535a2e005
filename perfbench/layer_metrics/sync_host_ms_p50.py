"""Median duration of the program's `sync.step` spans in the untraced window:
what one `MPI_PS.step(batch, block=False)` costs the caller's thread, batch
placement, dispatch and bookkeeping together."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "sync.step")
