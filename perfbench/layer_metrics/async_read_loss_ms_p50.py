"""Median host time of reading the losses of an update back from the device,
the one host sync of the PS loop: the program's `async.read_loss` span."""
from perfbench.layer_metrics._async_spans import median_ms


def read(obs):
    return median_ms(obs, "async.read_loss")
