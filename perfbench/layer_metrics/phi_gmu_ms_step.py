"""Device time per step under the program's `gmu` scope: the gated memory
unit whole, ``W_out(silu(W_in u) * m)`` with the memory layer's scan output
``m`` (XLA fuses the gate product into the two matrix products, so they are
read together), forward, rematerialised forward and backward; mean over the
chips."""
from perfbench.layer_metrics._sambay import scope_ms
from perfbench.models.sambay import GMU_SCOPE


def read(obs):
    return scope_ms(obs, GMU_SCOPE)
