"""Device time per step under the program's `eva_local` scope: the flash
calls over the windows' own bytes (`[rows x 4, 2048, 16, 128]` causal at
128 / 128, with the row statistics as a second output) and the transposes
round them, forward, rematerialised forward and backward; mean over the
chips."""
from perfbench.layer_metrics._sambay import work_ms


def read(obs):
    return work_ms(obs, "eva_local_flash")
