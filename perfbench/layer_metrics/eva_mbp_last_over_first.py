"""The eighth prediction head's loss over the first's, mean over the
window's steps, from the `mbp_loss` counter the step logs (`[8]`, head i
scored against byte t + 1 + i): whether the far heads learn as the near one
does."""
from perfbench.layer_metrics._glm import window_counter


def read(obs):
    losses = window_counter(obs, "mbp_loss")
    if losses is None:
        return None
    return float((losses[:, -1] / losses[:, 0]).mean())
