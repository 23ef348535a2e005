"""`loss_mtp / loss_main`, mean over the window's steps, from the two
counters the step logs: how much harder the token after next is than the
next one, through the same embedding and head."""
from perfbench.layer_metrics._glm import window_counter


def read(obs):
    main = window_counter(obs, "loss_main")
    mtp = window_counter(obs, "loss_mtp")
    if main is None or mtp is None:
        return None
    return float((mtp / main).mean())
