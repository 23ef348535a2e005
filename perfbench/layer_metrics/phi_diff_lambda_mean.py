"""Mean over the window's steps and the three attention layers of the
differential weight ``lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam_init``,
from the `diff_lambda` counter the step logs: how much of the second
softmax each layer subtracts (0.65 at initialisation: 0.356, 0.796, 0.798
for the published layers 1, 17, 19)."""
from perfbench.layer_metrics._glm import window_counter


def read(obs):
    lam = window_counter(obs, "diff_lambda")
    return None if lam is None else float(lam.mean())
