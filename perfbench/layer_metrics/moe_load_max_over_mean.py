"""Assignments on the fullest held expert over the mean of the held
experts, mean over the MoE layers and the steps: 1 is a balanced share,
`len(held)` is every routed token on one expert."""
from perfbench.layer_metrics._kimi import expert_load


def read(obs):
    load = expert_load(obs)
    if load is None:
        return None
    held = load[..., :-1]
    mean = held.mean(axis=-1)
    ok = mean > 0
    return float((held.max(axis=-1)[ok] / mean[ok]).mean()) if ok.any() \
        else None
