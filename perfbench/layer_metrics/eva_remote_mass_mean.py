"""Mean over the window's steps and the four layers of the share of a
query's normaliser that the chunk summaries hold (queries with at least one
earlier window), from the `eva_remote_mass` counter the step logs: above 0
while the summaries' path is live; at initialisation near 128 w / (128 w +
the window's own bytes so far)."""
from perfbench.layer_metrics._glm import window_counter


def read(obs):
    mass = window_counter(obs, "eva_remote_mass")
    return None if mass is None else float(mass.mean())
