"""Model FLOP/s utilization: FLOPs the forward and backward passes need per
sample (the family's shape formula; recomputation not counted) times
samples per second per chip, over the chip's published bf16 peak."""


def read(obs):
    if obs["peaks"] is None:
        return None
    return 100.0 * obs["family"].flops_per_sample() \
        * obs["samples_per_s_chip"] / obs["peaks"]["bf16_flops"]
