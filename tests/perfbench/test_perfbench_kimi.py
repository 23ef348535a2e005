"""The `kimi_linear` family's shape formulas against counts made by hand,
its configuration file against the published one, and its readers against
a hand-made trace."""

import json
import os

import pytest

from perfbench import harness
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.models import kimi_linear as kl

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PUBLISHED = {   # config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}


@pytest.fixture(scope="module")
def config():
    path = os.path.join(ROOT, "perfbench", "configs",
                        "kimi-linear-48b-a3b.json")
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def s(config):
    return kl.sizes(config, rehearse=False)


def test_every_published_key_is_kept_or_listed_as_reduced(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k) != v}
    assert changed == {"num_experts", "vocab_size"}
    assert changed <= set(config["reduced"])
    assert config["num_experts_published"] == 256
    assert config["vocab_size_published"] == 163840
    assert config["vocab_size"] * 8 == 163840
    assert config["num_experts"] == len(config["experts_held"]) == 8
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    # one leading dense layer and a whole 3 : 1 period after it
    assert config["num_layers"] == 5
    assert linear["kda_layers"] == [1, 2, 3, 5]
    assert linear["full_attn_layers"] == [4]
    assert set(config["reduced"]) == set(config["reduced_why"])


def test_parameter_count_by_hand(s):
    kda = (4 * 2304 * 4096            # q, k, v, o projections
           + 3 * 4096 * 4             # three 4-tap depthwise convolutions
           + 2 * (2304 * 128 + 128 * 4096)    # decay gate, output gate
           + 2304 * 32                # beta
           + 32 + 4096 + 128)         # A_log, dt_bias, output norm
    assert kda == 39_514_272
    mla = (2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
           + 512)                     # the latent's norm
    assert mla == 29_114_880
    expert = 3 * 2304 * 1024
    moe = 8 * expert + expert + 2304 * 256 + 256    # held, shared, router, bias
    dense = 3 * 2304 * 9216
    norms = 2 * 2304
    layers = (kda + dense) + 3 * (kda + moe) + (mla + moe) + 5 * norms
    vocab = 2 * 20480 * 2304 + 2304   # embedding, head, final norm
    assert kl.total_params(s) == layers + vocab == 602_434_432
    # 16 bytes a parameter (f32 parameter, gradient, Adam's two moments)
    assert 16 * kl.total_params(s) / 1e9 == pytest.approx(9.64, abs=0.005)


def test_matmul_parameters_and_flops_per_token_by_hand(s):
    kda = 39_514_272 - 32 - 4096 - 128
    mla = 29_114_880 - 512
    expert = 3 * 2304 * 1024
    moe = 2304 * 256 + expert + 0.25 * expert   # router, shared, 8 * 8 / 256
    want = 4 * kda + mla + 3 * 2304 * 9216 + 4 * moe + 2304 * 20480
    assert kl.matmul_params(s) == want == pytest.approx(335.79e6, rel=1e-4)
    assert 4 * kda / want == pytest.approx(0.47, abs=0.005)
    attention = 3 * 8192 * 32 * (192 + 128)         # one MLA layer
    recurrence = 4 * 3 * 7 * 32 * 128 * 128         # four KDA layers
    assert kl.flops_per_sample(s, 8192) == 6 * want + attention + recurrence
    assert kl.flops_per_sample(s, 8192) == pytest.approx(2.310e9, rel=1e-3)


def test_kda_work_by_hand(s):
    one = dict(s, kda_layers=(1,))
    w = kl.kda_work(one, batch=2, seq_len=8192)
    tokens = 2 * 8192
    # per head and token: decay 128*128, S^T k, the write and S^T q at
    # 2*128*128 each; forward once, backward twice
    assert w["flops"] == 3 * 7 * 128 * 128 * 32 * tokens
    wide = tokens * 4096                  # one [B, S, 32, 128] tensor
    heads = tokens * 32
    forward = wide * (2 + 2 + 2) + wide * 4 + heads * 4 + wide * 2
    backward = forward + wide * (2 + 2 + 2) + wide * 4 + heads * 4
    assert w["bytes"] == forward + backward
    assert w["scope"] == "kda"
    assert kl.kda_work(s, 2, 8192)["bytes"] == 4 * w["bytes"]
    least, bound = least_seconds(kl.kda_work(s, 2, 8192),
                                 harness.load_peaks("TPU v5 lite"))
    assert bound == "memory"
    assert least == pytest.approx(9.152e9 / 819e9, rel=1e-3)


def test_flash_work_at_192_and_128_by_hand(s):
    w = kl.flash_work(s, batch=2, seq_len=8192)
    half_square = 2 * 32 * 8192 * 8192 // 2   # causal, per unit of width
    forward = 2 * half_square * (192 + 128)          # QK^T, PV
    backward = 2 * half_square * (192 + 128 + 128 + 192 + 192)
    assert w["flops"] == forward + backward
    qk = 2 * 8192 * 32 * 192 * 2                     # bf16 [B, S, H, 192]
    v = 2 * 8192 * 32 * 128 * 2
    stats = 2 * 32 * 8192 * 4
    assert w["bytes"] == (2 * qk + 2 * v + stats) \
        + (2 * qk + 3 * v + stats) + (2 * qk + v)
    assert w["match"] == ('"kernel":"flash_fwd"', '"kernel":"flash_bwd_dkdv"',
                          '"kernel":"flash_bwd_dq"')
    _, bound = least_seconds(w, harness.load_peaks("TPU v5 lite"))
    assert bound == "compute"


def test_rehearsal_sizes_keep_the_pattern(config):
    toy = kl.sizes(config, rehearse=True)
    assert toy["n_layers"] == 5 and toy["kda_layers"] == (1, 2, 3, 5)
    assert toy["mla_layers"] == (4,) and toy["first_k_dense"] == 1
    assert len(toy["experts_held"]) < toy["n_experts"]
    assert kl.total_params(toy) < 1_000_000


def test_the_program_builds_what_the_formulas_count(config):
    """The rehearsal-sized model's parameter tree has exactly the count the
    formula gives: the formula counts this program, not another."""
    with open(os.path.join(ROOT, "perfbench", "workloads",
                           "kimi-linear-sync-1chip.json")) as f:
        cell = json.load(f)
    family = kl.build(config, {**cell, **cell["rehearsal"]},
                      impl="interpret", rehearse=True)
    params = family.init_params(0)
    assert sum(p.size for p in params.values()) \
        == kl.total_params(family.s)
    assert family.samples_per_row == cell["rehearsal"]["seq_len"]


def test_scope_readers_on_a_hand_trace():
    """Device time under a scope is the union of its operations' intervals
    (a loop shows as one event and as its body's), a step's share of it."""
    from perfbench.layer_metrics import _kimi, kda_ms_step, moe_ms_step
    from perfbench.trace_reduce import DeviceTrace, Op, Trace
    from pytorch_ps_mpi_tpu.utils import timing

    text = """
  %while.1 = (f32[4]) while(%t), metadata={op_name="jit(spmd_step)/block_0/attn/kda/while"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_0/attn/kda/while/body/mul"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_0/attn/q_proj/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/transpose(jvp(block_1))/moe/dot_general"}
"""
    timing.register_program(_kimi.PROGRAM, lambda: text)
    us = 1e-6
    ops = [Op("while.1", 10 * us, 50 * us), Op("fusion.2", 20 * us, 30 * us),
           Op("fusion.3", 50 * us, 60 * us), Op("fusion.4", 60 * us, 90 * us),
           Op("fusion.2", 95 * us, 99 * us)]
    trace = Trace(devices=[DeviceTrace(0, ops=ops)], spans=[],
                  window=(0.0, 100 * us))

    class Family:
        s = {}

        def kernel_work(self, rows):
            return {"kda": {"flops": 0.0, "bytes": 819e9 * 11 * us,
                            "scope": "kda"}}

    obs = {"trace": trace, "family": Family(),
           "result": {"trace_steps": 2, "rows_per_chip": 2},
           "peaks": harness.load_peaks("TPU v5 lite")}
    assert kda_ms_step.read(obs) == pytest.approx(1e3 * 44 * us / 2)
    assert moe_ms_step.read(obs) == pytest.approx(1e3 * 30 * us / 2)
    from perfbench.layer_metrics import kda_roofline_pct
    assert kda_roofline_pct.read(obs) == pytest.approx(100 * 11 / 22)
    assert kda_ms_step.read({**obs, "trace": None}) is None


def test_load_readers_take_the_window_steps_of_the_counter_log(config):
    """The log holds warm-up, window and traced steps in order; the readers
    take the `attempted` steps before the last `trace_steps`.  The FLOPs a
    sample counts follow the assignments the steps counted."""
    import numpy as np

    from perfbench.layer_metrics import (moe_load_max_over_mean,
                                         moe_routed_here_first_pct,
                                         moe_routed_here_last_pct,
                                         moe_routed_here_pct)
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    with open(os.path.join(ROOT, "perfbench", "workloads",
                           "kimi-linear-sync-1chip.json")) as f:
        cell = json.load(f)
    family = kl.build(config, cell, impl="interpret", rehearse=False)
    made = cell["rows_per_chip"] * cell["seq_len"] * 8   # a layer, a step
    layers, held = 4, 8

    def load(share):
        """Every layer alike: `share` of the assignments here, expert 0
        with twice the others' load."""
        each = share * made / (held + 1)
        row = np.asarray([2 * each] + [each] * (held - 1) + [share * made],
                         np.float32)
        return {"moe_load": np.tile(row, (layers, 1))}

    log = counter_log()
    log.clear()
    assert family.flops_per_sample() == kl.flops_per_sample(family.s, 8192)
    shares = [0.01] * 6 + [0.03, 0.05, 0.07] + [0.5] * 4
    for step, share in enumerate(shares):   # 6 warm-up, 3 window, 4 traced
        log.append("MPI_PS.step", step, load(share))
    obs = {"family": family,
           "result": {"attempted": 3, "trace_steps": 4, "rows_per_chip": 2}}
    try:
        assert moe_routed_here_first_pct.read(obs) == pytest.approx(3.0)
        assert moe_routed_here_last_pct.read(obs) == pytest.approx(7.0)
        assert moe_routed_here_pct.read(obs) == pytest.approx(5.0)
        assert moe_load_max_over_mean.read(obs) == pytest.approx(
            2 * held / (held + 1))
        routed = np.mean(shares) * 8        # expert products a token
        assert family.flops_per_sample() == pytest.approx(
            kl.flops_per_sample(family.s, 8192, routed=routed))
        assert family.flops_per_sample() > kl.flops_per_sample(family.s, 8192)
    finally:
        log.clear()
    assert moe_routed_here_pct.read(obs) is None
