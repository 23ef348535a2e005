"""The `glm_moe` family's shape formulas against counts made by hand, its
configuration file against the published one, and its readers against a
hand-made trace and a hand-made counter log."""

import json
import os

import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.models import glm_moe as gm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm47-flash-sync-1chip"
PUBLISHED = {   # config.json of zai-org/GLM-4.7-Flash, the catalog's row
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880,
}


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load("perfbench/configs/glm-4.7-flash.json")


@pytest.fixture(scope="module")
def cell():
    return load(f"perfbench/workloads/{CELL}.json")


@pytest.fixture(scope="module")
def s(config):
    return gm.sizes(config, rehearse=False)


def test_every_published_key_is_kept_or_listed_as_reduced(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert changed == {"n_routed_experts", "vocab_size"}
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    assert config["n_routed_experts_published"] == 64
    assert config["vocab_size_published"] == 154880
    assert config["vocab_size"] * 8 == 154880
    assert config["n_routed_experts"] == len(config["experts_held"]) == 8
    assert config["n_routed_experts"] * 8 == 64      # one of 8 chips' share
    # the leading dense layer once, four that follow it, the MTP module
    assert config["num_layers"] == 5 and config["first_k_dense_replace"] == 1
    assert config["num_nextn_predict_layers"] == 1
    assert {"rope_pairing", "mtp_loss_weight", "mtp_concat_order",
            "mtp_input"} <= set(config["assumed"])
    assert "8 chips share each layer" in config["deployment"]


def test_the_cell_is_as_the_issue_wrote_it(cell, config):
    assert (cell["rows_per_chip"], cell["seq_len"], cell["chips"]) \
        == (1, 8192, 1)
    assert (cell["optim"], cell["hyper"], cell["ps"]) \
        == ("adam", {"lr": 0.0001}, {})
    assert cell["feed"] == {"kind": "draw", "pool": "tokens", "pool_rows": 64}
    assert (cell["check_rows"], cell["warmup_steps"], cell["trace_steps"]) \
        == (1, 3, 4)
    assert cell["mtp_loss_weight"] == 0.3
    family = gm.build(config, cell, impl="interpret", rehearse=False)
    assert family.mtp_weight == 0.3 and family.tokens_per_step == 8192
    assert family.shapes == {"seq_len": 8192, "vocab_size": 19360}


def test_parameter_count_by_hand(s, config):
    mla = (2048 * 768 + 768          # q_a and the q latent's norm
           + 768 * 20 * 256          # q_b: 20 heads of 192 + 64
           + 2048 * 576 + 512        # kv_a (512 + 64) and the latent's norm
           + 512 * 20 * 448          # kv_b: 20 heads of 192 + 256
           + 20 * 256 * 2048)        # o
    assert mla == 21_759_232
    norms = 2 * 2048
    dense = mla + 3 * 2048 * 10240 + norms
    assert dense == 84_677_888
    expert = 3 * 2048 * 1536
    moe = 2048 * 64 + 64 + expert + 8 * expert   # router, bias, shared, held
    assert (2048 * 64, expert, 8 * expert) == (131_072, 9_437_184, 75_497_472)
    expert_layer = mla + moe + norms
    assert expert_layer == 106_829_120
    vocab = 2 * 19360 * 2048 + 2048              # embedding, head, final norm
    assert vocab == 79_300_608
    mtp = 2 * 2048 * 2048 + expert_layer + 3 * 2048   # eh_proj, block, norms
    assert mtp == 115_223_872
    assert gm.total_params(s) == dense + 4 * expert_layer + vocab + mtp \
        == 706_518_848 == config["parameters"]
    # 16 bytes a parameter (f32 parameter, gradient, Adam's two moments)
    assert 16 * gm.total_params(s) / 1e9 == pytest.approx(11.30, abs=0.005)
    assert 16 * gm.total_params(s) / 2 ** 30 == pytest.approx(10.53,
                                                              abs=0.005)
    # a sixth main layer, or 16 experts a chip, would not leave room
    assert 16 * gm.total_params(dict(s, n_layers=6)) / 1e9 \
        == pytest.approx(13.0, abs=0.05)
    assert gm.total_params(dict(s, experts_held=tuple(range(16)))) \
        == pytest.approx(1.08e9, rel=5e-3)


def test_matmul_parameters_and_flops_per_token_by_hand(s):
    mla = 21_759_232 - 768 - 512
    expert = 3 * 2048 * 1536
    moe = 2048 * 64 + expert + 0.5 * expert      # router, shared, 4 * 8 / 64
    head = 2048 * 19360
    main = 5 * mla + 3 * 2048 * 10240 + 4 * moe + head
    mtp = 2 * 2048 * 2048 + mla + moe + head     # the head a second time
    assert gm.matmul_params(s) == main + mtp == 352_583_680
    assert mtp / (main + mtp) == pytest.approx(0.24, abs=0.005)
    # one causal MLA layer, forward: QK^T over 256 and PV over 256 on half
    # of the square = 2 * (S / 2) * 20 * (256 + 256) a token; 3.5 times
    # that with the backward's five products
    forward = 8192 * 20 * 512
    assert forward == 83_886_080
    attention = 6 * 3.5 * forward
    assert gm.flops_per_sample(s, 8192) == 6 * (main + mtp) + attention
    assert gm.flops_per_sample(s, 8192) == pytest.approx(3.877e9, rel=1e-3)
    assert attention / gm.flops_per_sample(s, 8192) \
        == pytest.approx(0.45, abs=0.01)
    # the expert products the steps counted enter by `routed`
    assert gm.flops_per_sample(s, 8192, routed=1.5) \
        - gm.flops_per_sample(s, 8192) == 6 * 5 * expert


def test_flash_work_at_256_and_256_by_hand(s):
    w = gm.flash_work(s, batch=1, seq_len=8192)
    half_square = 20 * 8192 * 8192 // 2       # causal, per unit of width
    forward = 2 * half_square * (256 + 256)              # QK^T, PV
    backward = 2 * half_square * (256 + 256 + 256 + 256 + 256)
    assert w["flops"] == 6 * (forward + backward)        # six MLA layers
    wide = 8192 * 20 * 256 * 2                           # bf16 [1, S, 20, 256]
    stats = 20 * 8192 * 4
    assert w["bytes"] == 6 * ((3 * wide + wide + stats)
                              + (5 * wide + stats) + 3 * wide)
    assert w["match"] == ('"kernel":"flash_fwd"', '"kernel":"flash_bwd_dkdv"',
                          '"kernel":"flash_bwd_dq"')
    least, bound = least_seconds(w, harness.load_peaks("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(6 * 7 * half_square * 2 * 256 / 197e12)
    assert gm.flash_work(dict(s, n_mtp=0), 1, 8192)["flops"] * 6 \
        == w["flops"] * 5


def test_rehearsal_sizes_keep_the_pattern(config):
    toy = gm.sizes(config, rehearse=True)
    assert toy["first_k_dense"] == 1 and toy["n_layers"] >= 3
    assert toy["n_mtp"] == 1 and toy["top_k"] == 4
    assert len(toy["experts_held"]) < toy["n_experts"]
    assert toy["qk_nope_dim"] + toy["qk_rope_dim"] == toy["v_dim"]
    assert gm.total_params(toy) < 1_000_000


def test_the_program_builds_what_the_formulas_count(config, cell):
    """The rehearsal-sized model's parameter tree has exactly the count the
    formula gives: the formula counts this program, not another."""
    family = gm.build(config, {**cell, **cell["rehearsal"]},
                      impl="interpret", rehearse=True)
    params = family.init_params(0)
    assert sum(p.size for p in params.values()) \
        == gm.total_params(family.s)
    assert family.samples_per_row == cell["rehearsal"]["seq_len"]
    assert family.aux["counters"]["moe_load"].shape \
        == (gm.expert_layers(family.s), len(family.s["experts_held"]) + 1)


def test_scope_readers_on_a_hand_trace():
    """`rope`, `mtp` and `moe` nest: an operation of the MTP block's expert
    layer counts under `mtp` and under `moe`, its rotation under `rope` and
    `mtp`; each reader takes the union of its own scope's intervals."""
    from perfbench.layer_metrics import (_kimi, glm_moe_ms_step,
                                         glm_mtp_ms_step, glm_rope_ms_step)
    from perfbench.trace_reduce import DeviceTrace, Op, Trace
    from pytorch_ps_mpi_tpu.utils import timing

    text = """
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_1/attn/rope/mul"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/transpose(jvp(block_1))/moe/dot_general"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/mtp/mtp/block/attn/rope/mul"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/transpose(jvp(mtp))/mtp/block/moe/dot_general"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/mtp/mtp/eh_proj/dot_general"}
  %fusion.6 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/head_loss/reduce_sum"}
"""
    timing.register_program(_kimi.PROGRAM, lambda: text)
    us = 1e-6
    ops = [Op("fusion.1", 0, 10 * us), Op("fusion.2", 10 * us, 30 * us),
           Op("fusion.3", 30 * us, 34 * us), Op("fusion.4", 40 * us, 70 * us),
           Op("fusion.5", 70 * us, 76 * us), Op("fusion.6", 80 * us, 90 * us)]
    trace = Trace(devices=[DeviceTrace(0, ops=ops)], spans=[],
                  window=(0.0, 100 * us))
    obs = {"trace": trace, "result": {"trace_steps": 2, "rows_per_chip": 1}}
    assert glm_rope_ms_step.read(obs) == pytest.approx(1e3 * 14 * us / 2)
    assert glm_moe_ms_step.read(obs) == pytest.approx(1e3 * 50 * us / 2)
    assert glm_mtp_ms_step.read(obs) == pytest.approx(1e3 * 40 * us / 2)
    for reader in (glm_rope_ms_step, glm_moe_ms_step, glm_mtp_ms_step):
        assert reader.read({**obs, "trace": None}) is None


def test_counter_readers_take_the_window_steps_of_the_counter_log(config,
                                                                  cell):
    """The log holds warm-up, window and traced steps in order; the readers
    take the `attempted` steps before the last `trace_steps`.  The FLOPs a
    sample counts follow the assignments the steps counted."""
    from perfbench.layer_metrics import (glm_moe_routed_here_first_pct,
                                         glm_moe_routed_here_last_pct,
                                         glm_moe_routed_here_pct,
                                         glm_mtp_loss_ratio)
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    family = gm.build(config, cell, impl="interpret", rehearse=False)
    made = 8192 * 4                     # assignments a layer a step
    layers, held = 5, 8

    def counters(share, ratio):
        row = np.asarray([share * made / held] * held + [share * made],
                         np.float32)
        return {"moe_load": np.tile(row, (layers, 1)),
                "loss_main": np.float32(2.0),
                "loss_mtp": np.float32(2.0 * ratio)}

    log = counter_log()
    log.clear()
    assert family.flops_per_sample() == gm.flops_per_sample(family.s, 8192)
    shares = [0.125] * 6 + [0.15, 0.20, 0.25] + [0.5] * 4
    ratios = [9.0] * 6 + [1.5, 1.25, 1.0] + [9.0] * 4
    for step, (share, ratio) in enumerate(zip(shares, ratios)):
        log.append("MPI_PS.step", step, counters(share, ratio))
    obs = {"family": family,       # 6 warm-up, 3 window, 4 traced
           "result": {"attempted": 3, "trace_steps": 4, "rows_per_chip": 1}}
    try:
        assert glm_moe_routed_here_first_pct.read(obs) == pytest.approx(15.0)
        assert glm_moe_routed_here_last_pct.read(obs) == pytest.approx(25.0)
        assert glm_moe_routed_here_pct.read(obs) == pytest.approx(20.0)
        assert glm_mtp_loss_ratio.read(obs) == pytest.approx(1.25)
        routed = np.mean(shares) * 4        # expert products a token
        assert family.flops_per_sample() == pytest.approx(
            gm.flops_per_sample(family.s, 8192, routed=routed))
    finally:
        log.clear()
    assert glm_moe_routed_here_pct.read(obs) is None
    assert glm_mtp_loss_ratio.read(obs) is None
