"""The `sambay` family's shape formulas against counts made by hand, its
configuration file against the published one, the program's parameter tree
at the published widths against the formula, and its readers against a
hand-made trace and a hand-made counter log."""

import json
import os

import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.models import sambay as sm

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "phi4flash-sync-1chip"
PUBLISHED = {   # config.json of microsoft/Phi-4-mini-flash-reasoning
    "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
    "intermediate_size": 10240, "layer_norm_eps": 1e-05,
    "max_position_embeddings": 262144, "mb_per_layer": 2,
    "model_type": "phi4flash", "num_attention_heads": 40,
    "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
    "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
    "lm_head_bias": False, "vocab_size": 200064,
}
NEW_METRICS = {
    "phi_ssm_ms_step": "state-space scan",
    "phi_ssm_roofline_pct": "state-space scan",
    "phi_swa_flash_ms_step": "attention kernels",
    "phi_swa_flash_roofline_pct": "attention kernels",
    "phi_full_flash_ms_step": "attention kernels",
    "phi_full_flash_roofline_pct": "attention kernels",
    "phi_diff_ms_step": "cross-decoder and differential combine",
    "phi_gmu_ms_step": "cross-decoder and differential combine",
    "phi_diff_lambda_mean": "cross-decoder and differential combine",
    "phi_step_ms_p50": "sync step", "phi_step_ms_p99": "sync step",
    "phi_dispatch_ms_p50": "sync step",
}


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def config():
    return load("perfbench/configs/phi-4-mini-flash-reasoning.json")


@pytest.fixture(scope="module")
def cell():
    return load(f"perfbench/workloads/{CELL}.json")


@pytest.fixture(scope="module")
def s(config):
    return sm.sizes(config, rehearse=False)


def test_every_published_key_is_kept_or_listed_as_reduced(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert changed == {"vocab_size"}
    assert config["reduced"] == ["num_layers", "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    # the published depth stays and decides each layer's kind; the depth
    # that is run is a key of its own, as in the other two catalog models
    assert config["num_hidden_layers"] == 32
    assert config["layers_kept"] == [0, 1, 16, 17, 18, 19]
    assert config["num_layers"] == len(config["layers_kept"]) == 6
    assert config["vocab_size_published"] == 200064
    assert config["vocab_size"] * 8 == 200064
    # the state-space sizes are no keys of config.json: assumed, and said so
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) \
        == (16, 4, 2, 160) and 160 == -(-2560 // 16)
    assert {"mamba_sizes", "mamba_init", "memory", "head_pairing",
            "window_edge", "biases", "lambda_depth", "cross_differential",
            "init"} <= set(config["assumed"])
    assert "split by rows over 8 chips" in config["deployment"]


def test_the_layer_pattern_is_the_modeling_codes(s):
    """`use_mamba = i % 2 == 0`, the memory at 16, the kept keys and values
    at 17, readers from 18: 8 self-decoder periods, the pair, 7
    cross-decoder periods; the cut keeps one period of each."""
    kinds = [sm.layer_kind(i, 32, 2) for i in range(32)]
    assert kinds[:16] == ["mamba", "swa"] * 8
    assert kinds[16:18] == ["mamba_memory", "full_kv"]
    assert kinds[18:] == ["gmu", "cross"] * 7
    assert s["layers"] == (("mamba", 0), ("swa", 1), ("mamba_memory", 16),
                           ("full_kv", 17), ("gmu", 18), ("cross", 19))
    assert (s["d_model"], s["d_ff"], s["n_heads"], s["n_kv_heads"],
            s["head_dim"], s["window"], s["d_inner"], s["d_state"]) \
        == (2560, 10240, 40, 20, 64, 512, 5120, 16)


def test_the_cell_is_as_the_issue_wrote_it(cell, config):
    assert (cell["rows_per_chip"], cell["seq_len"], cell["chips"]) \
        == (1, 8192, 1)
    assert (cell["optim"], cell["hyper"], cell["ps"]) \
        == ("adam", {"lr": 0.0001}, {})
    assert cell["feed"] == {"kind": "draw", "pool": "tokens", "pool_rows": 64}
    assert (cell["check_rows"], cell["warmup_steps"], cell["trace_steps"]) \
        == (1, 3, 4)
    assert cell["mode"] == "sync" and cell["config"] == config["name"]
    bench = load("BENCHMARK.json")
    entry = bench["workloads"][-1]
    assert (entry["name"], entry["traffic"], entry["chips"]) \
        == (CELL, "sync-1chip-8k", 1)
    assert bench["configs"][-1]["name"] == config["name"]
    assert len(bench["workloads"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    added = bench["per_layer"][-len(NEW_METRICS):]
    assert {m["name"]: m["layer"] for m in added} == NEW_METRICS
    assert all(m["workloads"] == [CELL]
               and m["moves"] == "samples_per_s_chip" for m in added)


def test_parameter_count_by_hand(s, config):
    mlp = 3 * 2560 * 10240
    norms = 2 * 2 * 2560                       # two LayerNorms, scale + bias
    mamba = (2560 * 2 * 5120                   # in_proj: x and z
             + 4 * 5120 + 5120                 # the convolution and its bias
             + 5120 * (160 + 2 * 16)           # x_proj: delta, B, C
             + 160 * 5120 + 5120               # dt_proj and dt's bias
             + 5120 * 16 + 5120                # A_log, D
             + 5120 * 2560)                    # out_proj
    assert mamba == 41_241_600
    diff = 4 * 64 + 128                        # four lambdas, the norm
    attn = 2560 * 5120 + 5120 + 2560 * 2560 + 2560 + diff
    assert attn == 19_668_864
    gmu = 2 * 2560 * 5120
    cross = 2 * (2560 * 2560 + 2560) + diff
    assert (mlp, gmu, cross) == (78_643_200, 26_214_400, 13_112_704)
    layers = [mamba, attn, mamba, attn, gmu, cross]
    assert [x + mlp + norms for x in layers] == [
        119_895_040, 98_322_304, 119_895_040, 98_322_304, 104_867_840,
        91_766_144]
    vocab = 25008 * 2560                       # embedding = head, once
    assert vocab == 64_020_480
    assert sm.total_params(s) == sum(layers) + 6 * (mlp + norms) + vocab \
        + 2 * 2560 == 697_094_272 == config["parameters"]
    # 16 bytes a parameter (f32 parameter, gradient, Adam's two moments)
    assert 16 * sm.total_params(s) / 1e9 == pytest.approx(11.15, abs=0.005)
    assert 16 * sm.total_params(s) / 2 ** 30 == pytest.approx(10.39,
                                                              abs=0.005)
    # a second self-decoder period, or the whole vocabulary, does not fit
    more = dict(s, layers=s["layers"][:2] * 2 + s["layers"][2:])
    assert 16 * sm.total_params(more) / 1e9 == pytest.approx(14.6, abs=0.05)
    assert sm.total_params(dict(s, vocab_size=200064)) > 1.14e9


def test_the_programs_tree_at_the_published_widths_has_that_count(config,
                                                                  cell):
    """Abstract initialisation (`jax.eval_shape`: no memory) of the
    program's model at the published widths: the leaves and their sizes."""
    import jax

    family = sm.build(config, cell, impl="interpret", rehearse=False)
    shapes = jax.eval_shape(family.init_params, 0)
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) \
        == sm.total_params(family.s) == 697_094_272
    assert shapes["tok_embed/embedding"].shape == (25008, 2560)
    assert shapes["block_0/mixer/A_log"].shape == (5120, 16)
    assert shapes["block_3/mixer/qkv_proj/kernel"].shape == (2560, 5120)
    assert shapes["block_5/mixer/q_proj/kernel"].shape == (2560, 2560)
    assert not [n for n in shapes if "lm_head" in n]
    assert all(v.dtype == np.float32 for v in shapes.values())
    assert family.aux["counters"]["diff_lambda"].shape == (3,)


def test_matmul_parameters_and_flops_per_token_by_hand(s):
    mlp = 3 * 2560 * 10240
    mamba = 41_241_600 - 5120 - 5120 - 5120 * 16 - 5120
    attn = 2560 * 5120 + 2560 * 2560
    cross, gmu = 2 * 2560 * 2560, 2 * 2560 * 5120
    head = 25008 * 2560
    assert sm.matmul_params(s) == 2 * mamba + 2 * attn + gmu + cross \
        + 6 * mlp + head == 696_811_520
    # one attention layer, forward, a pair of (query, key): 40 heads of
    # QK^T over 64 and PV over 128 = 2 * 40 * 192
    per_pair = 2 * 40 * 192
    full = 8192 * 8192 / 2                    # pairs a head, causal
    band = 8192 * 512 - 512 * 512 / 2         # ... under the window
    assert sm.attended_pairs(8192, None) == full
    assert sm.attended_pairs(8192, 512) == band == 4_063_232
    assert sm.attended_pairs(300, 512) == 300 * 300 / 2   # all in the band
    assert full / band == pytest.approx(8.26, abs=0.01)
    attention = 3.0 * per_pair * (2 * full + band) / 8192
    scan = 3.0 * 2 * 7 * 5120 * 16
    assert sm.ssm_flops_per_token(s) == 7 * 81_920
    assert sm.flops_per_sample(s, 8192) \
        == 6 * 696_811_520 + attention + scan
    assert sm.flops_per_sample(s, 8192) == pytest.approx(4.585e9, rel=1e-3)
    assert attention / sm.flops_per_sample(s, 8192) \
        == pytest.approx(0.087, abs=0.002)
    # the window layer counts the band, not the square
    square = dict(s, window=8192)
    assert sm.flops_per_sample(square, 8192) - sm.flops_per_sample(s, 8192) \
        == pytest.approx(3.0 * per_pair * (full - band) / 8192)


def test_the_three_work_functions_by_hand(s):
    peaks = harness.load_peaks("TPU v5 lite")
    tokens = 8192
    # the scan: 7 FLOPs a state entry forward, three times with the backward
    w = sm.ssm_work(s, batch=1, seq_len=tokens)
    assert w["flops"] == 2 * 3 * 7 * 5120 * 16 * tokens
    wide, narrow = tokens * 5120, tokens * 16
    small = 5120 * 17 * 4
    forward = wide * (2 + 4 + 2) + narrow * 8 + small     # x dt y | B C | A D
    backward = wide * (2 + 4 + 2) + narrow * 8 \
        + wide * (2 + 4) + narrow * 8 + 2 * small         # + dx ddt | dB dC
    assert w["bytes"] == 2 * (forward + backward) and w["scope"] == "ssm"
    least, bound = least_seconds(w, peaks)
    assert bound == "memory" and least == pytest.approx(2.27e-3, rel=0.01)
    # attention at the true widths: seven products of 2 * pairs * width
    for work, layers, pairs, scope in (
            (sm.swa_flash_work, 1, 4_063_232, "swa"),
            (sm.full_flash_work, 2, 8192 * 8192 // 2, "full_attn")):
        w = work(s, batch=1, seq_len=tokens)
        assert w["flops"] == layers * 2 * 40 * pairs * (4 * 64 + 3 * 128)
        q, kv, o = (tokens * h * d * 2 for h, d in ((40, 64), (20, 64),
                                                    (40, 128)))
        stats = tokens * 40 * 4
        assert w["bytes"] == layers * (
            (q + 2 * kv + o + stats)                  # forward
            + (q + 2 * kv + 2 * o + stats)            # backward reads
            + (q + 2 * kv))                           # ... and writes
        assert w["scope"] == scope
        assert least_seconds(w, peaks)[1] == "compute"
    assert least_seconds(sm.full_flash_work(s, 1, tokens), peaks)[0] \
        == pytest.approx(17.44e-3, rel=0.01)
    assert least_seconds(sm.swa_flash_work(s, 1, tokens), peaks)[0] \
        == pytest.approx(1.056e-3, rel=0.01)


def test_rehearsal_sizes_keep_the_pattern(config):
    toy = sm.sizes(config, rehearse=True)
    assert [k for k, _ in toy["layers"]] == [
        "mamba", "swa", "mamba_memory", "full_kv", "gmu", "cross"]
    assert toy["n_heads"] == 2 * toy["n_kv_heads"] == 4
    assert toy["d_inner"] == 2 * toy["d_model"]
    assert toy["window"] < 96             # the rehearsal's rows are 96 long
    assert sm.total_params(toy) < 1_000_000


def test_scope_readers_on_a_hand_trace(config, cell):
    """Each reader takes the union of its own scope's intervals, bare or
    under `transpose(jvp(...))`; none of the five scopes nests in another;
    the roofline shares divide the work functions' least time by it."""
    from perfbench.layer_metrics import (
        _kimi, phi_diff_ms_step, phi_full_flash_ms_step,
        phi_full_flash_roofline_pct, phi_gmu_ms_step, phi_ssm_ms_step,
        phi_ssm_roofline_pct, phi_swa_flash_ms_step,
        phi_swa_flash_roofline_pct)
    from perfbench.trace_reduce import DeviceTrace, Op, Trace
    from pytorch_ps_mpi_tpu.utils import timing

    text = """
  %while.1 = f32[4]{0} while(%a), metadata={op_name="jit(spmd_step)/block_0/mixer/ssm/while"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/transpose(jvp(block_0))/mixer/ssm/while/body/mul"}
  %flash_fwd.3 = f32[4]{0} custom-call(%a), metadata={op_name="jit(spmd_step)/block_1/mixer/swa/flash_fwd"}
  %flash_bwd_dkdv.4 = f32[4]{0} custom-call(%a), metadata={op_name="jit(spmd_step)/transpose(jvp(block_3))/mixer/full_attn/flash_bwd_dkdv"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_5/mixer/full_attn/transpose"}
  %fusion.6 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_5/mixer/diff/sub"}
  %fusion.7 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_4/mixer/gmu/mul"}
  %fusion.8 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/block_4/mixer/in_proj/dot_general"}
"""
    timing.register_program(_kimi.PROGRAM, lambda: text)
    ms = 1e-3
    ops = [Op("while.1", 0, 400 * ms), Op("fusion.2", 100 * ms, 200 * ms),
           Op("flash_fwd.3", 400 * ms, 410 * ms),
           Op("flash_bwd_dkdv.4", 410 * ms, 470 * ms),
           Op("fusion.5", 470 * ms, 480 * ms),
           Op("fusion.6", 480 * ms, 484 * ms),
           Op("fusion.7", 484 * ms, 486 * ms),
           Op("fusion.8", 486 * ms, 500 * ms)]
    trace = Trace(devices=[DeviceTrace(0, ops=ops)], spans=[],
                  window=(0.0, 500 * ms))
    family = sm.build(config, cell, impl="interpret", rehearse=False)
    obs = {"trace": trace, "family": family,
           "peaks": harness.load_peaks("TPU v5 lite"),
           "result": {"trace_steps": 2, "rows_per_chip": 1}}
    assert phi_ssm_ms_step.read(obs) == pytest.approx(200.0)   # the union
    assert phi_swa_flash_ms_step.read(obs) == pytest.approx(5.0)
    assert phi_full_flash_ms_step.read(obs) == pytest.approx(35.0)
    assert phi_diff_ms_step.read(obs) == pytest.approx(2.0)
    assert phi_gmu_ms_step.read(obs) == pytest.approx(1.0)
    assert phi_ssm_roofline_pct.read(obs) == pytest.approx(
        100 * 2.27e-3 / 0.2, rel=0.01)
    assert phi_swa_flash_roofline_pct.read(obs) == pytest.approx(
        100 * 1.056e-3 / 5e-3, rel=0.01)
    assert phi_full_flash_roofline_pct.read(obs) == pytest.approx(
        100 * 17.44e-3 / 35e-3, rel=0.01)
    readers = (phi_ssm_ms_step, phi_ssm_roofline_pct, phi_swa_flash_ms_step,
               phi_swa_flash_roofline_pct, phi_full_flash_ms_step,
               phi_full_flash_roofline_pct, phi_diff_ms_step,
               phi_gmu_ms_step)
    for reader in readers:      # no trace; a program without the scopes
        assert reader.read({**obs, "trace": None}) is None
    timing.register_program(_kimi.PROGRAM, lambda: """
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/mla/dot"}
""")
    for reader in readers:
        assert reader.read(obs) is None


def test_the_lambda_reader_takes_the_window_steps_of_the_counter_log():
    from perfbench.layer_metrics import phi_diff_lambda_mean
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    log = counter_log()
    log.clear()
    lams = [[9.0] * 3] * 6 + [[0.3, 0.8, 0.7], [0.4, 0.8, 0.9],
                              [0.5, 0.8, 1.0]] + [[9.0] * 3] * 4
    for step, lam in enumerate(lams):
        log.append("MPI_PS.step", step,
                   {"diff_lambda": np.asarray(lam, np.float32)})
    obs = {"result": {"attempted": 3, "trace_steps": 4}}   # 6 warm-up before
    try:
        assert phi_diff_lambda_mean.read(obs) == pytest.approx(6.2 / 9)
    finally:
        log.clear()
    assert phi_diff_lambda_mean.read(obs) is None
