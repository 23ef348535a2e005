"""The `evabyte` family's shape formulas against counts made by hand, its
configuration file against the published one, the program's parameter tree
at the published widths against the formula, the family's pair of losses on
rehearsal sizes, and its readers against a hand-made trace and a hand-made
counter log.  Entries of `BENCHMARK.json` are found by name, never by
position or by a count of entries.  (`run.py --workload evabyte-sync-1chip
--rehearse` exiting 3 is `test_perfbench_cli.py`'s rehearsal case of this
cell: that test runs every cell `BENCHMARK.json` lists.)"""

import json
import os

import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.models import evabyte as ev

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "evabyte-sync-1chip"
PUBLISHED = {   # config.json of EvaByte/EvaByte, the catalog's row
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16,
    "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
    "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
    "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
    "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte",
    "norm_add_unit_offset": True, "num_attention_heads": 32,
    "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000, "tie_word_embeddings": False, "vocab_size": 320,
    "window_size": 2048,
}
NEW_METRICS = {
    "eva_attn_ms_step": "EVA attention",
    "eva_attn_roofline_pct": "EVA attention",
    "eva_local_flash_ms_step": "attention kernels",
    "eva_local_flash_roofline_pct": "attention kernels",
    "eva_summary_ms_step": "EVA attention",
    "eva_remote_mass_mean": "EVA attention",
    "eva_mbp_last_over_first": "multi-token prediction",
    "eva_step_ms_p50": "sync step", "eva_step_ms_p99": "sync step",
    "eva_dispatch_ms_p50": "sync step", "eva_fwd_ms_step": "sync step",
    "eva_remat_ms_step": "sync step", "eva_bwd_ms_step": "sync step",
    "eva_update_ms_step": "sync step", "eva_head_loss_ms_step": "sync step",
}


def load(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def named(entries, name):
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, name
    return found[0]


@pytest.fixture(scope="module")
def config():
    return load("perfbench/configs/evabyte.json")


@pytest.fixture(scope="module")
def cell():
    return load(f"perfbench/workloads/{CELL}.json")


@pytest.fixture(scope="module")
def s(config):
    return ev.sizes(config, rehearse=False)


def test_every_published_key_is_kept_or_listed_as_reduced(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert changed == {"num_attention_heads"}
    assert config["reduced"] == ["num_layers", "num_attention_heads"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    # the published depth stays; the depth that is run is a key of its own
    assert (config["num_hidden_layers"], config["num_layers"]) == (32, 4)
    assert (config["num_attention_heads"],
            config["num_attention_heads_published"]) == (16, 32)
    assert config["head_dim"] * 32 == config["hidden_size"]
    assert (config["name"], config["family"]) == ("evabyte", "evabyte")
    assert (config["compute_dtype"], config["param_dtype"]) \
        == ("bfloat16", "float32")
    assert {"head_dim", "pooling_scale", "mu", "summaries_after_rotation",
            "own_window", "heads", "init"} <= set(config["assumed"])
    assert "shared by heads over pairs of chips" in config["deployment"]
    assert "stages hold four layers" in config["deployment"]


def test_the_cell_is_as_the_issue_wrote_it(cell, config):
    assert (cell["rows_per_chip"], cell["seq_len"], cell["chips"]) \
        == (1, 8192, 1)
    assert (cell["optim"], cell["hyper"], cell["ps"]) \
        == ("adam", {"lr": 0.0001}, {})
    assert cell["feed"] == {"kind": "draw", "pool": "tokens", "pool_rows": 64}
    assert (cell["check_rows"], cell["warmup_steps"], cell["trace_steps"]) \
        == (1, 3, 4)
    assert cell["mode"] == "sync" and cell["config"] == config["name"]
    assert "who" in cell and "64-periodic" in cell["why"]
    bench = load("BENCHMARK.json")
    entry = named(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("evabyte", "sync-1chip-8k", 1)
    listed = named(bench["configs"], "evabyte")
    assert listed["file"] == "perfbench/configs/evabyte.json"
    assert listed["source"] == config["source"] \
        == "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    assert listed["reduced"] == config["reduced"]
    for name, layer in NEW_METRICS.items():
        m = named(bench["per_layer"], name)
        assert (m["layer"], m["workloads"], m["moves"]) \
            == (layer, [CELL], "samples_per_s_chip"), name
    # the cell reports the end-to-end metrics and the metrics with no list
    assert all(CELL in m.get("workloads", [CELL])
               for m in bench["end_to_end"])
    for name in ("compiles_in_window", "device_idle_pct", "mfu_pct"):
        assert "workloads" not in named(bench["per_layer"], name)
    # the rehearsal keeps the structure: >= 3 windows of >= 2 chunks, 8 heads
    toy = ev.sizes(config, rehearse=True)
    rows = cell["rehearsal"]["seq_len"]
    assert rows // toy["window"] >= 3 and rows % toy["window"] == 0
    assert toy["window"] // toy["chunk"] >= 2
    assert (toy["n_pred_heads"], toy["vocab_size"]) == (8, 320)
    assert toy["n_heads"] * toy["head_dim"] != toy["d_model"]


def test_the_traffic_at_320_ids_is_a_64_periodic_stream():
    """`data.token_pool` walks ``t+1 = 5t + 3 (mod vocab)`` with 2 % noise;
    ``gcd(5, 320) = 5``, so after a row's first byte the ids are 3 (mod 5)
    and a row repeats with period 64 and no shorter: the cell's ``why`` and
    PERF.md section 7 say so, for the next `benchmark` issue."""
    from perfbench import data

    rows = data.token_pool(4, 8192, 320, seed=3)
    assert rows.min() >= 0 and rows.max() < 320
    assert (rows[:, 1:] % 5 == 3).mean() > 0.97
    same = lambda p: (rows[:, p:] == rows[:, :-p]).mean()
    assert same(64) > 0.95 and same(128) > 0.95
    assert max(same(p) for p in (1, 2, 4, 8, 16, 32)) < 0.01


def test_parameter_count_by_hand(s, config):
    assert (s["d_model"], s["d_ff"], s["n_heads"], s["head_dim"],
            s["window"], s["chunk"], s["n_pred_heads"], s["vocab_size"]) \
        == (4096, 11008, 16, 128, 2048, 16, 8, 320)
    mlp = 3 * 4096 * 11008
    norms = 2 * 4096
    attn = 4 * 4096 * (16 * 128)               # W_q, W_k, W_v, W_o
    pooling = 2 * 16 * 128                     # phi and mu
    assert (mlp, attn, pooling) == (135_266_304, 33_554_432, 4_096)
    layer = attn + pooling + mlp + norms
    assert layer == 168_833_024 == sum(ev.layer_params(s).values())
    embedding, head = 320 * 4096, 4096 * 8 * 320
    assert (embedding, head) == (1_310_720, 10_485_760)
    assert ev.total_params(s) == 4 * layer + embedding + head + 4096 \
        == 687_132_672 == config["parameters"]
    # 16 bytes a parameter (f32 parameter, gradient, Adam's two moments)
    assert 16 * ev.total_params(s) / 1e9 == pytest.approx(10.99, abs=0.005)
    assert 16 * ev.total_params(s) / 2 ** 30 == pytest.approx(10.24,
                                                              abs=0.005)
    # a fifth layer, or all 32 heads, does not fit
    assert 16 * ev.total_params(dict(s, n_layers=5)) / 1e9 \
        == pytest.approx(13.7, abs=0.05)
    whole = dict(s, n_heads=32)
    assert sum(ev.layer_params(whole).values()) == 202_391_552
    assert ev.total_params(whole) == 821_366_784
    assert 16 * ev.total_params(whole) / 1e9 == pytest.approx(13.14, abs=0.005)


def test_the_programs_tree_at_the_published_widths_has_that_count(config,
                                                                  cell):
    """Abstract initialisation (`jax.eval_shape`: no memory) of the
    program's model at the published widths: the leaves and their sizes."""
    import jax

    family = ev.build(config, cell, impl="interpret", rehearse=False)
    shapes = jax.eval_shape(family.init_params, 0)
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) \
        == ev.total_params(family.s) == 687_132_672
    assert shapes["tok_embed/embedding"].shape == (320, 4096)
    assert shapes["lm_head/kernel"].shape == (4096, 2560)
    assert shapes["block_0/attn/q_proj/kernel"].shape == (4096, 2048)
    assert shapes["block_3/attn/o_proj/kernel"].shape == (2048, 4096)
    assert shapes["block_2/attn/phi"].shape \
        == shapes["block_2/attn/mu"].shape == (16, 128)
    assert shapes["block_1/mlp/gate/kernel"].shape == (4096, 11008)
    assert len(shapes) == 4 * 11 + 3
    assert all(v.dtype == np.float32 for v in shapes.values())
    assert family.aux["counters"]["eva_remote_mass"].shape == (4,)
    assert family.aux["counters"]["mbp_loss"].shape == (8,)


def test_pairs_and_flops_per_byte_by_hand(s):
    assert ev.matmul_params(s) == 4 * (33_554_432 + 135_266_304) \
        + 10_485_760 == 685_768_704
    local = 4 * 2048 * 2049 // 2               # four windows, causal
    remote = 2048 * 128 * (0 + 1 + 2 + 3)      # 128 summaries a window before
    assert ev.attended_pairs(8192, 2048, 16) == (local, remote) \
        == (8_392_704, 1_572_864)
    # a ragged last window sees every whole window before it
    assert ev.attended_pairs(4096 + 16, 2048, 16) \
        == (2 * 2048 * 2049 // 2 + 16 * 17 // 2, 2048 * 128 + 16 * 256)
    # at the published 32,768: 960 summaries a query on average, 192 here
    assert ev.attended_pairs(32768, 2048, 16)[1] / 32768 == 960
    assert remote / 8192 == 192
    # forward: QK^T and PV, 128 wide each, 16 heads
    per_pair = 2 * 16 * (128 + 128)
    attention = 3.0 * 4 * per_pair * (local + remote) / 8192
    assert ev.flops_per_sample(s, 8192) == 6 * 685_768_704 + attention \
        == 4_234_199_040
    assert attention / ev.flops_per_sample(s, 8192) \
        == pytest.approx(0.0282, abs=0.0005)


def test_the_three_work_functions_by_hand(s):
    peaks = harness.load_peaks("TPU v5 lite")
    tokens = 8192
    array = tokens * 16 * 128 * 2              # one bf16 tensor of the heads
    stats = tokens * 16 * 4
    local, remote = 16 * 8_392_704, 16 * 1_572_864
    # seven products of 2 * pairs * 128 a head: two forward, five backward
    w = ev.eva_local_flash_work(s, batch=1, seq_len=tokens)
    assert w["flops"] == 4 * 2 * local * 7 * 128
    # q k v o + dO, and dq dk dv: 4 + 5 + 3 passes; lse out, in, and its dlse
    assert w["bytes"] == 4 * (12 * array + 3 * stats)
    assert w["scope"] == "eva_local"
    least, bound = least_seconds(w, peaks)
    assert bound == "compute" and least == pytest.approx(4.886e-3, rel=1e-3)
    w = ev.eva_attn_work(s, batch=1, seq_len=tokens)
    assert w["flops"] == 4 * 2 * (local + remote) * 7 * 128
    summaries = 2 * array // 16                # k~ and v~, bf16
    assert w["bytes"] == 4 * (12 * array + 3 * stats + 3 * summaries)
    assert w["scope"] == "eva_attn"
    least, bound = least_seconds(w, peaks)
    assert bound == "compute" and least == pytest.approx(5.802e-3, rel=1e-3)
    w = ev.eva_summary_work(s, batch=1, seq_len=tokens)
    entries = tokens * 16 * 128
    assert w["flops"] == 4 * 3 * 6 * entries
    assert w["bytes"] == 4 * 2 * (6 * entries + 2 * (2 * entries // 16))
    assert w["scope"] == "eva_summary"
    least, bound = least_seconds(w, peaks)
    assert bound == "memory" and least == pytest.approx(1.024e-3, rel=1e-3)
    # two rows: twice the work
    assert ev.eva_attn_work(s, 2, tokens)["flops"] \
        == 2 * ev.eva_attn_work(s, 1, tokens)["flops"]


def test_the_familys_pair_agrees_on_rehearsal_sizes(config, cell):
    """The program's loss (bf16 products, the kernels' form under the
    interpreter) against the plain reference in f32 at highest precision,
    through the harness's own check program, at the rehearsal's sizes."""
    import jax

    from perfbench import data

    toy_cell = {**cell, **cell["rehearsal"]}
    family = ev.build(config, toy_cell, impl="interpret", rehearse=True)
    params = family.init_params(7)
    pool = data.make_pool(toy_cell["feed"], family.shapes, 7)
    out = harness.reference_check(
        family, "sync", params, data.fixed_sample(pool, 1),
        {"loss_rel": 2e-4, "grad_norm_rel": 1e-3, "grad_diff_rel": 0.02},
        jax.devices()[0])
    assert out["ok"], out
    assert out["grad_diff_rel"] > 1e-4         # bf16 against f32, not itself
    assert 5.5 < out["reference_loss"] < 7.0   # ln 320 = 5.77 and a little


def _trace_obs(config, cell, text, ops, window_ms):
    from perfbench.layer_metrics import _kimi
    from perfbench.trace_reduce import DeviceTrace, Op, Trace
    from pytorch_ps_mpi_tpu.utils import timing

    timing.register_program(_kimi.PROGRAM, lambda: text)
    ms = 1e-3
    trace = Trace(devices=[DeviceTrace(0, ops=[
        Op(name, a * ms, b * ms) for name, a, b in ops])], spans=[],
        window=(0.0, window_ms * ms))
    family = ev.build(config, cell, impl="interpret", rehearse=False)
    return {"trace": trace, "family": family,
            "peaks": harness.load_peaks("TPU v5 lite"),
            "result": {"trace_steps": 2, "rows_per_chip": 1}}


def test_scope_readers_on_a_hand_trace(config, cell):
    """`eva_local` lies inside `eva_attn`, `eva_summary` and `rope` beside
    it; each reader takes the union of its own scope's intervals, bare,
    rematerialised or under `transpose(jvp(...))`; the roofline shares divide
    the work functions' least time by it; the phases' doubles read the
    step's own scopes."""
    from perfbench.layer_metrics import (
        eva_attn_ms_step, eva_attn_roofline_pct, eva_bwd_ms_step,
        eva_fwd_ms_step, eva_head_loss_ms_step, eva_local_flash_ms_step,
        eva_local_flash_roofline_pct, eva_remat_ms_step, eva_summary_ms_step,
        eva_update_ms_step)

    g = "jit(spmd_step)/ps.grad"
    text = f"""
  %fusion.1 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/block_0/attn/eva_summary/mul"}}
  %flash_fwd.2 = f32[4]{{0}} custom-call(%a), metadata={{op_name="{g}/block_0/attn/eva_attn/eva_local/flash_fwd"}}
  %fusion.3 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/block_0/attn/eva_attn/dot_general"}}
  %fusion.4 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/block_0/attn/rope/mul"}}
  %flash_fwd.5 = f32[4]{{0}} custom-call(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/rematted_computation/attn/eva_attn/eva_local/flash_fwd"}}
  %flash_bwd_dkdv.6 = f32[4]{{0}} custom-call(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/attn/eva_attn/eva_local/flash_bwd_dkdv"}}
  %fusion.7 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/attn/eva_attn/logaddexp"}}
  %fusion.8 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/attn/eva_summary/mul"}}
  %fusion.9 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/head_loss/log_softmax"}}
  %fusion.10 = f32[4]{{0}} fusion(%a), metadata={{op_name="jit(spmd_step)/ps.update/adam/mul"}}
"""
    ops = [("fusion.1", 0, 2), ("flash_fwd.2", 2, 6), ("fusion.3", 6, 10),
           ("fusion.4", 10, 11), ("flash_fwd.5", 20, 24),
           ("flash_bwd_dkdv.6", 24, 36), ("fusion.7", 36, 40),
           ("fusion.8", 40, 44), ("fusion.9", 44, 46), ("fusion.10", 46, 56)]
    obs = _trace_obs(config, cell, text, ops, 60)
    assert eva_summary_ms_step.read(obs) == pytest.approx(3.0)
    assert eva_local_flash_ms_step.read(obs) == pytest.approx(10.0)
    assert eva_attn_ms_step.read(obs) == pytest.approx(14.0)   # local inside
    assert eva_local_flash_roofline_pct.read(obs) == pytest.approx(
        100 * 4.886e-3 / 10e-3, rel=1e-3)
    assert eva_attn_roofline_pct.read(obs) == pytest.approx(
        100 * 5.802e-3 / 14e-3, rel=1e-3)
    assert eva_fwd_ms_step.read(obs) == pytest.approx((11 + 2) / 2)
    assert eva_remat_ms_step.read(obs) == pytest.approx(2.0)
    assert eva_bwd_ms_step.read(obs) == pytest.approx(10.0)
    assert eva_update_ms_step.read(obs) == pytest.approx(5.0)
    assert eva_head_loss_ms_step.read(obs) == pytest.approx(1.0)
    readers = (eva_attn_ms_step, eva_attn_roofline_pct,
               eva_local_flash_ms_step, eva_local_flash_roofline_pct,
               eva_summary_ms_step, eva_fwd_ms_step, eva_remat_ms_step,
               eva_bwd_ms_step, eva_update_ms_step, eva_head_loss_ms_step)
    for reader in readers:      # no trace; a program without the scopes
        assert reader.read({**obs, "trace": None}) is None
    obs = _trace_obs(config, cell, """
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/mla/dot"}
""", [("fusion.1", 0, 2)], 60)
    for reader in readers:
        assert reader.read(obs) is None


def test_the_counter_readers_take_the_window_steps_of_the_counter_log():
    from perfbench.layer_metrics import (eva_mbp_last_over_first,
                                         eva_remote_mass_mean)
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    log = counter_log()
    log.clear()
    warm = ([9.0] * 4, [9.0] * 8)
    steps = [warm] * 6 + [
        ([0.1, 0.2, 0.3, 0.4], [2.0, 0, 0, 0, 0, 0, 0, 3.0]),
        ([0.2, 0.2, 0.2, 0.2], [1.0, 0, 0, 0, 0, 0, 0, 2.0]),
        ([0.3, 0.1, 0.3, 0.1], [4.0, 0, 0, 0, 0, 0, 0, 4.0])] + [warm] * 4
    for step, (mass, heads) in enumerate(steps):
        log.append("MPI_PS.step", step, {
            "eva_remote_mass": np.asarray(mass, np.float32),
            "mbp_loss": np.asarray(heads, np.float32)})
    obs = {"result": {"attempted": 3, "trace_steps": 4}}   # 6 warm-up before
    try:
        assert eva_remote_mass_mean.read(obs) == pytest.approx(2.6 / 12)
        assert eva_mbp_last_over_first.read(obs) \
            == pytest.approx((1.5 + 2.0 + 1.0) / 3)
    finally:
        log.clear()
    assert eva_remote_mass_mean.read(obs) is None
    assert eva_mbp_last_over_first.read(obs) is None


def test_the_doubles_are_the_accepted_readers():
    from perfbench.layer_metrics import (dispatch_ms_p50, eva_dispatch_ms_p50,
                                         eva_step_ms_p50, eva_step_ms_p99,
                                         step_ms_p50, step_ms_p99)
    assert eva_step_ms_p50.read is step_ms_p50.read
    assert eva_step_ms_p99.read is step_ms_p99.read
    assert eva_dispatch_ms_p50.read is dispatch_ms_p50.read
