"""The `nemotron_h` family's shape formulas against counts made by hand, its
configuration file against the catalog's published one, the program's
parameter tree at the published widths against the formula, the family's
pair of losses on rehearsal sizes, and its readers against a hand-made
trace and a hand-made counter log.  Entries of `BENCHMARK.json` are found
by name, never by position or by a count of entries.  (`run.py --workload
nemotron3-nano-sync-1chip --rehearse` exiting 3 is `test_perfbench_cli.py`'s
rehearsal case of this cell: that test runs every cell `BENCHMARK.json`
lists.)"""

import json
import os

import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.models import nemotron_h as nh

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "nemotron3-nano-sync-1chip"
CONFIG = "nemotron-3-nano-30b-a3b"
PUBLISHED = {   # config.json of NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856,
    "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 52,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072,
}
NEW_METRICS = {
    "nemo_ssd_ms_step": ("state-space duality scan", "device_trace"),
    "nemo_ssd_roofline_pct": ("state-space duality scan", "device_trace"),
    "nemo_mamba_ms_step": ("state-space duality scan", "device_trace"),
    "nemo_ssd_carry_mean": ("state-space duality scan", "program_counter"),
    "nemo_moe_ms_step": ("expert layer", "device_trace"),
    "nemo_moe_routed_here_pct": ("expert layer", "program_counter"),
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
    return load(f"perfbench/configs/{CONFIG}.json")


@pytest.fixture(scope="module")
def cell():
    return load(f"perfbench/workloads/{CELL}.json")


@pytest.fixture(scope="module")
def s(config):
    return nh.sizes(config, rehearse=False)


def test_every_published_key_is_kept_or_listed_as_reduced(config):
    changed = {k for k, v in PUBLISHED.items() if config.get(k, "-") != v}
    assert changed == {"n_routed_experts", "vocab_size"}
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_why"])
    # the published depth stays; the depth that is run is a key of its own
    assert (config["num_hidden_layers"], config["num_layers"]) == (52, 9)
    assert (config["n_routed_experts"],
            config["n_routed_experts_published"]) == (8, 128)
    assert config["experts_held"] == list(range(8))
    assert (config["vocab_size"], config["vocab_size_published"]) \
        == (16384, 131072)
    assert (config["name"], config["family"]) == (CONFIG, "nemotron_h")
    assert (config["compute_dtype"], config["param_dtype"]) \
        == ("bfloat16", "float32")
    assert {"no_rotation", "d_inner", "init",
            "e_score_correction_bias"} <= set(config["assumed"])
    assert "16 chips share each layer" in config["deployment"]
    assert {"loss_rel", "grad_norm_rel", "grad_diff_rel", "why"} \
        <= set(config["check"])


def test_the_cell_and_its_entries_are_as_written(cell, config):
    assert (cell["rows_per_chip"], cell["seq_len"], cell["chips"]) \
        == (1, 8192, 1)
    assert (cell["optim"], cell["hyper"], cell["ps"]) \
        == ("adam", {"lr": 0.0001}, {})
    assert cell["feed"] == {"kind": "draw", "pool": "tokens", "pool_rows": 64}
    assert (cell["check_rows"], cell["warmup_steps"], cell["trace_steps"]) \
        == (1, 3, 4)
    assert cell["mode"] == "sync" and cell["config"] == config["name"]
    assert "who" in cell and "~384 tokens" in cell["why"]
    bench = load("BENCHMARK.json")
    entry = named(bench["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == (CONFIG, "sync-1chip-8k", 1)
    listed = named(bench["configs"], CONFIG)
    assert listed["file"] == f"perfbench/configs/{CONFIG}.json"
    assert listed["source"] == config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"
        "/blob/main/config.json")
    assert listed["reduced"] == config["reduced"]
    for name, (layer, source) in NEW_METRICS.items():
        m = named(bench["per_layer"], name)
        assert (m["layer"], m["source"], m["workloads"], m["moves"]) \
            == (layer, source, [CELL], "samples_per_s_chip"), name
    # the cell reports the end-to-end metrics and the metrics with no list
    assert all(CELL in m.get("workloads", [CELL])
               for m in bench["end_to_end"])
    for name in ("compiles_in_window", "device_idle_pct", "mfu_pct"):
        assert "workloads" not in named(bench["per_layer"], name)
    # the rehearsal keeps the structure: the pattern, whole chunks, groups
    toy = nh.sizes(config, rehearse=True)
    assert toy["pattern"] == "MEMEM*EME"
    assert cell["rehearsal"]["seq_len"] % toy["chunk"] == 0
    assert cell["rehearsal"]["seq_len"] // toy["chunk"] >= 2
    assert 1 < toy["n_groups"] < toy["mamba_heads"]
    assert toy["n_kv_heads"] < toy["n_heads"]


def test_parameter_count_by_hand(s, config):
    assert s["pattern"] == "MEMEM*EME"
    assert (s["d_model"], s["mamba_heads"], s["mamba_head_dim"],
            s["n_groups"], s["d_state"], s["chunk"]) \
        == (2688, 64, 64, 8, 128, 128)
    mamba = 2688 * 10304 + 4 * 6144 + 6144 + 3 * 64 + 4096 \
        + 4096 * 2688 + 2688
    assert (2688 * 10304, 4096 * 2688) == (27_697_152, 11_010_048)
    assert mamba == 38_744_896
    expert, shared = 2 * 2688 * 1856, 2 * 2688 * 3712
    moe = 8 * expert + shared + 2688 * 128 + 128 + 2688
    assert (expert, shared, moe) == (9_977_856, 19_955_712, 100_125_440)
    attn = 2688 * 32 * 128 + 2 * 2688 * 2 * 128 + 32 * 128 * 2688 + 2688
    assert attn == 23_399_040
    vocab = 2 * 16384 * 2688 + 2688
    assert vocab == 88_083_072
    total = 4 * mamba + 4 * moe + attn + vocab
    assert nh.total_params(s) == total == 666_963_456 == config["parameters"]
    # 16 bytes a parameter (f32 parameter, gradient, Adam's two moments)
    assert 16 * total / 1e9 == pytest.approx(10.67, abs=0.005)
    assert 16 * total / 2 ** 30 == pytest.approx(9.94, abs=0.005)
    # a second period, or 16 experts a chip, does not fit in 16 GB
    assert 16 * nh.total_params(dict(s, pattern="MEMEM*EME" * 2)) / 1e9 \
        > 16
    assert 16 * nh.total_params(
        dict(s, experts_held=tuple(range(16)))) / 1e9 \
        == pytest.approx(15.78, abs=0.005)


def test_the_programs_tree_at_the_published_widths_has_that_count(config,
                                                                  cell):
    """Abstract initialisation (`jax.eval_shape`: no memory) of the
    program's model at the published widths: the leaves and their sizes."""
    import jax

    family = nh.build(config, cell, impl="interpret", rehearse=False)
    shapes = jax.eval_shape(family.init_params, 0)
    assert sum(int(np.prod(v.shape)) for v in shapes.values()) \
        == nh.total_params(family.s) == 666_963_456
    assert shapes["tok_embed/embedding"].shape == (16384, 2688)
    assert shapes["lm_head/kernel"].shape == (2688, 16384)
    assert shapes["block_0/mixer/in_proj/kernel"].shape == (2688, 10304)
    assert shapes["block_0/mixer/conv"].shape == (4, 6144)
    assert shapes["block_7/mixer/out_proj/kernel"].shape == (4096, 2688)
    assert shapes["block_1/mixer/w_up"].shape == (8, 2688, 1856)
    assert shapes["block_1/mixer/w_down"].shape == (8, 1856, 2688)
    assert shapes["block_8/mixer/shared/up/kernel"].shape == (2688, 3712)
    assert shapes["block_3/mixer/router"].shape == (2688, 128)
    assert shapes["block_5/mixer/q_proj/kernel"].shape == (2688, 4096)
    assert shapes["block_5/mixer/v_proj/kernel"].shape == (2688, 256)
    assert not any("w_gate" in n for n in shapes)
    assert len(shapes) == 4 * 9 + 4 * 7 + 5 + 3
    assert all(v.dtype == np.float32 for v in shapes.values())
    assert family.aux["counters"]["moe_load"].shape == (4, 9)
    assert family.aux["counters"]["ssd_carry"].shape == (4,)


def test_flops_by_hand(s):
    routed = 6 * 8 / 128
    matmul = 2688 * 16384 + 4 * (38_744_896 - 6144 - 3 * 64 - 4096 - 2688) \
        + (23_399_040 - 2688) \
        + 4 * (2688 * 128 + 19_955_712 + routed * 9_977_856)
    assert nh.matmul_params(s) == pytest.approx(matmul, rel=1e-12)
    # Q N G + Q P H a token for the two intra-chunk products, N P H twice
    # for the chunk's own state and the output from the carried one
    per_token = 2 * 128 * 128 * 8 + 2 * 128 * 64 * 64 + 4 * 128 * 64 * 64
    assert nh.ssd_flops_per_token(s) == per_token == 3_407_872
    attention = 3.0 * 2 * 32 * (128 + 128) * 8192 / 2
    assert nh.flops_per_sample(s, 8192) == pytest.approx(
        6 * matmul + attention + 3.0 * 4 * per_token, rel=1e-12)
    assert nh.flops_per_sample(s, 8192) == pytest.approx(2.1534e9, rel=1e-4)
    # counted at the experts' measured load: more products, more FLOPs
    assert nh.flops_per_sample(s, 8192, routed=1.0) \
        - nh.flops_per_sample(s, 8192) \
        == pytest.approx(6 * 4 * (1.0 - routed) * 9_977_856, rel=1e-9)


def test_the_scans_work_by_hand(s):
    peaks = harness.load_peaks("TPU v5 lite")
    tokens = 8192
    w = nh.ssd_work(s, batch=1, seq_len=tokens)
    assert w["flops"] == 4 * 3 * 3_407_872 * tokens
    wide, heads, groups = tokens * 64 * 64, tokens * 64, tokens * 8 * 128
    states = 64 * 64 * 64 * 128 * 4            # a state a chunk, f32
    inputs = 2 * wide + 4 * heads + 2 * 2 * groups
    forward = inputs + 4 * wide + states + 64 * 8
    backward = inputs + 4 * wide + states + inputs + 64 * 16
    assert w["bytes"] == 4 * (forward + backward)
    assert w["scope"] == "ssd"
    least, bound = least_seconds(w, peaks)
    assert bound == "memory" and least == pytest.approx(4.128e-3, rel=1e-3)
    assert nh.ssd_work(s, 2, tokens)["flops"] == 2 * w["flops"]


def _rehearsal_check(config, cell, seed, tolerances):
    import jax

    from perfbench import data

    toy_cell = {**cell, **cell["rehearsal"]}
    family = nh.build(config, toy_cell, impl="interpret", rehearse=True)
    params = family.init_params(seed)
    pool = data.make_pool(toy_cell["feed"], family.shapes, seed)
    return harness.reference_check(
        family, "sync", params, data.fixed_sample(pool, 1), tolerances,
        jax.devices()[0])


@pytest.mark.parametrize("seed", [3, 7])
def test_the_familys_pair_agrees_on_rehearsal_sizes(config, cell, seed):
    """The program's loss (the chunked scan, the grouped experts, the flash
    kernels under the interpreter) against the plain reference at highest
    precision, through the harness's own check program, at the rehearsal's
    sizes: in f32 they differ by summation order alone.  In the
    configuration's bf16 a toy router over 16 experts picks other experts
    for a few tokens whose scores nearly tie, which moves the gradient by
    5-20 % on these seeds; with nothing to pick (4 experts, 4 a token) what
    is left is the rounding of the products' inputs, ~3 %."""
    exact = _rehearsal_check(dict(config, compute_dtype="float32"), cell,
                             seed, {"loss_rel": 1e-5, "grad_norm_rel": 1e-5,
                                    "grad_diff_rel": 1e-4})
    assert exact["ok"], exact
    assert 5.3 < exact["reference_loss"] < 7.0   # ln 256 = 5.55 and a little
    no_choice = dict(config, rehearsal=dict(config["rehearsal"],
                                            n_routed_experts_published=4))
    rounded = _rehearsal_check(no_choice, cell, seed, {
        "loss_rel": 5e-4, "grad_norm_rel": 5e-3, "grad_diff_rel": 0.05})
    assert rounded["ok"], rounded
    assert rounded["grad_diff_rel"] > 1e-3     # bf16 against f32, not itself


def _trace_obs(config, cell, text, ops, window_ms):
    from perfbench.layer_metrics import _kimi
    from perfbench.trace_reduce import DeviceTrace, Op, Trace
    from pytorch_ps_mpi_tpu.utils import timing

    timing.register_program(_kimi.PROGRAM, lambda: text)
    ms = 1e-3
    trace = Trace(devices=[DeviceTrace(0, ops=[
        Op(name, a * ms, b * ms) for name, a, b in ops])], spans=[],
        window=(0.0, window_ms * ms))
    family = nh.build(config, cell, impl="interpret", rehearse=False)
    return {"trace": trace, "family": family,
            "peaks": harness.load_peaks("TPU v5 lite"),
            "result": {"trace_steps": 2, "rows_per_chip": 1}}


def test_scope_readers_on_a_hand_trace(config, cell):
    """`ssd` lies inside `mamba`; `moe` and `attn` beside them; each reader
    takes the union of its own scope's intervals, bare, rematerialised or
    under `transpose(jvp(...))`; the roofline share divides `ssd_work`'s
    least time by the `ssd` scope's time."""
    from perfbench.layer_metrics import (nemo_mamba_ms_step, nemo_moe_ms_step,
                                         nemo_ssd_ms_step,
                                         nemo_ssd_roofline_pct)

    g = "jit(spmd_step)/ps.grad"
    text = f"""
  %fusion.1 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/block_0/mamba/in_proj/dot_general"}}
  %fusion.2 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/block_0/mamba/ssd/dot_general"}}
  %fusion.3 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/block_1/moe/dot_general"}}
  %flash_fwd.4 = f32[4]{{0}} custom-call(%a), metadata={{op_name="{g}/block_5/attn/flash_fwd"}}
  %fusion.5 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/rematted_computation/mamba/ssd/exp"}}
  %fusion.6 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/mamba/ssd/dot_general"}}
  %fusion.7 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/transpose(jvp(block_0))/mamba/norm/mul"}}
  %fusion.8 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/transpose(jvp(block_1))/moe/dot_general"}}
  %fusion.9 = f32[4]{{0}} fusion(%a), metadata={{op_name="{g}/head_loss/log_softmax"}}
"""
    ops = [("fusion.1", 0, 2), ("fusion.2", 2, 6), ("fusion.3", 6, 10),
           ("flash_fwd.4", 10, 11), ("fusion.5", 20, 24),
           ("fusion.6", 24, 36), ("fusion.7", 36, 40), ("fusion.8", 40, 44),
           ("fusion.9", 44, 46)]
    obs = _trace_obs(config, cell, text, ops, 60)
    assert nemo_ssd_ms_step.read(obs) == pytest.approx((4 + 4 + 12) / 2)
    assert nemo_mamba_ms_step.read(obs) == pytest.approx(
        (2 + 4 + 4 + 12 + 4) / 2)
    assert nemo_moe_ms_step.read(obs) == pytest.approx((4 + 4) / 2)
    assert nemo_ssd_roofline_pct.read(obs) == pytest.approx(
        100 * 4.128e-3 / 10e-3, rel=1e-3)
    readers = (nemo_ssd_ms_step, nemo_mamba_ms_step, nemo_moe_ms_step,
               nemo_ssd_roofline_pct)
    for reader in readers:      # no trace; a program without the scopes
        assert reader.read({**obs, "trace": None}) is None
    obs = _trace_obs(config, cell, """
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(spmd_step)/mla/dot"}
""", [("fusion.1", 0, 2)], 60)
    for reader in readers:
        assert reader.read(obs) is None


def test_the_counter_readers_take_the_window_steps_of_the_counter_log(
        config, cell):
    from perfbench.layer_metrics import (nemo_moe_routed_here_pct,
                                         nemo_ssd_carry_mean)
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    family = nh.build(config, cell, impl="interpret", rehearse=False)
    log = counter_log()
    log.clear()
    made = 8192 * 6                                 # assignments a layer
    load = lambda share: np.asarray(
        [[0] * 8 + [share * made]] * 4, np.float32)
    warm = (load(0.5), [0.9] * 4)
    steps = [warm] * 6 + [
        (load(0.05), [0.1, 0.2, 0.3, 0.4]),
        (load(0.10), [0.2, 0.2, 0.2, 0.2]),
        (load(0.15), [0.3, 0.1, 0.3, 0.1])] + [warm] * 4
    for step, (moe, carry) in enumerate(steps):
        log.append("MPI_PS.step", step, {
            "moe_load": moe, "ssd_carry": np.asarray(carry, np.float32)})
    obs = {"result": {"attempted": 3, "trace_steps": 4, "rows_per_chip": 1},
           "family": family}
    try:
        assert nemo_ssd_carry_mean.read(obs) == pytest.approx(2.6 / 12)
        assert nemo_moe_routed_here_pct.read(obs) == pytest.approx(10.0)
    finally:
        log.clear()
    assert nemo_ssd_carry_mean.read(obs) is None
    assert nemo_moe_routed_here_pct.read(obs) is None


def test_the_doubles_are_the_accepted_readers():
    from perfbench.layer_metrics import (moe_ms_step, moe_routed_here_pct,
                                         nemo_moe_ms_step,
                                         nemo_moe_routed_here_pct)
    assert nemo_moe_ms_step.read is moe_ms_step.read
    assert nemo_moe_routed_here_pct.read is moe_routed_here_pct.read
