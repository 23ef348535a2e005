"""The shape formulas behind `mfu_pct` and `flash_roofline_pct`, against
counts made by hand, and the table of peaks."""

import json
import os

import pytest

from perfbench import harness
from perfbench.layer_metrics.flash_roofline_pct import least_seconds
from perfbench.models import gpt2, resnet

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_matmul_parameters():
    s = gpt2.sizes(config("gpt2-medium"), rehearse=False)
    # per block: qkv 1024x3072, out 1024x1024, fc1 1024x4096, fc2 4096x1024
    per_block = 3145728 + 1048576 + 4194304 + 4194304
    assert per_block == 12 * 1024 * 1024
    assert gpt2.matmul_params(s) == 24 * per_block + 1024 * 50257
    assert gpt2.matmul_params(s) == 353_453_056


def test_gpt2_medium_flops_per_token():
    s = gpt2.sizes(config("gpt2-medium"), rehearse=False)
    # causal attention per token and layer: QK^T and PV, 2*S*d each dense,
    # half under the mask = 2*S*d forward, three times with the backward
    attention = 24 * 3 * 2 * 1024 * 1024
    assert attention == 150_994_944
    assert gpt2.flops_per_sample(s, 1024) == 6 * 353_453_056 + attention
    assert gpt2.flops_per_sample(s, 1024) == pytest.approx(2.2717e9, rel=1e-4)


def test_flash_work_of_one_layer_by_hand():
    s = dict(gpt2.sizes(config("gpt2-medium"), rehearse=False), n_layer=1)
    w = gpt2.flash_work(s, batch=8, seq_len=1024)
    one_matmul = 2 * 8 * 16 * 1024 * 1024 * 64 // 2     # causal half
    assert w["flops"] == 7 * one_matmul                  # 2 forward + 5 back
    tensor = 8 * 1024 * 16 * 64 * 2                      # bf16 [B,S,H,D]
    stats = 8 * 16 * 1024 * 4
    assert w["bytes"] == 12 * tensor + 2 * stats
    assert set(w["kernels"]) == {"_fwd_kernel", "_bwd_dkdv_kernel",
                                 "_bwd_dq_kernel"}
    assert w["match"] == ('custom_call_target="tpu_custom_call"',)
    least, bound = least_seconds(w, harness.load_peaks("TPU v5 lite"))
    assert bound == "compute"
    assert least == pytest.approx(w["flops"] / 197e12)


def test_resnet50_multiply_accumulates():
    s = resnet.sizes(config("resnet50-imagenet"), rehearse=False)
    layers = resnet.conv_layers(s)
    assert len(layers) == 1 + 16 * 3 + 4 + 1      # stem, blocks, shortcuts, fc
    assert layers[0] == (7, 3, 64, 112)
    assert layers[1] == (1, 64, 64, 56)
    assert layers[-1] == (1, 2048, 1000, 1)
    stem = 7 * 7 * 3 * 64 * 112 * 112
    assert stem == 118_013_952
    # first block: 1x1 64->64, 3x3 64->64, 1x1 64->256, shortcut 64->256
    first = (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256) * 56 * 56
    assert sum(k * k * ci * co * o * o
               for k, ci, co, o in layers[1:5]) == first
    # torchvision's resnet50 (v1.5): 4.09 GMACs at 224 x 224
    assert resnet.macs_per_image(s) == 4_089_184_256
    assert resnet.flops_per_sample(s) == 6 * 4_089_184_256


def test_resnet50_stride_sits_on_the_3x3():
    s = resnet.sizes(config("resnet50-imagenet"), rehearse=False)
    layers = resnet.conv_layers(s)
    i = layers.index((1, 256, 128, 56))           # stage 2 opens at 56 px
    assert layers[i + 1] == (3, 128, 128, 28)     # and halves on the 3x3


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v5e"])
def test_v5e_peaks(kind):
    p = harness.load_peaks(kind)
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", ""])
def test_unknown_device_kind_is_an_error_not_a_default(kind):
    with pytest.raises(KeyError, match="peaks.json"):
        harness.load_peaks(kind)


@pytest.mark.parametrize("q,want", [(0, 1.0), (50, 2.5), (100, 4.0),
                                    (25, 1.75)])
def test_percentile(q, want):
    assert harness.percentile([4.0, 1.0, 3.0, 2.0], q) == pytest.approx(want)


def test_percentile_of_nothing_raises():
    with pytest.raises(ValueError):
        harness.percentile([], 50)
