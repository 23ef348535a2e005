"""The benchmark's traffic and its plain references: the pools and streams
are functions of the seed, and in f32 each reference is the program's own
model, so the tolerance the bf16 cells need hides no wrong reference."""

import json
import os

import numpy as np
import pytest

from perfbench import data, harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# -- traffic ------------------------------------------------------------------


def test_token_pool_is_a_function_of_the_seed():
    a = data.token_pool(8, 32, 1000, seed=5)
    assert a.shape == (8, 33) and a.dtype == np.int32
    assert np.array_equal(a, data.token_pool(8, 32, 1000, seed=5))
    assert not np.array_equal(a, data.token_pool(8, 32, 1000, seed=6))
    follows = (a[:, 1:] == (a[:, :-1] * 5 + 3) % 1000).mean()
    assert 0.9 < follows < 1.0               # the recurrence, a little noise


def test_image_pool_is_a_function_of_the_seed():
    x, y = data.image_pool(16, (8, 8, 3), 10, seed=2)
    assert x.shape == (16, 8, 8, 3) and x.dtype == np.float32
    x2, y2 = data.image_pool(16, (8, 8, 3), 10, seed=2)
    assert np.array_equal(x, x2) and np.array_equal(y, y2)
    same = [i for i in range(16) if y[i] == y[0]]
    if len(same) > 1:                        # one class, one mean
        d = x[same[0]] - x[same[1]]
        assert abs(d.std() - np.sqrt(2)) < 0.3


def test_streams_repeat_for_a_seed_and_follow_the_lm_contract():
    pool = {"rows": data.token_pool(16, 8, 50, seed=1)}
    a, b = data.draw_stream(pool, 4, seed=9), data.draw_stream(pool, 4, seed=9)
    for _ in range(3):
        x, y = next(a), next(b)
        assert set(x) == {"tokens", "targets", "positions"}
        assert all(np.array_equal(x[k], y[k]) for k in x)
        assert np.array_equal(x["tokens"][:, 1:], x["targets"][:, :-1])
        assert np.array_equal(x["positions"][0], np.arange(8))
    fn = data.worker_batch_fn(pool, 4, seed=9)
    assert np.array_equal(fn(0, 3)["tokens"], fn(0, 3)["tokens"])
    assert not np.array_equal(fn(0, 3)["tokens"], fn(1, 3)["tokens"])


def test_loader_stream_goes_through_the_programs_loader():
    x, y = data.image_pool(8, (4, 4, 3), 5, seed=0)
    it = data.loader_stream({"x": x, "y": y}, 4, seed=0, prefetch=2,
                            sharding=None)
    batch = next(it)
    assert batch["x"].shape == (4, 4, 4, 3) and batch["y"].shape == (4,)
    it.close()


# -- the references, against the program's own models in f32 ----------------


def _family(name, mode_cell, dtype="float32"):
    import importlib
    with open(os.path.join(ROOT, "perfbench", "configs", name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "perfbench", "workloads",
                           mode_cell + ".json")) as f:
        cell = json.load(f)
    cell = {**cell, **cell["rehearsal"]}
    config["compute_dtype"] = dtype
    mod = importlib.import_module(f"perfbench.models.{config['family']}")
    return mod.build(config, cell, impl="interpret", rehearse=True), cell


TIGHT = {"loss_rel": 1e-5, "grad_norm_rel": 1e-4, "grad_diff_rel": 1e-4}


def test_gpt2_reference_is_the_programs_model_in_f32():
    """In f32 the plain reference and the program's model (flash attention
    under the interpreter) are the same function: the tolerance that the
    bf16 cells need is not hiding a wrong reference."""
    import jax
    fam, cell = _family("gpt2-medium", "gpt2m-sync-1chip")
    params = fam.init_params(0)
    pool = data.make_pool(cell["feed"], fam.shapes, 0)
    out = harness.reference_check(fam, "sync", params,
                                  data.fixed_sample(pool, 2), TIGHT,
                                  jax.devices()[0])
    assert out["ok"], out


def test_resnet_reference_is_the_programs_model_in_f32():
    import jax
    fam, cell = _family("resnet50-imagenet", "resnet50-async-1chip")
    params = fam.init_params(0)
    pool = data.make_pool(cell["feed"], fam.shapes, 0)
    sample = data.fixed_sample(pool, 8)
    frozen = harness.reference_check(fam, "async", params, sample, TIGHT,
                                     jax.devices()[0])
    assert frozen["ok"], frozen
    # batch statistics over 8 x 2 x 2 values in the last stage are touchy:
    # flax takes E[x^2] - E[x]^2 and the reference the two-pass variance
    batch = harness.reference_check(
        fam, "sync", params, sample,
        {"loss_rel": 1e-4, "grad_norm_rel": 1e-2, "grad_diff_rel": 5e-2},
        jax.devices()[0])
    assert batch["ok"], batch


def test_a_lower_precision_fails_the_check():
    """bf16 compute against the f32 reference must not pass a tolerance
    fit for f32: the check can tell precisions apart."""
    import jax
    fam, cell = _family("gpt2-medium", "gpt2m-sync-1chip", dtype="bfloat16")
    params = fam.init_params(0)
    pool = data.make_pool(cell["feed"], fam.shapes, 0)
    out = harness.reference_check(fam, "sync", params,
                                  data.fixed_sample(pool, 2), TIGHT,
                                  jax.devices()[0])
    assert not out["ok"] and out["grad_diff_rel"] > 1e-3


def test_resnet_check_opens_the_zero_initialised_blocks():
    from perfbench.models import resnet
    import jax.numpy as jnp
    p = {"a/BatchNorm_2/scale": jnp.zeros(4), "a/BatchNorm_0/scale":
         jnp.ones(4) * 0.5, "a/Conv_0/kernel": jnp.zeros((1, 1, 4, 4))}
    q = resnet._open_blocks(p)
    assert float(q["a/BatchNorm_2/scale"][0]) == pytest.approx(0.05)
    assert float(q["a/BatchNorm_0/scale"][0]) == 0.5
    assert float(q["a/Conv_0/kernel"].sum()) == 0.0


def test_async_staleness_bound_follows_the_queue():
    from perfbench.modes.async_inprocess import staleness_bound

    class Opt:
        quota, num_workers, credit_window = 1, 1, 0
    assert staleness_bound(Opt) == 2
    Opt.credit_window = 4
    assert staleness_bound(Opt) == 5
