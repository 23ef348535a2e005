"""Kimi-Linear through the program's model against the benchmark's plain
reference (`perfbench/models/kimi_linear.py`: the recurrence token by
token, dense attention, a masked pass per expert), at toy sizes in f32."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.models import kimi_linear as ref
from pytorch_ps_mpi_tpu.models.kimi_linear import (KimiLinearConfig,
                                                   KimiLinearLM,
                                                   causal_conv_silu,
                                                   kimi_aux, make_kimi_loss)
from pytorch_ps_mpi_tpu.utils.flatten import named_params

TOY = dict(vocab_size=61, d_model=32, n_layers=5, kda_layers=(1, 2, 3, 5),
           first_k_dense=1, d_ff=48, d_expert=16, n_experts=16,
           experts_held=(2, 3, 5, 7), top_k=4, n_shared=1,
           routed_scale=2.446, n_heads=2, kv_lora_rank=16, qk_nope_dim=8,
           qk_rope_dim=4, v_dim=8, kda_heads=2, kda_head_dim=8, conv_size=4,
           gate_rank=8)
SIZES = dict(TOY, mla_layers=(4,), eps=1e-5)


@pytest.fixture(scope="module")
def toy():
    model = KimiLinearLM(KimiLinearConfig(**TOY))
    rows = np.random.RandomState(0).randint(0, 61, (2, 42)).astype(np.int32)
    batch = {"tokens": jnp.asarray(rows[:, :-1]),
             "targets": jnp.asarray(rows[:, 1:]),
             "positions": jnp.zeros((2, 41), jnp.int32)}
    params = named_params(
        model.init(jax.random.PRNGKey(1), batch["tokens"])["params"])
    return model, params, batch


def test_the_five_layer_toy_has_the_published_pattern(toy):
    _, params, _ = toy
    kinds = [("kda" if f"block_{i}/attn/A_log" in params else "mla",
              "dense" if f"block_{i}/mlp/gate/kernel" in params else "moe")
             for i in range(5)]
    assert kinds == [("kda", "dense"), ("kda", "moe"), ("kda", "moe"),
                     ("mla", "moe"), ("kda", "moe")]
    assert "pos_embed/embedding" not in params       # no position embedding
    assert params["block_1/moe/w_gate"].shape == (4, 32, 16)   # held only
    assert params["block_1/moe/router"].shape == (32, 16)      # all experts


def test_loss_and_gradient_match_the_plain_reference(toy):
    model, params, batch = toy
    loss = make_kimi_loss(model)
    system = lambda p: loss(p, kimi_aux(model), batch)[0]
    reference = lambda p: ref.reference_loss(SIZES, p, batch)
    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.jit(jax.value_and_grad(system))(params)
        want, want_grads = jax.jit(jax.value_and_grad(reference))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, w in want_grads.items():
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(w), rtol=2e-3,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)
    bias = [n for n in params if n.endswith("e_score_correction_bias")]
    assert bias and all(float(jnp.max(jnp.abs(got_grads[n]))) == 0.0
                        for n in bias)               # moves the choice only


def test_the_selection_bias_changes_some_selections(toy):
    _, params, _ = toy
    x = jnp.asarray(np.random.RandomState(3).randn(512, 32), jnp.float32)
    scores = jax.nn.sigmoid(x @ params["block_1/moe/router"])
    bias = params["block_1/moe/e_score_correction_bias"]
    with_bias = jax.lax.top_k(scores + bias, 4)[1]
    without = jax.lax.top_k(scores, 4)[1]
    changed = float(jnp.mean(jnp.sort(with_bias) != jnp.sort(without)))
    assert 0.0 < changed < 0.5


def test_the_loss_reports_the_expert_load(toy):
    model, params, batch = toy
    _, aux = make_kimi_loss(model)(params, kimi_aux(model), batch)
    load = np.asarray(aux["counters"]["moe_load"])
    assert load.shape == kimi_aux(model)["counters"]["moe_load"].shape \
        == (4, 5)
    np.testing.assert_array_equal(load[:, :-1].sum(axis=1), load[:, -1])
    assert (load[:, -1] <= 2 * 41 * 4).all() and load[:, -1].sum() > 0


def test_causal_convolution_sees_no_future_token():
    x = jnp.asarray(np.random.RandomState(0).randn(1, 12, 3), jnp.float32)
    kernel = jnp.asarray(np.random.RandomState(1).randn(4, 3), jnp.float32)
    y = causal_conv_silu(x, kernel)
    y_cut = causal_conv_silu(x.at[:, 7:].set(0.0), kernel)
    np.testing.assert_array_equal(np.asarray(y[:, :7]),
                                  np.asarray(y_cut[:, :7]))
    # the last tap multiplies the current token
    first = jax.nn.silu(x[0, 0] * kernel[3])
    np.testing.assert_allclose(np.asarray(y[0, 0]), np.asarray(first),
                               rtol=1e-6)

