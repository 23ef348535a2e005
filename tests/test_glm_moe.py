"""GLM-4.7-Flash through the program's model against the benchmark's plain
reference (`perfbench/models/glm_moe.py`: low-rank q, rotation and MTP
module written from the equations, dense attention, a masked pass per
expert), at toy sizes in f32; the rotation's properties; the MTP module's
alignment; the grown `LatentAttention` against the values Kimi's layer gave
before it grew; and the shares of an expert layer against the uncut layer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.models import glm_moe as ref
from pytorch_ps_mpi_tpu.models.glm_moe import (GlmMoeConfig, GlmMoeLM,
                                               glm_aux, make_glm_loss)
from pytorch_ps_mpi_tpu.models.kimi_linear import LatentAttention, rotate
from pytorch_ps_mpi_tpu.models.moe import ShareOfExperts
from pytorch_ps_mpi_tpu.parallel.ring_attention import dense_attention
from pytorch_ps_mpi_tpu.utils.flatten import (named_params,
                                              unflatten_params)

TOY = dict(vocab_size=61, d_model=32, n_layers=3, first_k_dense=1, d_ff=48,
           d_expert=16, n_experts=16, experts_held=(2, 3, 5, 7), top_k=4,
           n_shared=1, routed_scale=1.8, n_heads=2, q_lora_rank=12,
           kv_lora_rank=16, qk_nope_dim=12, qk_rope_dim=4, v_dim=16,
           rope_theta=1e6, n_mtp=1)
SIZES = dict(TOY, eps=1e-5)
WEIGHT = 0.3


def lm_rows(rows):
    b, s1 = rows.shape
    return {"tokens": jnp.asarray(rows[:, :-1]),
            "targets": jnp.asarray(rows[:, 1:]),
            "positions": jnp.broadcast_to(jnp.arange(s1 - 1, dtype=jnp.int32),
                                          (b, s1 - 1))}


@pytest.fixture(scope="module")
def toy():
    model = GlmMoeLM(GlmMoeConfig(**TOY))
    batch = lm_rows(
        np.random.RandomState(0).randint(0, 61, (2, 42)).astype(np.int32))
    params = named_params(model.init(
        jax.random.PRNGKey(1), batch["tokens"], batch["positions"],
        batch["targets"])["params"])
    return model, params, batch


@pytest.fixture(scope="module")
def losses(toy):
    """``batch -> (loss, loss_main, loss_mtp)`` of the program, jitted."""
    model, params, _ = toy
    loss = make_glm_loss(model, WEIGHT)

    @jax.jit
    def run(batch):
        total, aux = loss(params, glm_aux(model), batch)
        c = aux["counters"]
        return total, c["loss_main"], c["loss_mtp"]

    return run


def test_the_toy_has_a_dense_layer_two_expert_layers_and_an_mtp_module(toy):
    _, params, _ = toy
    kinds = ["dense" if f"block_{i}/mlp/gate/kernel" in params else "moe"
             for i in range(3)]
    assert kinds == ["dense", "moe", "moe"]
    assert "mtp/block/moe/router" in params
    assert params["mtp/eh_proj/kernel"].shape == (64, 32)
    assert params["block_0/attn/q_a_proj/kernel"].shape == (32, 12)
    assert params["block_0/attn/q_b_proj/kernel"].shape == (12, 2 * 16)
    assert "block_0/attn/q_proj/kernel" not in params
    # embedding and head appear once: the MTP module has none of its own
    assert [n for n in params if "embed" in n] == ["tok_embed/embedding"]
    assert [n for n in params if "head" in n] == ["lm_head/kernel"]
    assert sum(p.size for p in params.values()) == ref.total_params(SIZES)


def test_losses_and_gradient_match_the_plain_reference(toy):
    """f32 against f32 at highest precision: the two differ by summation
    order only, so the loss agrees to 1e-5 and each gradient to 2e-3 of
    its size (the rotation's f32 sines at positions to 40 included)."""
    model, params, batch = toy
    loss = make_glm_loss(model, WEIGHT)

    def system(p):
        total, aux = loss(p, glm_aux(model), batch)
        return total, aux["counters"]

    reference = lambda p: ref.reference_loss(SIZES, p, batch, WEIGHT)
    with jax.default_matmul_precision("highest"):
        (got, counters), got_grads = jax.jit(
            jax.value_and_grad(system, has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(reference))(params)
        want_main, want_mtp = jax.jit(
            lambda p: ref.reference_losses(SIZES, p, batch))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert float(counters["loss_main"]) == pytest.approx(float(want_main),
                                                         rel=1e-5)
    assert float(counters["loss_mtp"]) == pytest.approx(float(want_mtp),
                                                        rel=1e-5)
    assert float(got) == pytest.approx(
        float(want_main) + WEIGHT * float(want_mtp), rel=1e-5)
    assert set(got_grads) == set(want_grads)
    for name, w in want_grads.items():
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(w), rtol=2e-3,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)
    bias = [n for n in params if n.endswith("e_score_correction_bias")]
    assert len(bias) == 3 and all(
        float(jnp.max(jnp.abs(got_grads[n]))) == 0.0 for n in bias)


def test_the_step_reports_load_and_both_losses(toy):
    model, params, batch = toy
    _, aux = make_glm_loss(model, WEIGHT)(params, glm_aux(model), batch)
    want = glm_aux(model)["counters"]
    assert {k: np.shape(v) for k, v in aux["counters"].items()} \
        == {k: v.shape for k, v in want.items()} \
        == {"moe_load": (3, 5), "loss_main": (), "loss_mtp": ()}
    load = np.asarray(aux["counters"]["moe_load"])    # the MTP block's last
    np.testing.assert_array_equal(load[:, :-1].sum(axis=1), load[:, -1])
    assert (load[:, -1] <= 2 * 41 * 4).all() and (load[:, -1] > 0).all()


# -- rotation -----------------------------------------------------------------


def test_rotation_keeps_the_norm_of_every_pair():
    x = jnp.asarray(np.random.RandomState(0).randn(2, 9, 3, 8), jnp.float32)
    pos = jnp.asarray(np.random.RandomState(1).randint(0, 8192, (2, 9)))
    y = rotate(x, pos, 1e6)
    pair = lambda z: jnp.square(z[..., :4]) + jnp.square(z[..., 4:])
    np.testing.assert_allclose(np.asarray(pair(y)), np.asarray(pair(x)),
                               rtol=1e-5)
    # position 0 turns nothing, and the shared key's form takes no head axis
    np.testing.assert_array_equal(
        np.asarray(rotate(x, jnp.zeros((2, 9), jnp.int32), 1e6)),
        np.asarray(x))
    np.testing.assert_allclose(np.asarray(rotate(x[:, :, 0], pos, 1e6)),
                               np.asarray(y[:, :, 0]), rtol=1e-6)


def test_rotated_scores_depend_on_the_distance_only():
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 1, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, 1, 8), jnp.float32)
    at = lambda x, p: rotate(x, jnp.full((1, 1), p, jnp.int32), 1e4)
    score = lambda i, j: float(jnp.sum(at(q, i) * at(k, j)))
    assert score(7, 3) == pytest.approx(score(104, 100), rel=1e-4)
    assert score(7, 3) == pytest.approx(score(4, 0), rel=1e-4)
    assert abs(score(7, 3) - score(7, 4)) > 1e-3      # ... and do depend on it


def test_the_rotation_matches_the_references():
    x = jnp.asarray(np.random.RandomState(3).randn(2, 5, 2, 4), jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [9, 8, 7, 6, 5]], jnp.int32)
    np.testing.assert_allclose(np.asarray(rotate(x, pos, 1e6)),
                               np.asarray(ref.rope(x, pos, 1e6)), rtol=1e-6)


def test_positions_shifted_by_a_constant_leave_the_loss_equal(toy, losses):
    _, _, batch = toy
    moved = dict(batch, positions=batch["positions"] + 37)
    for a, b in zip(losses(batch), losses(moved)):
        assert float(a) == pytest.approx(float(b), rel=2e-5)
    other = dict(batch, positions=batch["positions"] * 2)
    assert abs(float(losses(other)[0]) - float(losses(batch)[0])) > 1e-4


# -- multi-token prediction ---------------------------------------------------


@pytest.fixture(scope="module")
def apply(toy):
    """``batch -> (logits, mtp_logits)`` of the program, jitted."""
    model, params, _ = toy
    variables = {"params": unflatten_params(params)}
    return jax.jit(lambda b: model.apply(
        variables, b["tokens"], b["positions"], b["targets"])[:2])


def _terms(logits, labels):
    """Per-position ``-log softmax(logits)[label]``."""
    return -jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                labels[..., None], axis=-1)[..., 0]


def test_the_module_embeds_the_next_token_at_its_own_position(toy, apply):
    """``Emb(t_{i+1}) = Emb(targets[i])`` enters the module at position
    ``i``, and attention is causal: another ``targets[j]`` leaves the main
    logits and the module's logits before ``j`` as they were."""
    _, _, batch = toy
    j = 17
    other = dict(batch, targets=batch["targets"].at[0, j].add(1) % 61)
    logits, mtp = apply(batch)
    logits2, mtp2 = apply(other)
    np.testing.assert_array_equal(np.asarray(logits2), np.asarray(logits))
    np.testing.assert_array_equal(np.asarray(mtp2[0, :j]),
                                  np.asarray(mtp[0, :j]))
    np.testing.assert_array_equal(np.asarray(mtp2[1]), np.asarray(mtp[1]))
    assert float(jnp.max(jnp.abs(mtp2[0, j] - mtp[0, j]))) > 1e-3


def test_a_later_target_moves_the_mtp_loss_and_not_the_main_loss(
        toy, apply, losses):
    """Position ``i`` of the module is held to ``targets[i + 1]``, position
    ``i`` of the main model to ``targets[i]``: another ``targets[i + 1]``
    moves the module's term at ``i`` (and `loss_mtp`) and leaves the main
    model's term at ``i``; and the scalars the step reports are the means of
    exactly these terms, `loss_mtp` over the ``S - 1`` positions a row that
    have a token after next: the last position carries no MTP loss."""
    _, _, batch = toy
    i = 20
    other = dict(batch, targets=batch["targets"].at[1, i + 1].add(1) % 61)
    terms = lambda b: (_terms(apply(b)[0], b["targets"]),
                       _terms(apply(b)[1][:, :-1], b["targets"][:, 1:]))
    (main, mtp), (main2, mtp2) = terms(batch), terms(other)
    assert mtp.shape == (2, 40)
    assert float(main2[1, i]) == float(main[1, i])
    assert abs(float(mtp2[1, i]) - float(mtp[1, i])) > 1e-3
    for b, (m, t) in ((batch, (main, mtp)), (other, (main2, mtp2))):
        total, loss_main, loss_mtp = losses(b)
        assert float(loss_main) == pytest.approx(float(jnp.mean(m)), rel=1e-6)
        assert float(loss_mtp) == pytest.approx(float(jnp.mean(t)), rel=1e-6)
        assert float(total) == pytest.approx(
            float(loss_main) + WEIGHT * float(loss_mtp), rel=1e-6)
    assert float(losses(other)[2]) != float(losses(batch)[2])


def test_embedding_and_head_gradients_are_the_sum_of_both_uses(toy):
    model, params, batch = toy

    def partial_losses(p):
        logits, mtp_logits, _ = model.apply(
            {"params": unflatten_params(p)}, batch["tokens"],
            batch["positions"], batch["targets"])
        return (jnp.mean(_terms(logits, batch["targets"])),
                jnp.mean(_terms(mtp_logits[:, :-1], batch["targets"][:, 1:])))

    of_main = jax.jit(jax.grad(lambda p: partial_losses(p)[0]))(params)
    of_mtp = jax.jit(jax.grad(lambda p: partial_losses(p)[1]))(params)
    total = jax.jit(jax.grad(lambda p: make_glm_loss(model, WEIGHT)(
        p, glm_aux(model), batch)[0]))(params)
    for name in ("tok_embed/embedding", "lm_head/kernel"):
        assert float(jnp.max(jnp.abs(of_main[name]))) > 0
        assert float(jnp.max(jnp.abs(of_mtp[name]))) > 0     # both use it
        np.testing.assert_allclose(
            np.asarray(total[name]),
            np.asarray(of_main[name] + WEIGHT * of_mtp[name]), rtol=1e-4,
            atol=1e-7, err_msg=name)
    # the module's own parts see its loss only; the main blocks see both;
    # the main model's final norm is not on the module's path
    assert float(jnp.max(jnp.abs(of_main["mtp/eh_proj/kernel"]))) == 0.0
    assert float(jnp.max(jnp.abs(of_mtp["block_0/mlp/up/kernel"]))) > 0
    assert float(jnp.max(jnp.abs(of_mtp["final_norm/scale"]))) == 0.0


# -- the shared classes -------------------------------------------------------

KIMI_LAYER_AT_THE_PARENT = {    # LatentAttention(32, 2, 16, 8, 4, 8), key 7
    "kv_a_norm/scale": ((16,), 16.0, 1.0),
    "kv_a_proj/kernel": ((32, 20), 4.155606269836426, -0.22727452218532562),
    "kv_b_proj/kernel": ((16, 32), -4.575944423675537, -0.07633610814809799),
    "o_proj/kernel": ((16, 32), -1.1208707094192505, 0.061917293816804886),
    "q_proj/kernel": ((32, 24), 5.8606061935424805, 0.09048151969909668),
}
KIMI_OUTPUT_AT_THE_PARENT = (
    [0.029874561354517937, -0.43090444803237915, 0.31444066762924194,
     0.4061816334724426, 0.30818215012550354, 0.17945486307144165],
    304.26312255859375)     # y[1, 5, :6] and sum |y|, commit 8c23ee9


def test_the_layer_without_rank_or_base_is_kimis_layer_as_it_was():
    """`LatentAttention(q_lora_rank=None, rope_theta=None)`: the parameter
    names, shapes and seeded values, and the outputs, that the layer had
    before it grew (pinned at the parent commit); positions change nothing."""
    layer = LatentAttention(
        32, 2, 16, 8, 4, 8, 1e-5, jnp.float32,
        lambda q, k, v: dense_attention(q, k, v, causal=True))
    x = jnp.asarray(np.random.RandomState(5).randn(2, 12, 32), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(7), x)
    params = named_params(variables["params"])
    assert set(params) == set(KIMI_LAYER_AT_THE_PARENT)
    for name, (shape, total, fourth) in KIMI_LAYER_AT_THE_PARENT.items():
        assert params[name].shape == shape
        assert float(jnp.sum(params[name])) == pytest.approx(total, rel=1e-6)
        assert float(params[name].reshape(-1)[3]) == fourth, name
    y = layer.apply(variables, x)
    head, total = KIMI_OUTPUT_AT_THE_PARENT
    np.testing.assert_allclose(np.asarray(y[1, 5, :6]), head, rtol=1e-6)
    assert float(jnp.sum(jnp.abs(y))) == pytest.approx(total, rel=1e-6)
    moved = layer.apply(variables, x, jnp.full((2, 12), 99, jnp.int32))
    np.testing.assert_array_equal(np.asarray(moved), np.asarray(y))


def test_four_shares_of_four_experts_add_up_to_the_uncut_layer():
    """The guide's shares test at a toy router over 16: the routed parts of
    the four shares summed, and the shared expert — which every chip
    computes alike — counted once, equal the uncut layer's output (all 16
    experts held by one layer, here and in the plain reference)."""
    d, f, n, k = 32, 16, 16, 4
    x = jnp.asarray(np.random.RandomState(4).randn(2, 24, d), jnp.float32)
    whole = ShareOfExperts(d, f, n, tuple(range(n)), k, 1.8, f)
    full = named_params(whole.init(jax.random.PRNGKey(2), x)["params"])
    with jax.default_matmul_precision("highest"):
        want, load = whole.apply({"params": unflatten_params(full)}, x)
        sizes = dict(top_k=k, routed_scale=1.8, n_shared=1,
                     experts_held=tuple(range(n)))
        np.testing.assert_allclose(
            np.asarray(ref._moe_layer(sizes, full, x)), np.asarray(want),
            rtol=1e-4, atol=1e-5)
        shared = ref._swiglu(x, full["shared/gate/kernel"],
                             full["shared/up/kernel"],
                             full["shared/down/kernel"])
        routed, here = jnp.zeros_like(x), 0.0
        for first in range(0, n, 4):
            held = tuple(range(first, first + 4))
            share = dict(full, **{w: full[w][first:first + 4]
                                  for w in ("w_gate", "w_up", "w_down")})
            y, part = ShareOfExperts(d, f, n, held, k, 1.8, f).apply(
                {"params": unflatten_params(share)}, x)
            routed = routed + (y - shared)
            here += float(part[-1])
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert here == float(load[-1]) == 2 * 24 * k     # every assignment once
