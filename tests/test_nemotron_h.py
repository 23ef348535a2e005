"""Nemotron-H through the program's model against the benchmark's plain
reference (`perfbench/models/nemotron_h.py`: the Mamba-2 recurrence token
by token, dense attention over gathered kv heads, a masked pass per
expert), at toy sizes in f32; the relu² expert layer against a dense loop
and its shares against the uncut layer; the SwiGLU layer against the values
it gave before it took an activation; the parts of the blocks; and the
step with its counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.models import nemotron_h as ref
from pytorch_ps_mpi_tpu.models.moe import ShareOfExperts
from pytorch_ps_mpi_tpu.models.nemotron_h import (GQAttention,
                                                  GroupGatedRMSNorm,
                                                  NemotronHConfig,
                                                  NemotronHLM,
                                                  make_nemotron_loss,
                                                  nemotron_aux)
from pytorch_ps_mpi_tpu.utils.flatten import named_params, unflatten_params

TOY = dict(vocab_size=61, d_model=32, pattern="MEMEM*EME", d_expert=16,
           d_shared=24, n_experts=16, experts_held=(2, 3, 5, 7), top_k=4,
           routed_scale=2.5, n_heads=4, n_kv_heads=2, head_dim=8,
           mamba_heads=4, mamba_head_dim=8, n_groups=2, d_state=8, d_conv=4,
           chunk=8)
SIZES = dict(TOY, eps=1e-5)


def lm_rows(rows):
    b, s1 = rows.shape
    return {"tokens": jnp.asarray(rows[:, :-1]),
            "targets": jnp.asarray(rows[:, 1:]),
            "positions": jnp.broadcast_to(jnp.arange(s1 - 1, dtype=jnp.int32),
                                          (b, s1 - 1))}


@pytest.fixture(scope="module")
def toy():
    model = NemotronHLM(NemotronHConfig(**TOY))
    batch = lm_rows(
        np.random.RandomState(0).randint(0, 61, (2, 33)).astype(np.int32))
    params = named_params(model.init(jax.random.PRNGKey(1),
                                     batch["tokens"])["params"])
    return model, params, batch


def test_the_toy_has_one_part_a_layer_by_the_pattern(toy):
    _, params, _ = toy
    kinds = []
    for i in range(9):
        if f"block_{i}/mixer/A_log" in params:
            kinds.append("M")
        elif f"block_{i}/mixer/router" in params:
            kinds.append("E")
        elif f"block_{i}/mixer/q_proj/kernel" in params:
            kinds.append("*")
    assert "".join(kinds) == "MEMEM*EME"
    # Mamba-2: one input projection [z | xBC | dt], a biased convolution
    # over xBC, a scalar decay, step bias and skip a head, a gated norm
    assert params["block_0/mixer/in_proj/kernel"].shape \
        == (32, 32 + (32 + 2 * 2 * 8) + 4)
    assert params["block_0/mixer/conv"].shape == (4, 64)
    assert params["block_0/mixer/conv_bias"].shape == (64,)
    for name in ("A_log", "dt_bias", "D"):
        assert params[f"block_2/mixer/{name}"].shape == (4,)
    assert params["block_4/mixer/norm/scale"].shape == (32,)
    # relu2 experts: no gate, held experts only, the router over all 16
    assert "block_1/mixer/w_gate" not in params
    assert params["block_1/mixer/w_up"].shape == (4, 32, 16)
    assert params["block_1/mixer/router"].shape == (32, 16)
    assert {n.split("/")[-2] for n in params if "/shared/" in n} \
        == {"up", "down"}
    # GQA: 4 query heads, 2 kv heads of 8
    assert params["block_5/mixer/k_proj/kernel"].shape == (32, 16)
    assert [n for n in params if "head" in n] == ["lm_head/kernel"]
    assert sum(p.size for p in params.values()) == ref.total_params(SIZES)


def test_loss_and_gradient_match_the_plain_reference(toy):
    """f32 against f32 at highest precision: chunked scan against the
    recurrence, grouped experts against masked passes, flash-free GQA
    against gathered kv heads: the loss to 1e-5 and each gradient to 1e-3
    of its size."""
    model, params, batch = toy
    loss = make_nemotron_loss(model)

    def system(p):
        total, aux = loss(p, nemotron_aux(model), batch)
        return total, aux["counters"]

    with jax.default_matmul_precision("highest"):
        (got, counters), got_grads = jax.jit(
            jax.value_and_grad(system, has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.reference_loss(SIZES, p, batch)))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert set(got_grads) == set(want_grads)
    for name, w in want_grads.items():
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(w), rtol=1e-3,
            atol=1e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)
    bias = [n for n in params if n.endswith("e_score_correction_bias")]
    assert len(bias) == 4 and all(
        float(jnp.max(jnp.abs(got_grads[n]))) == 0.0 for n in bias)
    assert counters["moe_load"].shape == (4, 5)
    assert counters["ssd_carry"].shape == (4,)
    assert bool(jnp.all((counters["ssd_carry"] > 0)
                        & (counters["ssd_carry"] < 1)))


def test_no_position_enters(toy):
    model, params, batch = toy
    loss = make_nemotron_loss(model)
    moved = dict(batch, positions=batch["positions"] + 1000)
    a = loss(params, nemotron_aux(model), batch)[0]
    b = loss(params, nemotron_aux(model), moved)[0]
    assert float(a) == float(b)


def test_the_config_reads_the_pattern_and_refuses_what_it_cannot_run():
    c = NemotronHConfig(**TOY)
    assert c.kinds == ("mamba", "moe", "mamba", "moe", "mamba", "attn",
                       "moe", "mamba", "moe")
    assert (c.count("mamba"), c.count("moe"), c.count("attn"), c.d_inner) \
        == (4, 4, 1, 32)
    with pytest.raises(ValueError, match="layer letters"):
        NemotronHConfig(**dict(TOY, pattern="MEX"))
    with pytest.raises(ValueError, match="divide"):
        NemotronHConfig(**dict(TOY, n_groups=3))


# -- the parts ----------------------------------------------------------------


def test_gqa_repeats_each_kv_head_over_its_query_heads():
    """Query head ``h`` meets kv head ``h // (H / Hk)``: the attention
    callable is handed four heads whose keys and values are kv heads 0, 0,
    1, 1."""
    cfg = NemotronHConfig(**TOY)
    seen = {}

    def attn(q, k, v):
        seen.update(q=q, k=k, v=v)
        return q

    layer = GQAttention(cfg, attn)
    u = jnp.asarray(np.random.RandomState(2).randn(2, 8, 32), jnp.float32)
    p = layer.init(jax.random.PRNGKey(3), u)["params"]
    layer.apply({"params": p}, u)
    k = (u @ p["k_proj"]["kernel"]).reshape(2, 8, 2, 8)
    v = (u @ p["v_proj"]["kernel"]).reshape(2, 8, 2, 8)
    for h, g in enumerate((0, 0, 1, 1)):
        np.testing.assert_allclose(np.asarray(seen["k"][:, :, h]),
                                   np.asarray(k[:, :, g]), rtol=1e-6)
        np.testing.assert_allclose(np.asarray(seen["v"][:, :, h]),
                                   np.asarray(v[:, :, g]), rtol=1e-6)
    assert seen["q"].shape == (2, 8, 4, 8)


def test_the_gated_norm_takes_its_statistics_a_group():
    norm = GroupGatedRMSNorm(4, 1e-5, jnp.float32)
    rng = np.random.RandomState(4)
    y = jnp.asarray(rng.randn(3, 8), jnp.float32)
    z = jnp.asarray(rng.randn(3, 8), jnp.float32)
    p = norm.init(jax.random.PRNGKey(0), y, z)
    out = norm.apply(p, y, z)
    g = np.asarray(y * jax.nn.silu(z)).reshape(3, 2, 4)
    want = g / np.sqrt(np.mean(g ** 2, axis=-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(out), want.reshape(3, 8),
                               rtol=1e-5, atol=1e-6)
    # scaling one group moves nothing of the other
    scaled = norm.apply(p, y.at[:, :4].multiply(10.0), z)
    np.testing.assert_allclose(np.asarray(scaled[:, 4:]),
                               np.asarray(out[:, 4:]), rtol=1e-6)


def _relu2_layer_by_hand(p, x, held, n, k, scale):
    """Every token, every expert chosen and held, one at a time."""
    toks = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    scores = 1 / (1 + np.exp(-toks @ np.asarray(p["router"], np.float64)))
    choice = scores + np.asarray(p["e_score_correction_bias"], np.float64)
    y = np.zeros_like(toks)
    for t in range(len(toks)):
        chosen = np.argsort(-choice[t])[:k]
        w = scores[t, chosen] / scores[t, chosen].sum() * scale
        for e, we in zip(chosen, w):
            if e in held:
                i = held.index(e)
                hidden = np.maximum(toks[t] @ p["w_up"][i], 0.0) ** 2
                y[t] += we * (hidden @ p["w_down"][i])
        hidden = np.maximum(toks[t] @ p["shared/up/kernel"], 0.0) ** 2
        y[t] += hidden @ p["shared/down/kernel"]
    return y.reshape(x.shape)


def test_the_relu2_layer_against_a_dense_loop_over_tokens_and_experts():
    d, f, n, k, held = 16, 8, 16, 4, (1, 4, 6, 11)
    x = jnp.asarray(np.random.RandomState(6).randn(2, 10, d), jnp.float32)
    layer = ShareOfExperts(d, f, n, held, k, 2.5, 12, act="relu2")
    p = named_params(layer.init(jax.random.PRNGKey(5), x)["params"])
    with jax.default_matmul_precision("highest"):
        y, load = layer.apply({"params": unflatten_params(p)}, x)
    want = _relu2_layer_by_hand(
        {n_: np.asarray(v, np.float64) for n_, v in p.items()}, x,
        list(held), n, k, 2.5)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    assert float(load[-1]) == float(jnp.sum(load[:-1])) > 0
    # the gradient of the sweep's hand-written backward against JAX's
    # through the reference's masked passes
    sizes = dict(top_k=k, routed_scale=2.5, experts_held=held)

    def mine(q):
        return jnp.sum(jnp.sin(layer.apply(
            {"params": unflatten_params(q)}, x)[0]))

    theirs = lambda q: jnp.sum(jnp.sin(ref._moe_layer(sizes, q, x)))
    with jax.default_matmul_precision("highest"):
        got, want = jax.grad(mine)(p), jax.grad(theirs)(p)
    for name, w in want.items():
        np.testing.assert_allclose(
            np.asarray(got[name]), np.asarray(w), rtol=1e-4,
            atol=1e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)


def test_sixteen_shares_of_eight_experts_add_up_to_the_uncut_layer():
    """The guide's shares test at the published router (128 experts, 6 a
    token, scaling 2.5): the routed parts of the 16 shares of 8 experts
    summed, and the shared expert — which every chip computes alike —
    counted once, equal the uncut layer's output (all 128 held by one
    layer, here and in the plain reference)."""
    d, f, n, k = 16, 8, 128, 6
    x = jnp.asarray(np.random.RandomState(8).randn(2, 16, d), jnp.float32)
    whole = ShareOfExperts(d, f, n, tuple(range(n)), k, 2.5, 12,
                           act="relu2")
    full = named_params(whole.init(jax.random.PRNGKey(9), x)["params"])
    with jax.default_matmul_precision("highest"):
        want, load = whole.apply({"params": unflatten_params(full)}, x)
        sizes = dict(top_k=k, routed_scale=2.5, experts_held=tuple(range(n)))
        np.testing.assert_allclose(
            np.asarray(ref._moe_layer(sizes, full, x)), np.asarray(want),
            rtol=1e-4, atol=1e-5)
        shared = ref._relu2(x, full["shared/up/kernel"],
                            full["shared/down/kernel"])
        routed, here = jnp.zeros_like(x), 0.0
        for first in range(0, n, 8):
            held = tuple(range(first, first + 8))
            share = dict(full, **{w: full[w][first:first + 8]
                                  for w in ("w_up", "w_down")})
            y, part = ShareOfExperts(d, f, n, held, k, 2.5, 12,
                                     act="relu2").apply(
                {"params": unflatten_params(share)}, x)
            routed = routed + (y - shared)
            here += float(part[-1])
    np.testing.assert_allclose(np.asarray(routed + shared), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert here == float(load[-1]) == 2 * 16 * k     # every assignment once


def test_an_unknown_activation_is_refused():
    x = jnp.zeros((1, 4, 8), jnp.float32)
    with pytest.raises(ValueError, match="expert activation"):
        ShareOfExperts(8, 4, 8, (0, 1), 2, act="gelu").init(
            jax.random.PRNGKey(0), x)


# Kimi's and GLM's layer (SwiGLU experts) as the parent commit gave it:
# ShareOfExperts(32, 16, 16, (2, 3, 5, 7), 4, 1.8, 16), key 7, x from
# RandomState(5) [2, 12, 32] -> (shape, sum, fourth value) a parameter,
# y[1, 5, :6], sum |y|, the load, and sum |grad| of sum(y^2) a parameter.
SWIGLU_AT_THE_PARENT = {
    "e_score_correction_bias": ((16,), 0.01318979449570179,
                                -0.006767983082681894),
    "router": ((32, 16), -3.5763020515441895, -0.20589309930801392),
    "shared/down/kernel": ((16, 32), -4.194893836975098,
                           0.31448227167129517),
    "shared/gate/kernel": ((32, 16), -1.2868480682373047,
                           0.07088235765695572),
    "shared/up/kernel": ((32, 16), 1.594178318977356, -0.009322013705968857),
    "w_down": ((4, 16, 32), 14.544020652770996, -0.30094558000564575),
    "w_gate": ((4, 32, 16), -4.5027852058410645, -0.07549077272415161),
    "w_up": ((4, 32, 16), 4.149258136749268, 0.3727435767650604),
}
SWIGLU_OUTPUT_AT_THE_PARENT = (
    [0.17994622886180878, -0.7780576348304749, 0.12708353996276855,
     -0.5307520031929016, 0.00808741245418787, -0.14950858056545258],
    379.9178771972656, [8.0, 7.0, 8.0, 4.0, 27.0])
SWIGLU_GRADS_AT_THE_PARENT = {
    "e_score_correction_bias": 0.0, "router": 1032.703857421875,
    "shared/down/kernel": 2671.2666015625,
    "shared/gate/kernel": 8025.53466796875,
    "shared/up/kernel": 7136.44677734375, "w_down": 1676.9517822265625,
    "w_gate": 3929.95361328125, "w_up": 3290.685791015625,
}


def test_the_swiglu_layer_is_the_layer_it_was():
    """The default activation gives the parameter names, shapes, seeded
    values, outputs, load and gradients that `ShareOfExperts` gave before
    it took one (pinned at the parent commit)."""
    layer = ShareOfExperts(32, 16, 16, (2, 3, 5, 7), 4, 1.8, 16)
    x = jnp.asarray(np.random.RandomState(5).randn(2, 12, 32), jnp.float32)
    params = named_params(layer.init(jax.random.PRNGKey(7), x)["params"])
    assert set(params) == set(SWIGLU_AT_THE_PARENT)
    for name, (shape, total, fourth) in SWIGLU_AT_THE_PARENT.items():
        assert params[name].shape == shape
        assert float(jnp.sum(params[name])) == pytest.approx(total, rel=1e-6)
        assert float(params[name].reshape(-1)[3]) == fourth, name
    f = lambda p: layer.apply({"params": unflatten_params(p)}, x)
    y, load = f(params)
    head, total, want_load = SWIGLU_OUTPUT_AT_THE_PARENT
    np.testing.assert_allclose(np.asarray(y[1, 5, :6]), head, rtol=1e-6)
    assert float(jnp.sum(jnp.abs(y))) == pytest.approx(total, rel=1e-6)
    assert [float(v) for v in load] == want_load
    grads = jax.grad(lambda p: jnp.sum(jnp.square(f(p)[0])))(params)
    for name, want in SWIGLU_GRADS_AT_THE_PARENT.items():
        assert float(jnp.sum(jnp.abs(grads[name]))) \
            == pytest.approx(want, rel=1e-5, abs=1e-6), name


# -- through the step ---------------------------------------------------------


def test_it_trains_through_the_step_and_logs_its_counters(toy):
    from pytorch_ps_mpi_tpu import Adam
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    model, params, batch = toy
    opt = Adam(list(params.items()), lr=1e-3,
               mesh=make_ps_mesh(devices=jax.devices()[:2]))
    opt.compile_step(make_nemotron_loss(model), has_aux=True,
                     aux=nemotron_aux(model))
    counter_log().clear()
    host = {k: np.asarray(v) for k, v in batch.items()}
    losses = [opt.step(host)[0] for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    records = counter_log().records("MPI_PS.step")
    assert len(records) == 4
    values = records[-1]["values"]
    load, carry = (np.asarray(values[n]) for n in ("moe_load", "ssd_carry"))
    assert load.shape == (4, 5) and carry.shape == (4,)
    # one row a chip: 16 tokens x 4 assignments a layer, averaged over chips
    assert np.all(load[:, -1] == load[:, :-1].sum(axis=1))
    assert np.all(load[:, -1] <= 16 * 4)
    assert np.isfinite(carry).all() and (carry > 0).all() \
        and (carry < 1).all()
    counter_log().clear()
