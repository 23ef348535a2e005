"""EvaByte through the program's model against the benchmark's plain
reference (`perfbench/models/evabyte.py`: one masked softmax over bytes and
summaries side by side) at the configuration's rehearsal sizes; the two
forms of `ops.eva_attention` (the flash kernels under the Pallas interpreter
with the join on the row statistics, and the plain one) against each other
and against a softmax over explicit masks; the summaries against a loop over
chunks; the row statistics as a differentiable second output of
`flash_attention`, and the call without them traced as before; the share by
heads; the eight-head loss; and the step through `MPI_PS`."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.models import evabyte as ref
from pytorch_ps_mpi_tpu.models.evabyte import (EvaAttention, EvaByteBlock,
                                               EvaByteConfig, EvaByteLM,
                                               evabyte_aux, make_evabyte_loss,
                                               multi_byte_losses)
from pytorch_ps_mpi_tpu.ops import flash_attention as _fa
from pytorch_ps_mpi_tpu.ops.eva_attention import (chunk_summaries,
                                                  eva_attention)
from pytorch_ps_mpi_tpu.utils.flatten import named_params

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
flash_attention = functools.partial(_fa.flash_attention, impl="interpret")


def lm_rows(rows):
    b, s1 = rows.shape
    return {"tokens": jnp.asarray(rows[:, :-1]),
            "targets": jnp.asarray(rows[:, 1:]),
            "positions": jnp.broadcast_to(jnp.arange(s1 - 1, dtype=jnp.int32),
                                          (b, s1 - 1))}


@pytest.fixture(scope="module")
def sizes():
    with open(os.path.join(ROOT, "perfbench/configs/evabyte.json")) as f:
        return ref.sizes(json.load(f), rehearse=True)


def config_of(s, **over):
    return EvaByteConfig(**{**s, **over})


@pytest.fixture(scope="module")
def toy(sizes):
    """The rehearsal sizes (64 wide, 2 held heads of 16, window 32, chunk 8,
    8 heads over 320 ids, 2 layers) on 2 rows of 96 bytes: three windows of
    four chunks."""
    model = EvaByteLM(config_of(sizes))
    batch = lm_rows(np.random.RandomState(0).randint(
        0, sizes["vocab_size"], (2, 97)).astype(np.int32))
    params = named_params(model.init(jax.random.PRNGKey(1),
                                     batch["tokens"])["params"])
    return model, params, batch


def _qkv(seed, b=2, s=96, h=2, d=16):
    rng = np.random.RandomState(seed)
    mk = lambda *shape: jnp.asarray(rng.randn(*shape).astype(np.float32))
    return (mk(b, s, h, d), mk(b, s, h, d), mk(b, s, h, d),
            0.25 * mk(h, d), 0.25 * mk(h, d))


# -- the row statistics of the flash call -------------------------------------


@pytest.mark.parametrize("s,window", [(96, None), (200, None), (200, 70)])
def test_the_row_statistics_are_the_logsumexp_and_differentiable(s, window):
    """`flash_attention(return_lse=True)` gives each row's logsumexp of its
    scaled, masked scores beside the output, and a loss that reads both has
    the gradients of the dense form: the cotangent of the statistics goes
    through the kernels' ``delta``."""
    q, k, v, _, _ = _qkv(3, s=s)

    def dense(q, k, v):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.25
        age = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        seen = age >= 0 if window is None else (age >= 0) & (age < window)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v), \
            jax.nn.logsumexp(scores, axis=-1)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.25, window=window, return_lse=True)
    loss = lambda fn: lambda q, k, v: (
        lambda o, lse: jnp.sum(jnp.sin(o)) + jnp.sum(jnp.cos(lse)))(
            *fn(q, k, v))
    (o, lse), (o2, lse2) = flash(q, k, v), dense(q, k, v)
    assert lse.shape == (2, 2, s) and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), np.asarray(o2), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse2), atol=2e-5)
    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   err_msg=name)


def test_without_the_row_statistics_the_program_is_the_one_it_was():
    """``return_lse=False`` (the default) traces what a call without the
    argument traces, to the letter of the jaxpr: the cells that never ask
    for the statistics keep their programs (as `tests/test_flash_attention.py`
    pins ``window=None``)."""
    q, k, v, _, _ = _qkv(12, b=1, s=300, h=2, d=64)
    text = lambda **kw: str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, **kw)),
        argnums=(0, 1, 2)))(q, k, v))
    assert text() == text(return_lse=False) == text(window=None)
    with_lse = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, return_lse=True)[0]),
        argnums=(0, 1, 2)))(q, k, v))
    assert with_lse != text()
    # the statistics' cotangent is one subtraction on delta, outside the
    # kernels: the same three kernels, called as often
    for kernel in _fa.KERNELS:
        assert with_lse.count(kernel) == text().count(kernel)


def test_the_cells_window_is_one_tile_a_head():
    """`[64, 2048, 128 / 128]` (4 windows x 16 heads of one 8,192-byte row):
    a head-window is one grid tile in both calls, every bound static, and
    the one backward call writes dq."""
    plan = _fa.tile_plan(2048, 128, 128, True)
    assert list(plan.tiles) == ["flash_fwd", "flash_bwd_dkdv"]
    assert plan.tiles["flash_fwd"] == (2048, 2048, 256, 256)
    assert plan.tiles["flash_bwd_dkdv"] == (2048, 2048, 512, 512)
    assert plan.counts["flash_fwd"] == (36, 8, 28)
    assert plan.counts["flash_bwd_dkdv"] == (10, 4, 6)


# -- the summaries and the two-set softmax ------------------------------------


def test_the_summaries_are_a_loop_over_chunks():
    _, k, v, phi, mu = _qkv(4, s=48)
    k_sum, v_sum = chunk_summaries(k, v, phi, mu, chunk=8, scale=0.25)
    assert k_sum.shape == v_sum.shape == (2, 6, 2, 16)
    k, v, phi, mu = (np.asarray(x, np.float64) for x in (k, v, phi, mu))
    for b in range(2):
        for c in range(6):
            for h in range(2):
                keys = k[b, 8 * c:8 * c + 8, h]
                score = 0.25 * keys @ phi[h]
                pi = np.exp(score - score.max())
                pi /= pi.sum()
                np.testing.assert_allclose(k_sum[b, c, h], pi @ keys + mu[h],
                                           atol=1e-5)
                np.testing.assert_allclose(
                    v_sum[b, c, h], pi @ v[b, 8 * c:8 * c + 8, h], atol=1e-5)
    with pytest.raises(ValueError, match="whole chunks"):
        chunk_summaries(k[:, :44], v[:, :44], phi, mu, chunk=8, scale=0.25)


def by_explicit_masks(q, k, v, phi, mu, window, chunk):
    """A softmax a query over the sets the model's equations name, byte by
    byte and chunk by chunk in numpy: ``(o, the summaries' share of z)``."""
    q, k, v, phi, mu = (np.asarray(x, np.float64) for x in (q, k, v, phi, mu))
    b, s, h, d = q.shape
    scale = d ** -0.5
    out, mass = np.zeros((b, s, h, d)), np.zeros((b, s, h))
    for bi in range(b):
        for hi in range(h):
            k_sum, v_sum = [], []
            for c in range(s // chunk):
                keys = k[bi, c * chunk:(c + 1) * chunk, hi]
                pi = np.exp(scale * keys @ phi[hi])
                pi /= pi.sum()
                k_sum.append(pi @ keys + mu[hi])
                v_sum.append(pi @ v[bi, c * chunk:(c + 1) * chunk, hi])
            for t in range(s):
                local = [j for j in range(s)
                         if j // window == t // window and j <= t]
                remote = [c for c in range(s // chunk)
                          if (c + 1) * chunk <= (t // window) * window]
                e_l = np.exp(scale * k[bi, local, hi] @ q[bi, t, hi])
                e_r = np.exp(scale * np.asarray(
                    [k_sum[c] for c in remote]).reshape(-1, d) @ q[bi, t, hi])
                z = e_l.sum() + e_r.sum()
                out[bi, t, hi] = (e_l @ v[bi, local, hi] + e_r @ np.asarray(
                    [v_sum[c] for c in remote]).reshape(-1, d)) / z
                mass[bi, t, hi] = e_r.sum() / z
    return out, mass


@pytest.mark.parametrize("s,window,chunk", [(96, 32, 8), (128, 32, 16)])
def test_both_forms_are_the_softmax_over_the_two_sets(s, window, chunk):
    """Three and four windows.  The kernels' form (under the interpreter)
    and the plain form give the output and the remote mass of the explicit
    masks, and each other's gradients, in f32."""
    q, k, v, phi, mu = _qkv(5, s=s)
    want, mass = by_explicit_masks(q, k, v, phi, mu, window, chunk)
    seen = np.arange(s) >= window           # queries with summaries to see
    assert mass[:, ~seen].max() == 0 and mass[:, seen].min() > 0
    grads = {}
    for impl in ("dense", "interpret"):
        fn = functools.partial(eva_attention, window=window, chunk=chunk,
                               impl=impl)
        out, got_mass = fn(q, k, v, phi, mu)
        np.testing.assert_allclose(np.asarray(out), want, atol=3e-5,
                                   err_msg=impl)
        assert float(got_mass) == pytest.approx(mass[:, seen].mean(),
                                                rel=1e-4)
        grads[impl] = jax.grad(
            lambda *a: (lambda o, m: jnp.sum(jnp.sin(o)) + m)(*fn(*a)),
            argnums=(0, 1, 2, 3, 4))(q, k, v, phi, mu)
    for g, w, name in zip(grads["interpret"], grads["dense"],
                          ("dq", "dk", "dv", "dphi", "dmu")):
        assert float(jnp.max(jnp.abs(w))) > 1e-3, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5,
                                   err_msg=name)


def test_a_ragged_last_window_is_refused_by_the_kernels_form_only():
    """80 bytes are two windows of 32 and half a third.  The plain form
    takes any whole number of chunks (the short window's queries see only
    what exists); the kernels' form refuses, it does not pad."""
    q, k, v, phi, mu = _qkv(6, s=80)
    want, _ = by_explicit_masks(q, k, v, phi, mu, 32, 8)
    out, _ = eva_attention(q, k, v, phi, mu, window=32, chunk=8)
    np.testing.assert_allclose(np.asarray(out), want, atol=3e-5)
    with pytest.raises(ValueError, match="ragged last window"):
        eva_attention(q, k, v, phi, mu, window=32, chunk=8, impl="interpret")
    with pytest.raises(ValueError, match="whole chunks"):
        eva_attention(q, k, v, phi, mu, window=36, chunk=8)
    # one window and less: no summaries to see, the mass is 0
    out, mass = eva_attention(q[:, :8], k[:, :8], v[:, :8], phi, mu,
                              window=32, chunk=8)
    assert out.shape == (2, 8, 2, 16) and float(mass) == 0.0


# -- the model against the plain reference ------------------------------------


def test_the_toy_keeps_the_structure(toy, sizes):
    _, params, _ = toy
    assert (sizes["window"] // sizes["chunk"], sizes["n_pred_heads"],
            sizes["vocab_size"]) == (4, 8, 320)
    assert 96 // sizes["window"] == 3
    # heads held (2 x 16) are not the hidden size (64)
    assert params["block_0/attn/q_proj/kernel"].shape == (64, 32)
    assert params["block_0/attn/o_proj/kernel"].shape == (32, 64)
    assert params["block_1/attn/phi"].shape \
        == params["block_1/attn/mu"].shape == (2, 16)
    assert params["lm_head/kernel"].shape == (64, 8 * 320)
    assert not [n for n in params if "bias" in n]
    assert float(jnp.max(jnp.abs(params["final_norm/scale"]))) == 0   # 1 + g
    assert sum(p.size for p in params.values()) == ref.total_params(sizes)


@pytest.mark.parametrize("impl", ["dense", "interpret"])
def test_loss_and_gradient_match_the_plain_reference(toy, sizes, impl):
    """f32 against f32 at highest precision: the program joins two partial
    softmaxes where the reference makes one, so the loss agrees to 1e-5 and
    each gradient to 2e-3 of its size; with the kernels' form too."""
    _, params, batch = toy
    model = EvaByteLM(config_of(sizes), attn=functools.partial(
        eva_attention, impl=impl))
    loss = make_evabyte_loss(model)
    system = lambda p: loss(p, evabyte_aux(model), batch)
    with jax.default_matmul_precision("highest"):
        (got, aux), got_grads = jax.jit(
            jax.value_and_grad(system, has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.reference_loss(sizes, p, batch)))(params)
        per_head = ref.reference_losses(sizes, params, batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert set(got_grads) == set(want_grads) == set(params)
    for name, w in want_grads.items():
        assert float(jnp.max(jnp.abs(w))) > 0, name    # every leaf is used
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(w), rtol=2e-3,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)
    counters = aux["counters"]
    np.testing.assert_allclose(np.asarray(counters["mbp_loss"]),
                               np.asarray(per_head), rtol=1e-5)
    mass = np.asarray(counters["eva_remote_mass"])
    assert mass.shape == (2,) and (0.05 < mass).all() and (mass < 0.6).all()


def test_the_eight_heads_are_eight_plain_cross_entropies():
    rng = np.random.RandomState(7)
    logits = jnp.asarray(rng.randn(2, 20, 8, 11), jnp.float32)
    targets = jnp.asarray(rng.randint(0, 11, (2, 20)), jnp.int32)
    got = np.asarray(multi_byte_losses(logits, targets))
    logp = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    for i in range(8):
        # head i at position t against byte t + 1 + i = targets[t + i]
        picked = [logp[b, t, i, targets[b, t + i]]
                  for b in range(2) for t in range(20 - i)]
        assert got[i] == pytest.approx(-np.mean(picked), rel=1e-5)
    moved = np.asarray(multi_byte_losses(
        logits, targets.at[:, -1].set((targets[:, -1] + 1) % 11)))
    assert (moved != got).all()        # every head has the last byte once
    moved = np.asarray(multi_byte_losses(
        logits, targets.at[:, 0].set((targets[:, 0] + 1) % 11)))
    assert moved[0] != got[0] and (moved[1:] == got[1:]).all()


# -- the share by heads -------------------------------------------------------


def test_two_shares_by_heads_add_up_to_the_uncut_layer():
    """One layer of 4 heads; the chips that hold heads 0-1 and 2-3 each get
    their columns of W_q, W_k, W_v, their rows of W_o and their rows of phi
    and mu.  The W_o partial sums added are the uncut layer's attention
    output; the feed-forward part (and the norms) every chip computes alike
    and is counted once."""
    whole = EvaByteConfig(vocab_size=8, d_model=64, d_ff=96, n_layers=1,
                          n_heads=4, head_dim=16, window=16, chunk=4)
    half = EvaAttention(dataclasses.replace(whole, n_heads=2), eva_attention)
    x = jnp.asarray(np.random.RandomState(8).randn(2, 48, 64), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(48), (2, 48))

    def share(p, i):
        """Heads 2 i and 2 i + 1 of the attention parameters ``p``."""
        cols = slice(32 * i, 32 * (i + 1))
        return {**{n: {"kernel": p[n]["kernel"][:, cols]}
                   for n in ("q_proj", "k_proj", "v_proj")},
                "o_proj": {"kernel": p["o_proj"]["kernel"][cols]},
                "phi": p["phi"][2 * i:2 * i + 2],
                "mu": p["mu"][2 * i:2 * i + 2]}

    def by_shares(p, u):
        return [half.apply({"params": share(p, i)}, u, positions)[0]
                for i in range(2)]

    layer = EvaAttention(whole, eva_attention)
    p = layer.init(jax.random.PRNGKey(9), x, positions)["params"]
    want, _ = layer.apply({"params": p}, x, positions)
    parts = by_shares(p, x)
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1]),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(parts[0] - want))) > 0.1   # not a no-op
    # the block: attention's partial sums added, the MLP once
    block = EvaByteBlock(whole, eva_attention)
    bp = block.init(jax.random.PRNGKey(10), x, positions)["params"]
    out, _ = block.apply({"params": bp}, x, positions)
    norm = lambda g, y: y * jax.lax.rsqrt(
        jnp.mean(jnp.square(y), axis=-1, keepdims=True) + 1e-5) * (1 + g)
    h = x + sum(by_shares(bp["attn"], norm(bp["attn_norm"]["scale"], x)))
    m = bp["mlp"]
    y = norm(bp["mlp_norm"]["scale"], h)
    mlp = (jax.nn.silu(y @ m["gate"]["kernel"]) * (y @ m["up"]["kernel"])) \
        @ m["down"]["kernel"]
    np.testing.assert_allclose(np.asarray(h + mlp), np.asarray(out),
                               rtol=1e-4, atol=1e-4)


# -- through the step ---------------------------------------------------------


def test_it_trains_through_the_step_and_logs_its_counters(toy):
    from pytorch_ps_mpi_tpu import Adam
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    model, params, batch = toy
    opt = Adam(list(params.items()), lr=1e-3,
               mesh=make_ps_mesh(devices=jax.devices()[:2]))
    opt.compile_step(make_evabyte_loss(model), has_aux=True,
                     aux=evabyte_aux(model))
    counter_log().clear()
    host = {k: np.asarray(v) for k, v in batch.items()}
    losses = [opt.step(host)[0] for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    records = counter_log().records("MPI_PS.step")
    assert len(records) == 4
    values = records[-1]["values"]
    mass, heads = (np.asarray(values[n])
                   for n in ("eva_remote_mass", "mbp_loss"))
    assert mass.shape == (2,) and heads.shape == (8,)
    assert np.isfinite(mass).all() and (mass > 0).all() and (mass < 1).all()
    assert np.isfinite(heads).all() and (heads > 0).all()
    assert float(np.mean(heads)) == pytest.approx(float(losses[-1]), rel=1e-5)
    counter_log().clear()
