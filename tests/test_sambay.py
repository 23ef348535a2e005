"""SambaY with differential attention (Phi-4-mini-flash-reasoning) through
the program's model against the benchmark's plain reference
(`perfbench/models/sambay.py`: the state-space recurrence token by token,
dense band-masked softmax, the differential combination as ``(A1 - lam A2)
V``), at the configuration's rehearsal sizes in f32 with all six kinds of
layer; the differential combination through two softmaxes of one flash
call; what crosses blocks (the memory, one layer's keys and values) and how
its gradients add up over its readers; the flash call's output and row
statistics kept across the mixer's remat, and the names that keep them inert
in programs without that policy; the vocabulary's share; and the step
through `MPI_PS`."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.models import sambay as ref
from pytorch_ps_mpi_tpu.models import sambay as sambay_mod
from pytorch_ps_mpi_tpu.models.sambay import (DiffAttention, SambaYBlock,
                                              SambaYConfig, SambaYLM,
                                              dense_window_attention,
                                              lambda_init, make_sambay_loss,
                                              sambay_aux)
from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
from pytorch_ps_mpi_tpu.utils.flatten import (named_params,
                                              unflatten_params)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lm_rows(rows):
    b, s1 = rows.shape
    return {"tokens": jnp.asarray(rows[:, :-1]),
            "targets": jnp.asarray(rows[:, 1:]),
            "positions": jnp.broadcast_to(jnp.arange(s1 - 1, dtype=jnp.int32),
                                          (b, s1 - 1))}


@pytest.fixture(scope="module")
def sizes():
    with open(os.path.join(
            ROOT, "perfbench/configs/phi-4-mini-flash-reasoning.json")) as f:
        return ref.sizes(json.load(f), rehearse=True)


def config_of(s, **over):
    shape = {k: v for k, v in s.items() if k not in ("head_dim",)}
    return SambaYConfig(**{**shape, **over})


@pytest.fixture(scope="module")
def toy(sizes):
    """The rehearsal sizes (64 wide, 4 / 2 heads of 16, window 24, 6
    layers) on 2 rows of 60 tokens: longer than two windows."""
    model = SambaYLM(config_of(sizes))
    batch = lm_rows(np.random.RandomState(0).randint(
        0, sizes["vocab_size"], (2, 61)).astype(np.int32))
    params = named_params(model.init(jax.random.PRNGKey(1),
                                     batch["tokens"])["params"])
    return model, params, batch


def test_the_toy_has_every_kind_of_layer_once_and_one_embedding(toy, sizes):
    _, params, _ = toy
    assert [k for k, _ in sizes["layers"]] == [
        "mamba", "swa", "mamba_memory", "full_kv", "gmu", "cross"]
    assert [i for _, i in sizes["layers"]] == [0, 1, 16, 17, 18, 19]
    assert "block_0/mixer/A_log" in params and "block_2/mixer/D" in params
    assert params["block_1/mixer/qkv_proj/kernel"].shape == (64, 64 + 2 * 32)
    assert params["block_5/mixer/q_proj/kernel"].shape == (64, 64)
    assert "block_5/mixer/qkv_proj/kernel" not in params   # no k, v of its own
    assert params["block_4/mixer/in_proj/kernel"].shape == (64, 128)
    assert not [n for n in params if n.startswith("block_4/mixer/")
                and "proj" not in n]                   # a GMU: two products
    # the head is the embedding's transpose: once in the tree
    assert [n for n in params if "embed" in n or "head" in n] \
        == ["tok_embed/embedding"]
    assert sum(p.size for p in params.values()) == ref.total_params(sizes)


def test_loss_and_gradient_match_the_plain_reference(toy, sizes):
    """f32 against f32 at highest precision: the two differ by summation
    order only (the blocked scan against the token loop, one softmax call
    of 4 heads against the pairs' formula), so the loss agrees to 1e-5 and
    each gradient to 2e-3 of its size."""
    model, params, batch = toy
    loss = make_sambay_loss(model)
    system = lambda p: loss(p, sambay_aux(model), batch)
    reference = lambda p: ref.reference_loss(sizes, p, batch)
    with jax.default_matmul_precision("highest"):
        (got, aux), got_grads = jax.jit(
            jax.value_and_grad(system, has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(reference))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert set(got_grads) == set(want_grads) == set(params)
    for name, w in want_grads.items():
        assert float(jnp.max(jnp.abs(w))) > 0, name    # every leaf is used
        np.testing.assert_allclose(
            np.asarray(got_grads[name]), np.asarray(w), rtol=2e-3,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)
    # lam of the three attention layers, at their published depths
    lam = np.asarray(aux["counters"]["diff_lambda"])
    assert lam.shape == sambay_aux(model)["counters"]["diff_lambda"].shape \
        == (3,)
    np.testing.assert_allclose(lam, [lambda_init(i) for i in (1, 17, 19)],
                               atol=0.1)
    assert lambda_init(1) == pytest.approx(0.8 - 0.6 * np.exp(-0.3))


@pytest.mark.parametrize("window", [None, 70, 128])
def test_two_flash_softmaxes_subtracted_are_the_formula(window):
    """`DiffAttention` makes one flash call over 4 (q, k) heads of 16 with
    a 32-wide v (under the interpreter here), the kv pair repeated over its
    query pairs, and subtracts outside the kernel; the reference computes
    ``(A1 - lam A2) V`` from two dense softmaxes a pair.  f32: 2e-4."""
    s = dict(n_heads=8, n_kv_heads=4, head_dim=16, eps=1e-5)
    cfg = SambaYConfig(vocab_size=8, d_model=128, d_ff=8, n_heads=8,
                       n_kv_heads=4, window=window or 1, d_inner=8,
                       layers=(("swa" if window else "full_kv", 3),))
    attn = lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=0.25, window=window, impl="interpret")
    layer = DiffAttention(cfg, 3, attn, "swa")
    u = jnp.asarray(np.random.RandomState(0).randn(1, 200, 128), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), u)["params"]
    # lam away from its start, so that the subtraction is not a special one
    params = dict(params, lambda_q1=params["lambda_q1"] + 0.3)
    flat = named_params(params)

    def both(flat):
        got, (k, v), lam = layer.apply({"params": unflatten_params(flat)}, u)
        want, (k2, v2) = ref._attention_layer(s, flat, u, 3, window)
        return got, want, k, k2, v, v2, lam

    with jax.default_matmul_precision("highest"):
        got, want, k, k2, v, v2, lam = both(flat)
        g_got = jax.grad(lambda f: jnp.sum(jnp.sin(both(f)[0])))(flat)
        g_want = jax.grad(lambda f: jnp.sum(jnp.sin(both(f)[1])))(flat)
    assert k.shape == v.shape == (1, 200, 4, 16)
    np.testing.assert_array_equal(np.asarray(k), np.asarray(k2))
    assert abs(float(lam) - lambda_init(3)) > 0.01
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    for name, w in g_want.items():
        np.testing.assert_allclose(
            np.asarray(g_got[name]), np.asarray(w), rtol=2e-3,
            atol=2e-4 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)


def test_the_default_attention_is_the_band_of_the_reference():
    """`dense_window_attention`, the model's attention off the chip, keeps
    keys ``0 <= i - j < window``: a key 24 back is seen under a window of
    25 and not under one of 24."""
    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(1, 40, 2, 8), jnp.float32)
               for _ in range(3))
    base = dense_window_attention(q, k, v, window=24)
    moved = dense_window_attention(q, k.at[:, 3].add(1.0), v, window=24)
    rows = np.abs(np.asarray(moved - base)).max(axis=(0, 2, 3)) > 0
    assert rows.nonzero()[0].tolist() == list(range(3, 27))   # 24 rows
    np.testing.assert_allclose(
        np.asarray(dense_window_attention(q, k, v, window=40)),
        np.asarray(dense_window_attention(q, k, v)), rtol=1e-6)


# -- what crosses blocks ------------------------------------------------------

READERS = (("mamba_memory", 16), ("full_kv", 17), ("gmu", 18), ("cross", 19),
           ("gmu", 20), ("cross", 21))


@pytest.fixture(scope="module")
def two_readers():
    """A cross-decoder with two readers of each shared tensor, run block by
    block through `SambaYBlock.apply` so that a reader's use of the memory
    or of the keys and values can be detached (same values, no gradient)."""
    cfg = SambaYConfig(vocab_size=32, d_model=32, d_ff=48, n_heads=4,
                       n_kv_heads=2, window=8, layers=READERS, d_inner=64,
                       d_state=4, dt_rank=4)
    model = SambaYLM(cfg)
    batch = lm_rows(np.random.RandomState(2).randint(
        0, 32, (2, 25)).astype(np.int32))
    params = model.init(jax.random.PRNGKey(3), batch["tokens"])["params"]
    loss = make_sambay_loss(model)

    def by_hand(params, detach=()):
        x = params["tok_embed"]["embedding"][batch["tokens"]]
        shared = {}
        for i, (kind, index) in enumerate(READERS):
            given = shared.get({"gmu": "mamba_memory",
                                "cross": "full_kv"}.get(kind))
            if i in detach:
                given = jax.lax.stop_gradient(given)
            x, keep, _ = SambaYBlock(
                cfg, kind, index, dense_window_attention).apply(
                    {"params": params[f"block_{i}"]}, x, given)
            shared[kind] = keep
        mean = jnp.mean(x, axis=-1, keepdims=True)
        x = (x - mean) * jax.lax.rsqrt(jnp.var(x, axis=-1, keepdims=True)
                                       + cfg.eps)
        x = x * params["final_norm"]["scale"] + params["final_norm"]["bias"]
        logp = jax.nn.log_softmax(x @ params["tok_embed"]["embedding"].T)
        return -jnp.mean(jnp.take_along_axis(
            logp, batch["targets"][..., None], axis=-1))

    whole = lambda p: loss(named_params(p), sambay_aux(model), batch)[0]
    return params, by_hand, whole


def test_the_blocks_by_hand_are_the_model(two_readers):
    params, by_hand, whole = two_readers
    assert float(by_hand(params)) == pytest.approx(float(whole(params)),
                                                   rel=1e-6)


@pytest.mark.parametrize("source,readers,leaves", [
    # layer 17's W_k and W_v: the columns after the 4 query heads
    (1, (3, 5), ("qkv_proj/kernel", "qkv_proj/bias")),
    # the memory layer's scan: everything before its output projection
    (0, (2, 4), ("in_proj/kernel", "x_proj/kernel", "dt_proj/kernel",
                 "A_log", "D", "conv", "dt_bias")),
])
def test_a_shared_tensors_gradient_is_the_sum_over_its_readers(
        two_readers, source, readers, leaves):
    """Drop one reader (detach its copy of the tensor) and the gradient of
    the parameters that made the tensor falls by that reader's part: the
    same part whether or not the other reader is there, not zero, and with
    every reader the gradient is the model's own.  The half-blocks are
    rematerialised each on its own, so these sums cross `nn.remat`."""
    params, by_hand, whole = two_readers
    mixer = lambda g: named_params(g[f"block_{source}"]["mixer"])
    grad = lambda detach: mixer(jax.grad(by_hand)(params, detach))
    everyone, model = grad(()), mixer(jax.grad(whole)(params))
    a, b = readers
    without_a, without_b, nobody = grad((a,)), grad((b,)), grad((a, b))
    for name in leaves:
        scale = float(jnp.max(jnp.abs(everyone[name])))
        close = functools.partial(np.testing.assert_allclose, rtol=1e-4,
                                  atol=1e-5 * scale, err_msg=name)
        close(np.asarray(everyone[name]), np.asarray(model[name]))
        part_a = everyone[name] - without_a[name]
        part_b = everyone[name] - without_b[name]
        if name.startswith("qkv_proj"):     # q's 4 x 8 columns have no reader
            assert float(jnp.max(jnp.abs(part_a[..., :32]))) <= 1e-6 * scale
            assert float(jnp.max(jnp.abs(part_a[..., 32:]))) > 1e-3 * scale
        for part in (part_a, part_b):
            assert float(jnp.max(jnp.abs(part))) > 1e-3 * scale, name
        close(np.asarray(without_b[name] - nobody[name]), np.asarray(part_a))
        close(np.asarray(nobody[name] + part_a + part_b),
              np.asarray(everyone[name]))


def test_the_memory_is_the_scan_output_before_the_gate(two_readers):
    """What the memory layer hands on has d_inner columns, does not depend
    on its own gate ``z`` (the second half of ``W_in``'s columns) and does
    on the ``D`` skip."""
    params, _, _ = two_readers
    cfg = SambaYConfig(vocab_size=32, d_model=32, d_ff=48, n_heads=4,
                       n_kv_heads=2, window=8, layers=READERS, d_inner=64,
                       d_state=4, dt_rank=4)
    x = jnp.asarray(np.random.RandomState(4).randn(1, 12, 32), jnp.float32)
    block = SambaYBlock(cfg, "mamba_memory", 16, dense_window_attention)
    p = params["block_0"]
    memory = lambda p: block.apply({"params": p}, x)[1]
    assert memory(p).shape == (1, 12, 64)
    w_in = p["mixer"]["in_proj"]["kernel"]
    gate_moved = {**p, "mixer": {**p["mixer"], "in_proj": {
        "kernel": w_in.at[:, 64:].add(1.0)}}}
    np.testing.assert_array_equal(np.asarray(memory(gate_moved)),
                                  np.asarray(memory(p)))
    skip_moved = {**p, "mixer": {**p["mixer"], "D": p["mixer"]["D"] + 1.0}}
    assert float(jnp.max(jnp.abs(memory(skip_moved) - memory(p)))) > 0.1


# -- the vocabulary's share ---------------------------------------------------


def test_eight_slices_of_the_vocabulary_tile_the_whole(sizes):
    """A chip that holds rows ``[k V/8, (k+1) V/8)`` of the tied embedding
    and sees ids of that slice computes the same hidden states as the whole
    model, and its logits are that slice of the whole model's logits; the
    eight slices side by side are the whole embedding."""
    whole = SambaYLM(config_of(sizes, vocab_size=64))
    share = SambaYLM(config_of(sizes, vocab_size=8))
    rng = np.random.RandomState(5)
    params = whole.init(jax.random.PRNGKey(6),
                        jnp.zeros((1, 20), jnp.int32))["params"]
    table = params["tok_embed"]["embedding"]
    slices = [table[8 * k:8 * (k + 1)] for k in range(8)]
    np.testing.assert_array_equal(np.asarray(jnp.concatenate(slices)),
                                  np.asarray(table))
    for k in (0, 5):
        local = jnp.asarray(rng.randint(0, 8, (1, 20)), jnp.int32)
        want, _ = whole.apply({"params": params}, local + 8 * k)
        got, _ = share.apply({"params": {
            **params, "tok_embed": {"embedding": slices[k]}}}, local)
        assert got.shape == (1, 20, 8)
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(want[..., 8 * k:8 * (k + 1)]),
                                   rtol=1e-5, atol=1e-5)


# -- the flash call's output kept across the mixer's remat --------------------


def pallas_calls(closed) -> dict:
    """Kernel name -> how many `pallas_call`s of it the jaxpr makes, every
    sub-jaxpr (jit, remat, custom rules) counted once for each equation
    that calls it."""
    calls: dict = {}

    def subs(v):
        if hasattr(v, "eqns"):
            yield v
        elif hasattr(getattr(v, "jaxpr", None), "eqns"):
            yield v.jaxpr
        elif isinstance(v, (tuple, list)):
            for x in v:
                yield from subs(x)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                name = dict(eqn.params.get("metadata") or {}).get("kernel")
                calls[name] = calls.get(name, 0) + 1
            for v in eqn.params.values():
                for sub in subs(v):
                    walk(sub)

    walk(closed.jaxpr)
    return calls


@pytest.fixture(scope="module")
def flash_toy(toy):
    """The toy with the flash kernels (under the interpreter) for ``attn``,
    and its loss of the parameters."""
    model, params, batch = toy
    model = SambaYLM(model.cfg, attn=functools.partial(
        flash_attention, causal=True, impl="interpret"))
    loss = make_sambay_loss(model)
    return model, params, lambda p: loss(p, sambay_aux(model), batch)[0]


@pytest.mark.parametrize("kept", [True, False])
def test_the_backward_runs_flash_fwd_once_an_attention_layer(
        flash_toy, monkeypatch, kept):
    """Kept: one `flash_fwd` a layer, the forward's; under a bare `nn.remat`
    (the policy taken away) the rematerialised forward runs it again.  The
    backward kernel runs once a layer either way."""
    model, params, loss = flash_toy
    if not kept:
        monkeypatch.setattr(sambay_mod, "SAVE_FLASH", None)
    calls = pallas_calls(jax.make_jaxpr(jax.grad(loss))(params))
    n = model.cfg.n_attention
    assert n == 3
    assert calls == {"flash_fwd": n if kept else 2 * n, "flash_bwd_dkdv": n}


def test_kept_flash_outputs_give_the_bare_remats_loss_and_gradients(
        flash_toy, monkeypatch):
    """Bit for bit: the output kept is the one the rematerialised forward
    would have made again from the same q, k and v."""
    _, params, loss = flash_toy
    kept = jax.jit(jax.value_and_grad(loss))(params)
    monkeypatch.setattr(sambay_mod, "SAVE_FLASH", None)
    bare = jax.jit(jax.value_and_grad(loss))(params)
    assert float(kept[0]) == float(bare[0])
    assert set(kept[1]) == set(bare[1]) == set(params)
    for name in params:
        np.testing.assert_array_equal(np.asarray(kept[1][name]),
                                      np.asarray(bare[1][name]), err_msg=name)


def _glm_block_step():
    """One GLM-shaped `DecoderBlock` (rotary MLA with a low-rank q, a dense
    MLP) through a gradient step."""
    from pytorch_ps_mpi_tpu.models.glm_moe import GlmMoeConfig
    from pytorch_ps_mpi_tpu.models.kimi_linear import DecoderBlock
    cfg = GlmMoeConfig(
        vocab_size=61, d_model=32, n_layers=1, first_k_dense=1, d_ff=48,
        d_expert=16, n_experts=16, experts_held=(2, 3), top_k=2, n_shared=1,
        routed_scale=1.8, n_heads=2, q_lora_rank=12, kv_lora_rank=16,
        qk_nope_dim=12, qk_rope_dim=4, v_dim=16, rope_theta=1e6, n_mtp=0)
    block = DecoderBlock(cfg, functools.partial(
        flash_attention, causal=True, scale=16 ** -0.5, impl="interpret"),
        linear=False, dense=True)
    x = jnp.asarray(np.random.RandomState(7).randn(2, 40, 32), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), (2, 40))
    params = block.init(jax.random.PRNGKey(8), x, pos)
    return lambda p: jnp.sum(jnp.sin(block.apply(p, x, pos)[0])), params


def _gpt2_step():
    """A two-layer GPT-2-style `TransformerLM` toy through a gradient step."""
    from pytorch_ps_mpi_tpu.models.transformer import TransformerLM
    model = TransformerLM(vocab_size=61, d_model=64, n_heads=2, n_layers=2,
                          d_ff=128, max_len=64, attn=functools.partial(
                              flash_attention, causal=True,
                              impl="interpret"))
    rows = jnp.asarray(np.random.RandomState(9).randint(0, 61, (2, 33)),
                       jnp.int32)
    tokens, targets = rows[:, :-1], rows[:, 1:]
    pos = jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (2, 32))
    params = model.init(jax.random.PRNGKey(10), tokens, pos)

    def loss(p):
        logp = jax.nn.log_softmax(model.apply(p, tokens, pos), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
    return loss, params


@pytest.mark.parametrize("make", [_glm_block_step, _gpt2_step],
                         ids=["glm_decoder_block", "gpt2_toy"])
def test_the_flash_names_are_inert_without_a_policy(monkeypatch, make):
    """A program whose remat has no policy, or that has no remat, lowers
    to the same StableHLO with the names as without them: so its compiled
    program, and its entry in the compilation cache, are the ones it had."""
    from pytorch_ps_mpi_tpu.ops import flash_attention as fa

    def lowered():
        loss, params = make()

        def step(p):
            value, grads = jax.value_and_grad(loss)(p)
            return value, jax.tree.map(lambda w, g: w - 0.1 * g, p, grads)
        jaxpr = str(jax.make_jaxpr(step)(params))
        return jaxpr, jax.jit(step).lower(params).as_text()

    named_jaxpr, named = lowered()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    plain_jaxpr, plain = lowered()
    assert "flash_out" in named_jaxpr and "flash_lse" in named_jaxpr
    assert "flash_out" not in plain_jaxpr
    assert named == plain


# -- through the step ---------------------------------------------------------


def test_it_trains_through_the_step_and_logs_lambda(toy):
    from pytorch_ps_mpi_tpu import Adam
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    model, params, batch = toy
    opt = Adam(list(params.items()), lr=1e-3,
               mesh=make_ps_mesh(devices=jax.devices()[:2]))
    opt.compile_step(make_sambay_loss(model), has_aux=True,
                     aux=sambay_aux(model))
    counter_log().clear()
    host = {k: np.asarray(v) for k, v in batch.items()}
    losses = [opt.step(host)[0] for _ in range(4)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    records = counter_log().records("MPI_PS.step")
    assert len(records) == 4
    lam = np.asarray(records[-1]["values"]["diff_lambda"])
    assert lam.shape == (3,) and 0.2 < lam[0] < 0.5 < lam[1] < 1.0
    counter_log().clear()
