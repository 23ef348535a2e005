"""Flash attention vs dense attention: forward and gradient equality.

The dense softmax attention is the oracle (same strategy as the ring
tests): the Pallas streaming-softmax kernel (run under the interpreter on
the CPU test mesh — same kernel logic, just emulated) and its blockwise
custom-vjp backward must match to numerical tolerance across causal
masking, non-multiple-of-block lengths, head-dim padding, and scale
overrides — and must plug into the transformer as the attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import flash_attention as _fa
from pytorch_ps_mpi_tpu.ops.flash_attention import BLOCK
from pytorch_ps_mpi_tpu.parallel.ring_attention import dense_attention

# The default lowers the Mosaic kernel; on the CPU mesh the interpreter is
# asked for by name.
flash_attention = functools.partial(_fa.flash_attention, impl="interpret")


def _qkv(seed, b=2, s=96, h=2, d=8):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [64, 96, BLOCK, BLOCK + 40, 2 * BLOCK])
def test_flash_matches_dense(causal, s):
    q, k, v = _qkv(0, s=s)
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_flash_scale_and_headdim_padding():
    # d=20 exercises the lane-padding path; scale override must thread.
    q, k, v = _qkv(1, b=1, s=40, h=3, d=20)
    want = dense_attention(q, k, v, causal=True, scale=0.2)
    got = flash_attention(q, k, v, causal=True, scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(2, b=1, s=BLOCK + 24, h=2, d=16)
    tgt = jnp.asarray(np.random.RandomState(3)
                      .randn(*q.shape).astype(np.float32))

    def loss(attn):
        def f(q, k, v):
            return jnp.sum((attn(q, k, v, causal=causal) - tgt) ** 2)
        return f

    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multi_tile_grid(causal):
    """s=640 pads past BWD_BLOCK (512) but is not a multiple of it: the
    backward runs a 2x2 tile grid, exercising scratch accumulation across
    grid steps, the init/finish gating, the causal tile skip, AND the
    edge-tile re-pad guard (off-tile rows would otherwise read out of
    bounds on hardware)."""
    from pytorch_ps_mpi_tpu.ops.flash_attention import BWD_BLOCK_Q

    s = BWD_BLOCK_Q + BLOCK          # 640
    q, k, v = _qkv(6, b=1, s=s, h=1, d=16)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(attn(q, k, v, causal=causal)))

    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_under_jit_and_bf16_io():
    q, k, v = _qkv(4, s=64, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(functools.partial(flash_attention, causal=True))(qb, kb, vb)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_transformer_trains_with_flash_attention():
    """flash_attention plugs into TransformerLM as the attention and the
    model trains; forward parity with the dense-attn model at init.
    (The 8-virtual-device environment comes from conftest; SGD(mesh=None)
    builds the default all-device mesh.)"""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)

    dense = TransformerLM(vocab_size=17, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_len=64)
    flash = dense.copy(
        attn=functools.partial(flash_attention, causal=True))
    params = build_lm(dense, seq_len=16)
    toks = np.random.RandomState(5).randint(0, 17, size=(8, 17))

    ld = make_lm_loss(dense)(dict(params), lm_batch(toks))
    lf = make_lm_loss(flash)(dict(params), lm_batch(toks))
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)

    opt = SGD(list(params.items()), lr=0.1, mesh=None)
    # mesh=None -> all devices; use default mesh for a quick train check.
    opt.compile_step(make_lm_loss(flash))
    losses = [opt.step(lm_batch(toks))[0] for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# -- a v width other than the q / k width (latent attention: 192 / 128) ------


def _qkv_widths(seed, b=2, s=200, h=2, d=24, dv=16, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda w: jnp.asarray(rng.randn(b, s, h, w), dtype)
    return mk(d), mk(d), mk(dv)


@pytest.mark.parametrize("d,dv", [(24, 16), (192, 128), (16, 24), (130, 8)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_another_v_width_matches_dense(d, dv, causal):
    q, k, v = _qkv_widths(0, s=BLOCK + 40, d=d, dv=dv)
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    assert got.shape == want.shape == (2, BLOCK + 40, 2, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("d,dv", [(24, 16), (192, 128)])
def test_flash_gradients_with_another_v_width(d, dv):
    q, k, v = _qkv_widths(1, s=150, d=d, dv=dv)
    scale = d ** -0.5
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(
        fn(q, k, v, causal=True, scale=scale)))
    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)


def test_v_is_not_widened_to_the_q_width():
    """At 192 / 128 the kernels see q and k at 256 lanes and v, the
    accumulator and the output at 128: v is not padded to q's width."""
    seen = []
    real = _fa.pl.pallas_call

    def spy(kernel, **kw):
        seen.append([s.shape[-1] for s in jax.tree.leaves(kw["out_shape"])])
        return real(kernel, **kw)

    q, k, v = _qkv_widths(2, s=64, d=192, dv=128)
    _fa.pl.pallas_call = spy
    try:
        jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    finally:
        _fa.pl.pallas_call = real
    # forward: o 128 (+ the row statistics' lane tile); dk 256, dv 128; dq 256
    assert [128, BLOCK] in seen and [256, 128] in seen and [256] in seen


# What the kernels gave at the parent commit (sha256 over out, dq, dk, dv as
# f32 bytes; inputs as `_digest` makes them), and what plain `jax.numpy`
# gives on the same inputs on the machine that recorded them: where the
# latter differs, this is another CPU and the comparison says nothing.
_BEFORE = {
    (64, "bfloat16"):
        "21c5bef24b194dd5d63f5e4c580047b091007bda4327536bb52c122388a0ef04",
    (64, "float32"):
        "5c1715fa1aeacb887ef5c036c94588dbbafbd6a743ba6b8b8e11b0cbaef77587",
    (128, "bfloat16"):
        "303c8dfb09df8b04d06fafc0beeb1033f10e044e78505594981d875dd98473a3",
}
_CANARY = "e5323ba271f34050ea54f017f5b1c104244597807658466bf2c7a563917a40b0"


def _digest(arrays):
    import hashlib
    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a.astype(jnp.float32)).tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("d,dtype", sorted(_BEFORE))
def test_bit_equal_to_before_at_equal_widths(d, dtype):
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(2, 200, 2, d), dtype) for _ in range(3))
    canary = _digest([dense_attention(*(x.astype(jnp.float32)
                                        for x in _qkv(7, s=200, d=64)),
                                      causal=True)])
    if canary != _CANARY:
        pytest.skip("another CPU than the one the digests were taken on")
    out = flash_attention(q, k, v, causal=True)
    grads = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, causal=True).astype(jnp.float32))), argnums=(0, 1, 2))(
            q, k, v)
    assert _digest([out, *grads]) == _BEFORE[(d, dtype)]
