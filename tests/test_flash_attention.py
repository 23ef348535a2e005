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
