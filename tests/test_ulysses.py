"""Ulysses all-to-all sequence parallelism vs dense attention: forward and
gradient equality, flash-kernel composition, and end-to-end LM training
parity — the same oracles the ring-attention suite uses, for the second
long-context strategy."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM, build_lm,
                                                   lm_batch, make_lm_loss)
from pytorch_ps_mpi_tpu.parallel.mesh import make_dp_sp_mesh, make_ps_mesh
from pytorch_ps_mpi_tpu.parallel.ring_attention import dense_attention
from pytorch_ps_mpi_tpu.parallel.ulysses import (make_ulysses_attention,
                                                 ulysses_attention)

from lm_helpers import toy_tokens


def _qkv(seed, b=2, s=32, h=4, d=8):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sp", [2, 4])
def test_ulysses_matches_dense(causal, sp):
    mesh = make_dp_sp_mesh(dp=1, sp=sp)
    q, k, v = _qkv(0)
    want = dense_attention(q, k, v, causal=causal)
    got = make_ulysses_attention(mesh, causal=causal)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_ulysses_flash_inner_matches_dense():
    """Ulysses composes with the Pallas flash kernel (under the
    interpreter here): the all_to_all resharding hands it full sequences."""
    import functools

    from pytorch_ps_mpi_tpu.ops import flash_attention as fa

    flash_attention = functools.partial(fa.flash_attention, impl="interpret")

    mesh = make_dp_sp_mesh(dp=1, sp=2)
    q, k, v = _qkv(4, b=1, s=256, h=2, d=8)
    want = dense_attention(q, k, v, causal=True)
    got = make_ulysses_attention(mesh, causal=True,
                                 inner=flash_attention)(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_gradients_match_dense(causal):
    """Differentiate the shard_mapped scalar from outside (one global seed,
    like the ring-attention gradient test): grads wrt q, k, v must equal
    the dense-attention grads."""
    mesh = make_dp_sp_mesh(dp=1, sp=4)
    q, k, v = _qkv(2, b=1, s=16, h=4, d=4)
    tgt = jnp.asarray(np.random.RandomState(3)
                      .randn(*q.shape).astype(np.float32))

    def dense_loss(q, k, v):
        return jnp.sum((dense_attention(q, k, v, causal=causal) - tgt) ** 2)

    want = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)

    spec = P(None, "sp")

    def inner(q, k, v, tgt):
        o = ulysses_attention(q, k, v, causal=causal)
        return jax.lax.psum(jnp.sum((o - tgt) ** 2), "sp")

    smapped = jax.shard_map(
        inner, mesh=mesh, in_specs=(spec,) * 4, out_specs=P(),
        check_vma=False)
    with jax.set_mesh(mesh):
        got = jax.grad(lambda q, k, v: smapped(q, k, v, tgt),
                       argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=1e-5)


def test_ulysses_rejects_indivisible_heads():
    mesh = make_dp_sp_mesh(dp=1, sp=4)
    q, k, v = _qkv(1, h=3)
    with pytest.raises(ValueError, match="heads do not split"):
        make_ulysses_attention(mesh)(q, k, v)


def test_ulysses_lm_training_matches_dense():
    """(dp=2, sp=4) LM training with Ulysses attention == dp=2 dense —
    mirror of the ring-attention trainer parity test."""
    dense = TransformerLM(vocab_size=29, d_model=32, n_heads=4, n_layers=2,
                          d_ff=64, max_len=64)
    sp_model = dense.copy(attn=functools.partial(
        ulysses_attention, axis="sp", causal=True))
    params = build_lm(dense, seq_len=16)

    opt_sp = SGD(list(params.items()), lr=0.05, momentum=0.9,
                 mesh=make_dp_sp_mesh(dp=2, sp=4),
                 batch_spec=P("ps", "sp"))
    opt_sp.compile_step(make_lm_loss(sp_model))

    opt_dp = SGD(list(params.items()), lr=0.05, momentum=0.9,
                 mesh=make_ps_mesh(2))
    opt_dp.compile_step(make_lm_loss(dense))

    for step in range(5):
        batch = lm_batch(toy_tokens(8, 16, seed=step))
        ls, _ = opt_sp.step(batch)
        ld, _ = opt_dp.step(batch)
        assert abs(ls - ld) < 1e-4, (step, ls, ld)

    for n in opt_dp.params:
        np.testing.assert_allclose(
            np.asarray(opt_sp.params[n]), np.asarray(opt_dp.params[n]),
            rtol=2e-3, atol=2e-5, err_msg=n)
