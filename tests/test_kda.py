"""The chunked KDA recurrence (`ops/kda.py`) against the recurrence written
token by token (the benchmark's reference, `perfbench/models/
kimi_linear.py:kda_recurrence`): forward and gradient, several chunk and
sub-chunk sizes, a length that is no multiple of the chunk, and decays
strong enough that a factored ``exp(-G)`` would overflow.  And the Pallas
kernels (`ops/kda_pallas.py`) under the interpreter against `kda_chunked`,
which is also what picks between the two (`kda_attention`)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.models.kimi_linear import kda_recurrence
from pytorch_ps_mpi_tpu.ops import kda, kda_pallas
from pytorch_ps_mpi_tpu.ops.kda import kda_attention, kda_chunked

# An implementation, and the head widths it is tried at: the kernels take
# whole lane tiles, the plain code is tried where it always was.
IMPLS = {"ref": dict(dk=16, dv=8), "interpret": dict(dk=128, dv=128)}


def _inputs(seed, b=2, s=150, h=2, dk=16, dv=8, decay=0.3):
    r = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (unit(r.randn(b, s, h, dk)) for _ in range(2))
    v = r.randn(b, s, h, dv)
    g = -decay * np.exp(r.randn(b, s, h, dk))
    beta = 1.0 / (1.0 + np.exp(-r.randn(b, s, h)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.fixture(autouse=True)
def _f32_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


def _sizes(monkeypatch, chunk, sub):
    """The chunk and sub-chunk are constants of `ops/kda.py`, picked on the
    chip; the algebra has to hold for any pair a later sweep may pick."""
    monkeypatch.setattr(kda, "CHUNK", chunk)
    monkeypatch.setattr(kda, "SUB_CHUNK", sub)


@pytest.mark.parametrize("chunk,sub", [(64, 16), (32, 8), (16, 16), (64, 64),
                                       (128, 16)])
@pytest.mark.parametrize("s", [150, 64])
def test_chunked_matches_token_by_token(chunk, sub, s, monkeypatch):
    _sizes(monkeypatch, chunk, sub)
    args = _inputs(0, s=s)
    want = kda_recurrence(*args)
    got = kda_chunked(*args)
    assert got.shape == want.shape == (2, s, 2, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("chunk,sub", [(32, 8), (64, 16)])
def test_gradients_match_token_by_token(chunk, sub, monkeypatch):
    _sizes(monkeypatch, chunk, sub)
    args = _inputs(1, s=100)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a)))
    want = jax.grad(loss(kda_recurrence), argnums=range(5))(*args)
    got = jax.grad(loss(kda_chunked), argnums=range(5))(*args)
    for name, g, w in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


@pytest.mark.parametrize("impl", IMPLS)
def test_strong_decay_stays_finite_and_exact(impl, monkeypatch):
    """A channel that decays by e^-3 a token has lost e^-190 by the end of
    a 64-token chunk: exp(+190) is not an f32."""
    _sizes(monkeypatch, 64, 16)
    fn = functools.partial(kda_attention, impl=impl)
    args = _inputs(2, s=130, decay=3.0, **IMPLS[impl])
    want = kda_recurrence(*args)
    got = fn(*args)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    grads = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=range(5))(*args)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


@pytest.mark.parametrize("impl", IMPLS)
def test_bf16_products_accumulate_in_f32(impl):
    """As the model calls it: q, k and v in bf16, decay and beta in f32."""
    args = _inputs(4, s=128, **IMPLS[impl])
    want = kda_recurrence(*args)
    q, k, v = (x.astype(jnp.bfloat16) for x in args[:3])
    got = kda_attention(q, k, v, *args[3:], impl=impl)
    assert got.dtype == jnp.bfloat16          # v's dtype
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                / jnp.max(jnp.abs(want)))
    assert err < 0.05


@pytest.mark.parametrize("chunk,sub", [(48, 32), (48, 16)])
def test_chunk_must_be_a_power_of_two_multiple_of_the_sub_chunk(
        chunk, sub, monkeypatch):
    _sizes(monkeypatch, chunk, sub)
    with pytest.raises(ValueError, match="multiple"):
        kda_chunked(*_inputs(0, s=32))


@pytest.mark.parametrize("c,block", [(64, 8), (64, 16), (32, 32), (128, 16)])
def test_inverse_of_a_unit_lower_triangle(c, block):
    """Against numpy in f64, on a matrix whose entries are all near one
    (every key of the chunk alike, beta near 1): the inverse is bounded,
    the powers of the matrix are not."""
    from pytorch_ps_mpi_tpu.ops.kda import _inverse_unit_lower
    r = np.random.RandomState(c + block)
    lower = np.tril(0.9 + 0.1 * r.rand(3, c, c), k=-1)
    want = np.linalg.inv(np.eye(c) + lower)
    got = _inverse_unit_lower(jnp.asarray(lower, jnp.float32), block)
    assert np.abs(want).max() < 10.0
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)


@pytest.mark.parametrize("impl", IMPLS)
def test_keys_that_are_all_alike(impl, monkeypatch):
    """Every key of a chunk the same unit vector, beta near 1, no decay:
    the system to invert is all ones below its diagonal."""
    q, k, v, g, beta = _inputs(5, s=128, **IMPLS[impl])
    k = jnp.broadcast_to(k[:, :1], k.shape)
    g, beta = jnp.zeros_like(g), jnp.full_like(beta, 0.98)
    want = kda_recurrence(q, k, v, g, beta)
    _sizes(monkeypatch, 64, 8)
    got = kda_attention(q, k, v, g, beta, impl=impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-3, atol=1e-4)


# -- the kernels, under the interpreter, against `kda_chunked` ----------------

# (shape [B, S, H, D], tokens a grid step): a tail inside one block, a
# length that is one block of one chunk, and a state carried from block to
# block with a tail.
KERNEL_CASES = {"tail": ((2, 150, 2, 128), 256), "one-block": ((1, 64, 1, 128), 256),
                "carried": ((1, 150, 1, 128), 128)}


def _kernel_pair(case, dtype, seed):
    (b, s, h, d), block_t = KERNEL_CASES[case]
    q, k, v, g, beta = _inputs(seed, b=b, s=s, h=h, dk=d, dv=d)
    args = (*(x.astype(dtype) for x in (q, k, v)), g, beta)
    kernels = lambda *a: kda_pallas._kda(*a, True, block_t)   # interpreted
    # f32: rounding only.  bf16: both sides round their products' inputs,
    # at different places; of the largest entry, as the chip smoke has it.
    return args, kernels, (2e-5 if dtype == jnp.float32 else 3e-2)


def _close(got, want, tol, what):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    err = np.abs(got - want).max() / np.abs(want).max()
    assert err <= tol, f"{what}: {err:g} of the largest entry > {tol:g}"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernels_match_the_plain_code(case, dtype):
    args, kernels, tol = _kernel_pair(case, dtype, seed=6)
    got, want = kernels(*args), kda_chunked(*args)
    assert got.shape == want.shape and got.dtype == want.dtype == dtype
    _close(got, want, tol, "o")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernel_gradients_match_the_plain_code(case, dtype):
    """All five, through the hand-written backward."""
    args, kernels, tol = _kernel_pair(case, dtype, seed=7)
    loss = lambda fn: lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))
    got = jax.grad(loss(kernels), argnums=range(5))(*args)
    want = jax.grad(loss(kda_chunked), argnums=range(5))(*args)
    for name, g, w, a in zip("q k v g beta".split(), got, want, args):
        assert g.shape == a.shape and g.dtype == a.dtype, name
        _close(g, w, 3 * tol, "d" + name)


def _pallas_calls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("pallas_call")


def test_the_widths_choose_the_implementation():
    """No setting picks: a width of 16 is the plain code and nothing else;
    whole lane tiles bring the kernels, for the platform that has them."""
    assert _pallas_calls(kda_attention, *_inputs(0, s=32)) == 0
    wide = _inputs(0, b=1, s=32, h=1, dk=128, dv=128)
    assert _pallas_calls(kda_attention, *wide) == 1
    assert _pallas_calls(
        functools.partial(kda_attention, impl="ref"), *wide) == 0
    with pytest.raises(ValueError, match="multiples of 128"):
        kda_attention(*_inputs(0, s=32), impl="interpret")


def test_off_the_chip_the_default_is_the_plain_code():
    """`lax.platform_dependent` lowers the branch of the platform the
    program is built for: here the CPU, so `kda_chunked`, bit for bit, and
    differentiable by JAX."""
    args = _inputs(8, b=1, s=70, h=1, dk=128, dv=128)
    got, want = jax.jit(kda_attention)(*args), jax.jit(kda_chunked)(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    grads = jax.grad(lambda *a: jnp.sum(kda_attention(*a)),
                     argnums=range(5))(*args)
    wants = jax.grad(lambda *a: jnp.sum(kda_chunked(*a)),
                     argnums=range(5))(*args)
    for g, w in zip(grads, wants):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6,
                                   atol=1e-7)
