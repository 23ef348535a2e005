"""The blocked selective scan (`ops/selective_scan.py`: one state kept a
block of tokens, a hand-written backward) against the recurrence one token
a step differentiated by JAX, in f32: the output and the gradients of all
six inputs, at lengths that are and are not whole blocks; what the backward
keeps; the Pallas kernels (`ops/selective_scan_pallas.py`) under the
interpreter against the same oracle, and which sizes get them; and the
benchmark's own token-by-token reference."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import selective_scan as ss
from pytorch_ps_mpi_tpu.ops import selective_scan_pallas as sp
from pytorch_ps_mpi_tpu.ops.selective_scan import (selective_scan,
                                                   selective_scan_loop)

NAMES = ("x", "dt", "A", "B", "C", "D")


def inputs(seed, rows, s, d, n, dtype=jnp.float32):
    """Steps between 0.02 and 0.9 and decays ``A`` between -16 and -1, the
    range the layer's initialisers give (and a decade more)."""
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    dt = jax.nn.softplus(f(rows, s, d) - 2.0)
    a = -jnp.exp(jnp.asarray(rng.rand(d, n) * np.log(16.0), jnp.float32))
    return (f(rows, s, d).astype(dtype), dt, a, f(rows, s, n), f(rows, s, n),
            f(d))


def blocked(block):
    """The plain form at ``block`` tokens a block, with the ``D`` skip."""
    def scan(x, dt, a, b, c, skip):
        y = ss._scan(x, dt, a.T, b, c, min(block, x.shape[1]))
        return (y + skip * x.astype(jnp.float32)).astype(x.dtype)
    return scan


def _loss(fn):
    return lambda *a: jnp.sum(jnp.sin(fn(*a).astype(jnp.float32)))


@pytest.mark.parametrize("rows,s,d,n,block", [
    (2, 64, 24, 4, 16),     # four whole blocks
    (1, 50, 24, 4, 16),     # a last block of two tokens, the rest padding
    (2, 37, 8, 16, 8),      # 16 states, a length that is prime
    (1, 20, 8, 4, 128),     # shorter than a block: one block of 20
    (1, 9, 8, 4, 1),        # a block a token: every state is kept
])
def test_output_and_all_six_gradients_match_the_token_loop(rows, s, d, n,
                                                           block):
    """f32 both, the same operations in another order (the blocked form
    recomputes a block's states and walks the adjoint back through them):
    2e-5 of each value's size."""
    args = inputs(0, rows, s, d, n)
    scan = blocked(block)
    want = [selective_scan_loop(*args),
            *jax.grad(_loss(selective_scan_loop), argnums=range(6))(*args)]
    got = [scan(*args), *jax.grad(_loss(scan), argnums=range(6))(*args)]
    for g, w, name in zip(got, want, ("y",) + tuple("d" + n for n in NAMES)):
        assert g.shape == w.shape and g.dtype == jnp.float32
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)


def test_the_output_and_gradients_take_their_inputs_types():
    """bf16 ``x`` (the convolution's output in the model) with f32 steps:
    ``y`` and ``dx`` come back bf16, every step in between is f32, so the
    result is the f32 result of the rounded ``x``, rounded once."""
    args = inputs(1, 2, 40, 16, 4, dtype=jnp.bfloat16)
    y = blocked(16)(*args)
    grads = jax.grad(_loss(blocked(16)), argnums=range(6))(*args)
    assert y.dtype == jnp.bfloat16
    assert [g.dtype for g in grads] == [jnp.bfloat16] + [jnp.float32] * 5
    want = selective_scan_loop(*args)
    np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(want),
                               rtol=1e-2, atol=1e-2)


def test_the_backward_keeps_one_state_a_block_and_never_one_a_token():
    """The residuals of the custom VJP are the five inputs and ``[blocks,
    rows, N, d_inner]`` of states; no array of the traced backward has a
    token axis beside both the state axes except one block's worth."""
    rows, s, d, n, block = 1, 96, 8, 4, 16
    args = inputs(2, rows, s, d, n)
    _, res = ss._scan_vjp_fwd(*args[:2], args[2].T, *args[3:5], block)
    assert res[-1].shape == (s // block, rows, n, d)
    assert [r.shape for r in res[:-1]] == [a.shape for a in (
        args[0], args[1], args[2].T, args[3], args[4])]
    jaxpr = jax.make_jaxpr(jax.grad(_loss(blocked(block)),
                                    argnums=range(6)))(*args)

    def shapes(j):
        for eqn in j.eqns:
            for v in eqn.outvars:
                yield tuple(v.aval.shape)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from shapes(sub)

    states = [sh for sh in shapes(jaxpr.jaxpr)
              if sh[-2:] == (n, d) and len(sh) > 3]
    assert states and max(np.prod(sh[:-3]) for sh in states) <= max(
        block, s // block)


def test_a_zero_step_leaves_the_state_and_padding_adds_nothing():
    """``dt = 0`` is ``h_t = h_{t-1}``: tokens with no step repeat the
    output of the state they found, which is how the last block is
    padded."""
    x, dt, a, b, c, d = inputs(3, 1, 12, 8, 4)
    dt = dt.at[:, 5:9].set(0.0)
    c = c.at[:, 5:9].set(c[:, 4:5])
    y = blocked(4)(x, dt, a, b, c, jnp.zeros_like(d))
    np.testing.assert_allclose(np.asarray(y[:, 5:9]),
                               np.broadcast_to(np.asarray(y[:, 4:5]),
                                               (1, 4, 8)), rtol=1e-6)


def test_the_benchmarks_reference_is_the_same_recurrence():
    """`perfbench/models/sambay.py:ssm_recurrence` (its own token loop, in
    blocks under `jax.checkpoint`) without the ``D`` skip."""
    from perfbench.models.sambay import ssm_recurrence

    x, dt, a, b, c, d = inputs(4, 2, 70, 8, 4)
    want = selective_scan_loop(x, dt, a, b, c, jnp.zeros_like(d))
    np.testing.assert_allclose(np.asarray(ssm_recurrence(x, dt, a, b, c)),
                               np.asarray(want), rtol=1e-5, atol=1e-6)


# -- the kernels --------------------------------------------------------------


@pytest.mark.parametrize("rows,s,d,n,block_t,block_d", [
    (1, 40, 128, 8, 16, 128),    # 2.5 blocks of tokens: a padded tail
    (2, 64, 256, 16, 32, 128),   # two rows, two blocks of channels, 16 states
    (1, 21, 256, 8, 64, 256),    # shorter than a block: 24 tokens, one step
    (1, 48, 384, 8, 16, 256),    # 384 channels: blocks of 128 (256 no divisor)
])
def test_the_kernels_match_the_token_loop_under_the_interpreter(
        rows, s, d, n, block_t, block_d):
    """`ssm_fwd` / `ssm_bwd` as the chip runs them but for the interpreter:
    the state carried over blocks of tokens in scratch, the blocks taken
    last to first in the backward, ``dA`` added up over the sequence, ``dB``
    and ``dC`` as lane-tile sums that the caller reduces.  f32: 2e-5 of each
    value's size."""
    args = inputs(5, rows, s, d, n)

    def kernels(x, dt, a, b, c, skip):
        return sp.ssm_kernels(x, dt, a.T, b, c, impl="interpret",
                              block_t=block_t, block_d=block_d) + skip * x

    want = [selective_scan_loop(*args),
            *jax.grad(_loss(selective_scan_loop), argnums=range(6))(*args)]
    got = [kernels(*args), *jax.grad(_loss(kernels), argnums=range(6))(*args)]
    for g, w, name in zip(got, want, ("y",) + tuple("d" + n for n in NAMES)):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=2e-5,
            atol=2e-5 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)


def test_the_sizes_decide_and_a_name_overrides():
    """Channels in whole lane tiles and states in whole sublane tiles get
    the kernels on a TPU; anything else, and any program lowered for
    another platform (this one: the CPU), the blocked plain form.  The
    kernels refuse other sizes by name."""
    small, wide = inputs(6, 1, 24, 24, 4), inputs(6, 1, 24, 128, 8)
    assert not sp.supports(small[0], small[2].T)
    assert sp.supports(wide[0], wide[2].T)
    assert sp._blocks(8192, 5120, sp.BLOCK_T, sp.BLOCK_D) \
        == (sp.BLOCK_T, 8192, sp.BLOCK_D)
    assert sp._blocks(21, 384, 64, 256) == (24, 24, 128)
    with pytest.raises(ValueError, match="multiples of 128"):
        sp.ssm_kernels(*small[:2], small[2].T, *small[3:5], impl="interpret")
    for args in (small, wide):      # lowered here, for the CPU: no kernel
        text = jax.jit(selective_scan).lower(*args).as_text()
        assert "tpu_custom_call" not in text
        np.testing.assert_allclose(
            np.asarray(selective_scan(*args)),
            np.asarray(selective_scan(*args, impl="ref")), rtol=1e-6)
    named = selective_scan(*wide, impl="interpret")
    np.testing.assert_allclose(np.asarray(named),
                               np.asarray(selective_scan_loop(*wide)),
                               rtol=2e-5, atol=2e-5)
