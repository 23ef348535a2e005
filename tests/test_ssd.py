"""`ops.ssd.ssd` (the chunked state-space duality scan) against `ssd_loop`
(the recurrence one token a step, differentiated by JAX): outputs and every
gradient at several lengths of whole chunks, with groups fewer than heads,
where a chunk's decay underflows, and what the backward keeps.  And the
Pallas kernels of `ops.ssd_pallas` under the interpreter against both, at
the widths they take (heads of 64, a state of 128), and which shapes `ssd`
sends to them."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import ssd_pallas
from pytorch_ps_mpi_tpu.ops.ssd import carried_share, ssd, ssd_chunked, ssd_loop

ROWS, HEADS, P, GROUPS, N = 2, 4, 8, 2, 16


def inputs(seq, seed=0, dt_scale=1.0, a_scale=1.0):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    x = f(ROWS, seq, HEADS, P)
    dt = jax.nn.softplus(f(ROWS, seq, HEADS)) * dt_scale
    a = -jnp.exp(f(HEADS)) * a_scale
    return x, dt, a, f(ROWS, seq, GROUPS, N), f(ROWS, seq, GROUPS, N), \
        f(HEADS)


def loss(fn):
    return lambda *args: jnp.sum(jnp.sin(fn(*args)))


@pytest.mark.parametrize("seq,chunk", [(8, 8), (32, 8), (48, 16), (64, 64)])
def test_the_chunked_scan_is_the_recurrence(seq, chunk):
    args = inputs(seq, seed=seq)
    got = ssd(*args, chunk=chunk)
    want = ssd_loop(*args)
    assert got.dtype == jnp.float32 and got.shape == (ROWS, seq, HEADS, P)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("seq,chunk", [(32, 8), (48, 16)])
def test_every_gradient_is_the_recurrences(seq, chunk):
    args = inputs(seq, seed=seq + 1)
    got = jax.grad(loss(lambda *a: ssd(*a, chunk=chunk)),
                   argnums=range(6))(*args)
    want = jax.grad(loss(ssd_loop), argnums=range(6))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), got, want):
        assert g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=1e-4,
            atol=1e-4 * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)


def test_a_head_reads_the_group_it_belongs_to():
    """Heads 0, 1 read group 0 and heads 2, 3 group 1: changing group 1's
    ``B`` moves heads 2 and 3 only."""
    x, dt, a, b, c, d = inputs(16, seed=3)
    base = ssd(x, dt, a, b, c, d, chunk=8)
    moved = ssd(x, dt, a, b.at[:, :, 1].add(1.0), c, d, chunk=8)
    changed = jnp.max(jnp.abs(moved - base), axis=(0, 1, 3))
    assert [bool(v > 1e-3) for v in changed] == [False, False, True, True]


def test_where_a_chunks_decay_underflows_nothing_is_inf_or_nan():
    """``dt A`` of -40 a token: a chunk's decay ``exp(-640)`` is 0 in f32,
    and so is ``exp`` of every difference across a chunk.  The chunked form
    takes ``exp`` of differences that are never positive, so its output and
    gradients stay finite and equal the recurrence's.  ``A``'s gradient to
    2 % only: it reaches ``A`` through the running sums, as the difference
    of terms a thousand times its size at these exponents (f32 rounding),
    where the recurrence's reaches it one token at a time; the f64
    recurrence agrees with the f32 one here."""
    args = inputs(32, seed=5, dt_scale=20.0, a_scale=10.0)
    assert float(jnp.min(args[1][..., None] * args[2])) < -40
    got = ssd(*args, chunk=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ssd_loop(*args)),
                               rtol=1e-4, atol=1e-4)
    grads = jax.grad(loss(lambda *a: ssd(*a, chunk=16)),
                     argnums=range(6))(*args)
    want = jax.grad(loss(ssd_loop), argnums=range(6))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), grads, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        rel = 2e-2 if name == "A" else 1e-4
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), rtol=rel,
            atol=rel * max(1.0, float(jnp.max(jnp.abs(w)))), err_msg=name)
    assert float(carried_share(args[1], args[2], chunk=16)) == 0.0


def test_a_length_that_is_not_whole_chunks_is_refused():
    with pytest.raises(ValueError, match="whole number of chunks"):
        ssd(*inputs(24), chunk=16)
    with pytest.raises(ValueError, match="whole number of chunks"):
        carried_share(inputs(24)[1], inputs(24)[2], chunk=16)


def test_the_backward_keeps_no_state_a_token():
    """What the vjp holds for the backward: nothing as large as a state a
    token (``[rows, S, H, P, N]``); the carried states it keeps are one a
    chunk."""
    seq, chunk = 64, 8
    args = inputs(seq, seed=7)
    _, vjp = jax.vjp(lambda *a: ssd(*a, chunk=chunk), *args)
    sizes = [np.size(leaf) for leaf in jax.tree.leaves(vjp)]
    per_token = ROWS * seq * HEADS * P * N
    per_chunk = ROWS * (seq // chunk) * HEADS * P * N
    assert max(sizes) < per_token
    assert per_chunk in sizes


def test_the_carried_share_by_hand():
    dt = jnp.full((1, 16, 2), 0.5, jnp.float32)
    a = jnp.asarray([-1.0, -0.25], jnp.float32)
    # a chunk of 8 tokens: exp(-4) and exp(-1), the mean over the two heads
    want = (np.exp(-4.0) + np.exp(-1.0)) / 2
    assert float(carried_share(dt, a, chunk=8)) == pytest.approx(want,
                                                                 rel=1e-6)


# -- the kernels --------------------------------------------------------------

WIDE = {"heads": 4, "p": 64, "groups": 2, "n": 128}   # 2 groups of 2 heads


def wide_inputs(rows, seq, seed=0, dt_scale=0.1, a_scale=1.0,
                dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    f = lambda *shape: jnp.asarray(rng.randn(*shape), jnp.float32)
    h, p, g, n = WIDE["heads"], WIDE["p"], WIDE["groups"], WIDE["n"]
    x = f(rows, seq, h, p).astype(dtype)
    dt = jax.nn.softplus(f(rows, seq, h)) * dt_scale
    a = -jnp.exp(f(h)) * a_scale
    b, c = (0.3 * f(rows, seq, g, n)).astype(dtype), \
        (0.3 * f(rows, seq, g, n)).astype(dtype)
    return x, dt, a, b, c, f(h)


def kernels(*args):
    return ssd_pallas.ssd_kernels(*args, impl="interpret")


def close(got, want, rel, name=""):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=rel,
        atol=rel * max(1.0, float(jnp.max(jnp.abs(want)))), err_msg=name)


@pytest.mark.parametrize("rows,chunks", [(1, 2), (2, 2), (1, 4)])
def test_the_kernels_are_the_chunked_scan_and_the_recurrence(rows, chunks):
    """Output and every gradient, in f32, against `ssd_chunked` and
    `ssd_loop`; 2 chunks is one grid block (the state carried from chunk to
    chunk inside it), 4 are two (and from block to block in scratch)."""
    args = wide_inputs(rows, chunks * ssd_pallas.CHUNK, seed=rows + chunks)
    got = kernels(*args)
    assert got.dtype == jnp.float32 and got.shape == args[0].shape
    close(got, ssd_chunked(*args, chunk=128), 1e-5)
    close(got, ssd_loop(*args), 1e-4)
    grads = jax.grad(loss(kernels), argnums=range(6))(*args)
    chunked = jax.grad(loss(lambda *a: ssd_chunked(*a, chunk=128)),
                       argnums=range(6))(*args)
    looped = jax.grad(loss(ssd_loop), argnums=range(6))(*args)
    for name, g, c, w in zip(("x", "dt", "A", "B", "C", "D"), grads,
                             chunked, looped):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        close(g, c, 1e-4, name)
        close(g, w, 1e-4, name)


def test_the_kernels_in_bf16_round_only_the_products_operands():
    """x, B and C in bf16: the gradients come back in their primal's
    dtype, and the output and gradients stay within bf16 rounding of the
    plain form's (which takes its products in f32 on the CPU)."""
    args = wide_inputs(1, 256, seed=11, dtype=jnp.bfloat16)
    close(kernels(*args), ssd_chunked(*args, chunk=128), 2e-2)
    grads = jax.grad(loss(kernels), argnums=range(6))(*args)
    want = jax.grad(loss(lambda *a: ssd_chunked(*a, chunk=128)),
                    argnums=range(6))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), grads, want):
        assert g.dtype == w.dtype, name
        rel = float(jnp.linalg.norm((g - w).astype(jnp.float32))
                    / jnp.linalg.norm(w.astype(jnp.float32)))
        assert rel < 2e-2, (name, rel)


def test_where_a_chunks_decay_underflows_the_kernels_stay_finite():
    """``dt A`` below -40 a token: ``exp`` of every difference across a
    chunk is 0 in f32; the kernels' exponents are never positive, so the
    output and every gradient are finite and the recurrence's (``A``'s to
    2 %, as `test_where_a_chunks_decay_underflows_nothing_is_inf_or_nan`
    says why)."""
    args = wide_inputs(1, 256, seed=5, dt_scale=20.0, a_scale=10.0)
    assert float(jnp.min(args[1][..., None] * args[2])) < -40
    got = kernels(*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    close(got, ssd_loop(*args), 1e-4)
    grads = jax.grad(loss(kernels), argnums=range(6))(*args)
    want = jax.grad(loss(ssd_loop), argnums=range(6))(*args)
    for name, g, w in zip(("x", "dt", "A", "B", "C", "D"), grads, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        close(g, w, 2e-2 if name == "A" else 1e-4, name)


def test_ssd_sends_only_the_kernels_widths_to_them():
    """The rehearsal's widths (heads of 16, a state of 16, chunks of 32),
    the tests' small ones and a length that is not whole blocks take the
    plain form on every platform; the cell's widths at whole blocks take
    the kernels where the program is lowered for a TPU (on this CPU, the
    plain form again)."""
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    rehearsal = (shape(2, 96, 4, 16), shape(2, 96, 2, 16), 32)
    assert not ssd_pallas.supports(*rehearsal)
    assert not ssd_pallas.supports(shape(2, 64, 4, 8), shape(2, 64, 2, 16), 8)
    cell = (shape(1, 8192, 64, 64), shape(1, 8192, 8, 128), 128)
    assert ssd_pallas.supports(*cell)
    assert not ssd_pallas.supports(shape(1, 8192 + 64, 64, 64),
                                   shape(1, 8192 + 64, 8, 128), 64)
    assert not ssd_pallas.supports(shape(1, 384, 64, 64),
                                   shape(1, 384, 8, 128), 128)
    with pytest.raises(ValueError, match="heads of 64"):
        kernels(*inputs(32))
    args = wide_inputs(1, 256, seed=2)
    close(ssd(*args, chunk=128), ssd_chunked(*args, chunk=128), 1e-6)
