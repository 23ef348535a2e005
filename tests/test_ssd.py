"""`ops.ssd.ssd` (the chunked state-space duality scan) against `ssd_loop`
(the recurrence one token a step, differentiated by JAX): outputs and every
gradient at several lengths of whole chunks, with groups fewer than heads,
where a chunk's decay underflows, and what the backward keeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops.ssd import carried_share, ssd, ssd_loop

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
