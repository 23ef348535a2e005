"""The chunked KDA recurrence as Pallas TPU kernels, forward and backward.

What is computed is what `ops.kda.kda_chunked` computes (its docstring has
the five equations), at the same precision: product inputs in v's dtype with
f32 accumulation; the log-decays, their running sums and exponentials, the
unit-lower inverse and its products, and the carried state in f32.  What
differs is where the intermediates live.  Two kernels, each a grid
``(row, head, block of tokens)`` whose last axis runs in order and carries a
``[Dk, Dv]`` f32 state in VMEM scratch:

* `kda_fwd`: a block of `BLOCK_T` tokens of one head.  The intra-chunk
  matrices of its ``BLOCK_T / CHUNK`` chunks, the inverse and the decays are
  built in VMEM (`_intra`), then the chunks are taken in order through the
  state.  Reads q, k, v, g, beta once, writes o and the state at the start
  of the block (64 KB a block: all the backward keeps besides its inputs).
* `kda_bwd`: the same blocks last to first, carrying the state's gradient.
  It rebuilds `_intra`, replays the block's chunks from the saved state,
  walks them back, and differentiates the intra-chunk matrices by hand:
  every decay-weighted product ``A[i, j] = sum_d x_i k_j exp(G_i - G_j)``
  has ``dG = x * dx - k * dk`` whatever way the exponential was factored,
  the inverse ``dL = -X^T dX X^T`` collapses to one product of the solved
  right-hand sides, and ``dg`` is a reverse running sum.

The issue sketched four kernels with the chunk operands written to HBM
between them; here intra-chunk and state pass are one kernel in each
direction, so the operands (six arrays of the size of q) never leave VMEM.

**The three numerical rules of the plain code hold.**  No exponent is ever
positive: the ``SUB x SUB`` blocks on the diagonal are computed element by
element, one row of the block at a time against the block's other rows
(``exp(G_i - G_j)`` only where ``j <= i``); every block below the diagonal
factors through the first row ``r`` of its row half, ``exp(G_i - G_r) *
exp(G_r - G_j)`` with ``j < r <= i``.  The blocks below the diagonal are
taken level by level (halves of 8, 16, 32 tokens), the levels of the
pairwise merges of the inverse: one exponential a token, channel and level
where the flat form of the plain code pays one a row sub-chunk.  The inverse
is by substitution on the ``SUB x SUB`` blocks and pairwise merges by matrix
products in f32 at `Precision.HIGHEST`, never the Neumann product.  The
tail is padded with tokens that decay nothing and write nothing.

Layout: ``q, k, v, g`` as ``[B, S, H * D]`` (a free reshape), so a block
``(1, BLOCK_T, D)`` at column block ``h`` is one head's tokens with ``D`` on
the lanes; ``beta`` as ``[B, H, 1, S]``.  Inside, the chunk matrices are held
transposed and banded: ``at[j, i]`` for token ``j`` of the block (sublanes)
and ``i`` the position inside j's chunk (lanes) is ``A[chunk * CHUNK + i,
j]``, because the element-by-element blocks come out that way (a reduction
over the lanes leaves its result on the sublanes).  ``Dk`` and ``Dv`` have
to be multiples of 128.

``impl="interpret"`` runs the same kernel bodies under the Pallas
interpreter, by name, for the CPU tests; `kda_chunked` is the oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _named
from .pallas_kernels import use_interpreter

CHUNK = 64       # tokens a step of the state pass
SUB = 8          # side of the blocks computed element by element: one f32 tile
BLOCK_T = 256    # tokens a grid step
LANE = 128
# From a sweep on the v5e of one layer's recurrence at [1, 8192, 32, 128] in
# bf16 (my chip runs, PR 29), as (chunk, tokens a grid step) -> ms of the
# forward / of the backward kernel: (32, 256) 5.96 / 11.94, (32, 512) 5.82 /
# 12.12, (64, 128) 6.15 / 10.93, **(64, 256) 5.13 / 10.16**, (64, 512) 4.95 /
# 10.17, (128, 256) 5.48 / 9.99, (128, 512) 4.89 / 9.73.  A step pays two
# forwards and a backward: 20.4 ms at (64, 256), 19.5 at (128, 512) with
# four times the VMEM and twice the length of every chain in the inverse.
# `kda_chunked` beside them: 10.9 forward, 42.6 forward and backward.  SUB is
# not swept: a sub-chunk is one (8, 128) tile of f32, so a row of it is taken
# out and handed round along the sublanes, which costs a cycle a tile where
# anything along the lanes costs 4.5 (the table in PERF.md, section 6).

_HI = lax.Precision.HIGHEST
_F32 = jnp.float32
# dot_general contractions of [batch, rows, cols] operands
_NN, _NT, _TN = ((2,), (1,)), ((2,), (2,)), ((1,), (1,))


def _bdot(a, b, dims, precision=None):
    """Batched product over the leading axis, accumulated in f32."""
    return lax.dot_general(a, b, (dims, ((0,), (0,))), precision=precision,
                           preferred_element_type=_F32)


def _dot(a, b, dims):
    """``[rows, cols]`` product: `_NN`, `_NT` or `_TN` less the batch."""
    dims = tuple((d[0] - 1,) for d in dims)
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _div(x, m: int):
    """``x // m`` for ``x >= 0`` and ``m`` a power of two."""
    return lax.shift_right_logical(x, jnp.int32(m.bit_length() - 1))


def _mod(x, m: int):
    return x & (m - 1)


def _running_sum(x, reverse: bool = False):
    """The running sum of ``x: [nc, CHUNK, D]`` (f32) along each chunk, as a
    product with a triangle of ones.  ``x`` is cut into three bf16 pieces
    whose sum is ``x`` exactly; a piece times a one is exact, so three
    one-pass products accumulated in f32 are the f32 sum, at half the passes
    of one f32 product at `Precision.HIGHEST`."""
    nc = x.shape[0]
    row, col = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    tri = (col >= row) if reverse else (col <= row)
    tri = jnp.broadcast_to(tri.astype(jnp.bfloat16), (nc, CHUNK, CHUNK))
    total = jnp.zeros(x.shape, _F32)
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        total = total + _bdot(tri, piece, _NN)
        x = x - piece.astype(_F32)
    return total


def _row_to_col(row):
    """``[1, T] -> [T, 1]`` without a transpose: mask a square, reduce."""
    t = row.shape[1]
    eye = _iota((t, t), 0) == _iota((t, t), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _col_to_row(col):
    t = col.shape[0]
    eye = _iota((t, t), 0) == _iota((t, t), 1)
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _own(n: int):
    """For the banded ``[n, SUB, CHUNK]`` view of a block's transposed chunk
    matrices (sub-chunk ``n``, row ``j`` on the sublanes, position ``i``
    inside the chunk on the lanes): the lane's index inside the row's own
    sub-chunk, ``0 <= own < SUB`` on the diagonal block and nowhere else.
    Written and read with masks on the lanes: a slice or a concatenation
    along the lanes costs the v5e 4 to 28 cycles a tile, a select a third
    of one (my chip runs, PR 29)."""
    shape = (n, SUB, CHUNK)
    return _iota(shape, 2) - _mod(_iota(shape, 0), CHUNK // SUB) * SUB


def _diagonal_pass(k3, g3, i):
    """Row ``i`` of every sub-chunk against the rows ``j <= i`` of its own
    sub-chunk: ``e[n, j, d] = exp(G_i - G_j)`` (0 where ``j > i``) and
    ``k_j * e``."""
    sub = _iota(g3.shape, 1)
    e = jnp.exp(jnp.where(sub <= i, g3[:, i:i + 1, :] - g3, -jnp.inf))
    return e, k3 * e


def _levels():
    """Half sizes of the blocks below the diagonal, and for each the mask of
    its blocks in a chunk's transposed matrix ``[j, i]``: the same pair of
    halves, the column ``j`` in the first and the row ``i`` in the second."""
    j, i = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    m = SUB
    while m < CHUNK:
        yield m, ((_div(j, 2 * m) == _div(i, 2 * m))
                  & ((j & m) == 0) & ((i & m) != 0))
        m *= 2


def _intra(q, k, v, g, beta_col, dtype):
    """Everything the chunks of one block need besides the incoming state.
    ``q, k, g: [T, Dk]`` and ``v: [T, Dv]`` in f32, ``beta_col: [T, 1]``."""
    t, dk = q.shape
    nc, n = t // CHUNK, t // SUB
    c3 = lambda x: x.reshape(nc, CHUNK, x.shape[-1])
    s3 = lambda x: x.reshape(n, SUB, x.shape[-1])

    # The running sum of the log-decay inside each chunk.
    g_cum = _running_sum(c3(g)).reshape(t, dk)

    # On the diagonal, element by element, a row i of every sub-chunk at a
    # time: its entries come out of the reduction on the sublanes (j), so
    # the matrices are held transposed.
    q3, k3, g3 = s3(q), s3(k), s3(g_cum)
    own, below = _own(n), _iota((n, SUB, 1), 1)
    at_q = jnp.zeros((n, SUB, CHUNK), _F32)
    at_k = jnp.zeros((n, SUB, CHUNK), _F32)
    columns = []
    for i in range(SUB):
        _, p = _diagonal_pass(k3, g3, i)
        aq = jnp.sum(q3[:, i:i + 1, :] * p, axis=2, keepdims=True)
        ak = jnp.sum(k3[:, i:i + 1, :] * p, axis=2, keepdims=True)
        ak = jnp.where(below < i, ak, 0.0)                  # strictly lower
        at_q = jnp.where(own == i, aq, at_q)
        at_k = jnp.where(own == i, ak, at_k)
        columns.append(ak)
    at_q = at_q.reshape(nc, CHUNK, CHUNK)
    at_k = at_k.reshape(nc, CHUNK, CHUNK)

    # Below the diagonal, level by level, through the first row of the
    # second half of each pair of halves.
    tok = _iota((t, 1), 0)
    levels = []
    for m, mask in _levels():
        pairs = g_cum.reshape(t // (2 * m), 2 * m, dk)
        first = jnp.broadcast_to(pairs[:, m:m + 1, :], pairs.shape)
        first = first.reshape(t, dk)
        second = (tok & m) != 0
        f = jnp.exp(jnp.where(second, g_cum - first, first - g_cum))
        kf, qf = c3((k * f).astype(dtype)), c3((q * f).astype(dtype))
        at_q = at_q + jnp.where(mask, _bdot(kf, qf, _NT), 0.0)
        at_k = at_k + jnp.where(mask, _bdot(kf, kf, _NT), 0.0)
        levels.append((f, kf, qf, mask))

    # X^T = (I + (Akk Diag(beta))^T)^-1, upper triangular in [j, i]: back
    # substitution on the SUB x SUB blocks (row i is final once the rows
    # below it are, and is then taken out of every row above it), then
    # merges pair by pair, (X - X C X)^T = X^T - X^T C^T X^T.
    x_t = (own == _iota(own.shape, 1)).astype(_F32)
    beta3 = s3(beta_col)
    for i in range(SUB - 1, 0, -1):
        x_t = x_t - (columns[i] * beta3) * x_t[:, i:i + 1, :]
    inverse_t = x_t.reshape(nc, CHUNK, CHUNK)
    at_q, at_k = at_q.reshape(t, CHUNK), at_k.reshape(t, CHUNK)
    upper = c3(at_k * beta_col)
    for m, mask in _levels():
        # X^T C^T X^T is nothing but the corners C^T has: only the rows j of
        # the first halves are worked out, half the rows through the MXU.
        pairs = (nc, CHUNK // (2 * m), 2 * m, CHUNK)
        firsts = lambda x: x.reshape(pairs)[:, :, :m, :].reshape(
            nc, CHUNK // 2, CHUNK)
        spread = lambda x: jnp.concatenate(
            [x.reshape(pairs[:2] + (m, CHUNK))] * 2, axis=2).reshape(
            nc, CHUNK, CHUNK)
        corner = firsts(jnp.where(mask, upper, 0.0))
        first_half = (_iota((CHUNK, 1), 0) & m) == 0
        inner = jnp.where(
            first_half, spread(_bdot(corner, inverse_t, _NN, _HI)), 0.0)
        update = spread(_bdot(firsts(inverse_t), inner, _NN, _HI))
        inverse_t = inverse_t - jnp.where(first_half, update, 0.0)

    decay = jnp.exp(g_cum)
    last = c3(g_cum)[:, CHUNK - 1:, :]                      # [nc, 1, Dk]
    out = jnp.exp(jnp.broadcast_to(last, (nc, CHUNK, dk)).reshape(t, dk)
                  - g_cum)
    k_in = k * decay
    decay_out = jnp.exp(last)
    solved = _bdot(inverse_t, c3(jnp.concatenate([v, k_in], axis=1)), _TN,
                   _HI)
    return {
        "g_cum": g_cum, "at_q": at_q, "at_k": at_k, "levels": levels,
        "inverse_t": inverse_t, "solved": solved, "decay": decay, "out": out,
        "u0": solved[..., :v.shape[-1]].astype(dtype),
        "w": solved[..., v.shape[-1]:].astype(dtype),
        "q_in": c3((q * decay).astype(dtype)),
        "mt": c3((at_q * beta_col).astype(dtype)),
        "k_out": c3((k * out * beta_col).astype(dtype)),
        "decay_out": decay_out,                             # [nc, 1, Dk]
        # a column a chunk, for the state's rows: [Dk, 1]
        "decay_col": [_row_to_col(decay_out[b]) for b in range(nc)],
    }


def _chunk_step(ops, b, state, dtype):
    """Chunk ``b`` of the block from ``state`` (``[Dk, Dv]`` f32): the
    solved ``u`` in the products' dtype, the output, the next state.  The
    operands that have to be transposed do not depend on the state."""
    read = state.astype(dtype)
    u = ops["u0"][b].astype(_F32) - _dot(ops["w"][b], read, _NN)
    u = u.astype(dtype)
    o = _dot(ops["q_in"][b], read, _NN) + _dot(ops["mt"][b], u, _TN)
    state = state * ops["decay_col"][b] + _dot(ops["k_out"][b], u, _TN)
    return read, u, o, state


def _load(q_ref, k_ref, v_ref, g_ref, b_ref):
    q, k, v, g = (r[0].astype(_F32) for r in (q_ref, k_ref, v_ref, g_ref))
    return q, k, v, g, _row_to_col(b_ref[0, 0].astype(_F32))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, start_ref, state):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    dtype = v_ref.dtype
    ops = _intra(*_load(q_ref, k_ref, v_ref, g_ref, b_ref), dtype)
    s = state[...]
    start_ref[0, 0, 0] = s
    for b in range(q_ref.shape[1] // CHUNK):
        _, _, o, s = _chunk_step(ops, b, s, dtype)
        o_ref[0, b * CHUNK:(b + 1) * CHUNK, :] = o.astype(o_ref.dtype)
    state[...] = s


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, start_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, dstate):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    dtype = v_ref.dtype
    q, k, v, g, beta_col = _load(q_ref, k_ref, v_ref, g_ref, b_ref)
    t, dk = q.shape
    dv_width = v.shape[1]
    nc, n = t // CHUNK, t // SUB
    c3 = lambda x: x.reshape(nc, CHUNK, x.shape[-1])
    s3 = lambda x: x.reshape(n, SUB, x.shape[-1])
    flat = lambda x: x.reshape(t, x.shape[-1])
    ops = _intra(q, k, v, g, beta_col, dtype)
    do = c3(do_ref[0])

    # The block's chunks again, first to last, from the saved state.
    s = start_ref[0, 0, 0]
    states, reads, us = [], [], []
    for b in range(nc):
        states.append(s)
        read, u, _, s = _chunk_step(ops, b, s, dtype)
        reads.append(read)
        us.append(u)

    # And back, carrying the state's gradient.
    ds = dstate[...]
    dus, dk_outs, ddecays = [None] * nc, [None] * nc, [None] * nc
    for b in reversed(range(nc)):
        ds_read = ds.astype(dtype)
        du = _dot(ops["mt"][b], do[b], _NN) \
            + _dot(ops["k_out"][b], ds_read, _NN)
        dk_outs[b] = _dot(us[b], ds_read, _NT)
        ddecays[b] = _col_to_row(
            jnp.sum(states[b] * ds, axis=1, keepdims=True))
        ds = ds * ops["decay_col"][b] \
            + _dot(ops["q_in"][b], do[b], _TN) \
            - _dot(ops["w"][b], du.astype(dtype), _TN)
        dus[b] = du
    dstate[...] = ds

    du = jnp.stack(dus)                                     # [nc, C, Dv]
    u, read = jnp.stack(us), jnp.stack(reads)
    dq_in = flat(_bdot(do, read, _NT))
    dw = -_bdot(du.astype(dtype), read, _NT)
    j, i = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    dmt = flat(jnp.where(j <= i, _bdot(u, do, _NT), 0.0))   # [T, C]: [j, i]
    # [u0, w] = X [v, k_in]:  d[v, k_in] = X^T d[u0, w]  and
    # dL = -X^T dX X^T = -d[v, k_in] [u0, w]^T, strictly lower.
    drhs = _bdot(ops["inverse_t"], jnp.concatenate([du, dw], axis=2), _NN,
                 _HI)
    dlt = flat(jnp.where(j < i, -_bdot(ops["solved"], drhs, _NT, _HI), 0.0))
    dv = flat(drhs[..., :dv_width])
    dk_in = flat(drhs[..., dv_width:])
    dk_out = flat(jnp.stack(dk_outs))

    at_q, at_k = ops["at_q"], ops["at_k"]
    dbeta = jnp.sum(dmt * at_q + dlt * at_k, axis=1, keepdims=True)
    datq, datk = dmt * beta_col, dlt * beta_col             # [T, C]

    # The intra-chunk matrices: rows (i) and columns (j) apart, because
    # dG = q * dq + k * dk_row - k * dk_col.
    dq_a = jnp.zeros((t, dk), _F32)
    dk_row = jnp.zeros((t, dk), _F32)
    dk_col = jnp.zeros((t, dk), _F32)
    for f, kf, qf, mask in ops["levels"]:
        mq = jnp.where(mask, c3(datq), 0.0).astype(dtype)
        mk = jnp.where(mask, c3(datk), 0.0).astype(dtype)
        dq_a += flat(_bdot(mq, kf, _TN)) * f
        dk_row += flat(_bdot(mk, kf, _TN)) * f
        dk_col += flat(_bdot(mq, qf, _NN) + _bdot(mk, kf, _NN)) * f
    q3, k3, g3 = s3(q), s3(k), s3(ops["g_cum"])
    datq3, datk3, own = s3(datq), s3(datk), _own(n)
    sub = _iota((n, SUB, dk), 1)
    dq_d = jnp.zeros((n, SUB, dk), _F32)
    dkr_d = jnp.zeros((n, SUB, dk), _F32)
    dkc_d = jnp.zeros((n, SUB, dk), _F32)
    for r in range(SUB):
        e, p = _diagonal_pass(k3, g3, r)
        cq_r = jnp.sum(jnp.where(own == r, datq3, 0.0), axis=2, keepdims=True)
        ck_r = jnp.sum(jnp.where(own == r, datk3, 0.0), axis=2, keepdims=True)
        dq_d = jnp.where(
            sub == r, jnp.sum(cq_r * p, axis=1, keepdims=True), dq_d)
        dkr_d = jnp.where(
            sub == r, jnp.sum(ck_r * p, axis=1, keepdims=True), dkr_d)
        dkc_d += (cq_r * q3[:, r:r + 1, :] + ck_r * k3[:, r:r + 1, :]) * e
    dq_a, dk_row, dk_col = dq_a + flat(dq_d), dk_row + flat(dkr_d), \
        dk_col + flat(dkc_d)

    decay, out = ops["decay"], ops["out"]
    through_out = dk_out * k * out                          # d k_out * k_out / beta
    dbeta += jnp.sum(through_out, axis=1, keepdims=True)
    through_out = through_out * beta_col
    dlast = jnp.sum(c3(through_out), axis=1, keepdims=True) \
        + jnp.stack(ddecays) * ops["decay_out"]             # [nc, 1, Dk]
    is_last = _mod(_iota((t, 1), 0), CHUNK) == CHUNK - 1
    dg_cum = q * dq_a + k * (dk_row - dk_col) \
        + (dq_in * q + dk_in * k) * decay - through_out \
        + jnp.where(is_last, flat(jnp.broadcast_to(dlast, (nc, CHUNK, dk))),
                    0.0)
    dg_ref[0] = flat(_running_sum(c3(dg_cum), reverse=True))
    dq_ref[0] = (dq_a + dq_in * decay).astype(dq_ref.dtype)
    dk_ref[0] = (dk_row + dk_col + dk_in * decay
                 + dk_out * out * beta_col).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    db_ref[0, 0] = _col_to_row(dbeta)


def _block(s: int, block_t: int) -> "tuple[int, int]":
    """Tokens a grid step and the padded length: a sequence shorter than a
    block is one block, of whole chunks."""
    t = min(block_t, -(-s // CHUNK) * CHUNK)
    return t, -(-s // t) * t


def _wide(x, s_pad):
    """``[B, S, H, D] -> [B, S_pad, H * D]``, padded with zeros."""
    b, s, h, d = x.shape
    return jnp.pad(x.reshape(b, s, h * d), ((0, 0), (0, s_pad - s), (0, 0)))


def _per_head(beta, s_pad):
    """``[B, S, H] -> [B, H, 1, S_pad]`` in f32."""
    b, s, h = beta.shape
    beta = jnp.pad(beta.astype(_F32), ((0, 0), (0, s_pad - s), (0, 0)))
    return beta.transpose(0, 2, 1)[:, :, None, :]


def _specs(t, dk, dv, nb, reverse):
    """Block specs of q / k / g (``Dk`` wide), v / o (``Dv``), beta and the
    saved states, for a grid ``(row, head, block)``; ``reverse`` takes the
    blocks last to first."""
    at = (lambda i: nb - 1 - i) if reverse else (lambda i: i)
    wide = lambda d: pl.BlockSpec((1, t, d), lambda b, h, i: (b, at(i), h))
    beta = pl.BlockSpec((1, 1, 1, t), lambda b, h, i: (b, h, 0, at(i)))
    state = pl.BlockSpec((1, 1, 1, dk, dv),
                         lambda b, h, i: (b, h, at(i), 0, 0))
    return wide(dk), wide(dv), beta, state


_SEMANTICS = ("parallel", "parallel", "arbitrary")


# Jitted, so that the layers of a model that call it at one shape trace the
# kernel's body once: a trace is a second or two of Python, eight of them a
# program were 20 s of the cell's set-up (my chip runs, PR 29).
@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _fwd_call(q, k, v, g, beta, *, interpret, block_t):
    """``q, k, g: [B, S_pad, H * Dk]``, ``v: [B, S_pad, H * Dv]``, ``beta:
    [B, H, 1, S_pad]``; returns o like v and the states at the start of
    every block, ``[B, H, S_pad / T, Dk, Dv]`` f32."""
    b, s_pad, _ = q.shape
    h = beta.shape[1]
    dk, dv = q.shape[2] // h, v.shape[2] // h
    t, nb = block_t, s_pad // block_t
    key, value, per_head, state = _specs(t, dk, dv, nb, reverse=False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(b, h, nb),
        in_specs=[key, key, value, key, per_head],
        out_specs=[value, state],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, h, nb, dk, dv), _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        **_named("kda_fwd"),
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("interpret", "block_t"))
def _bwd_call(q, k, v, g, beta, starts, do, *, interpret, block_t):
    b, s_pad, _ = q.shape
    h = beta.shape[1]
    dk, dv = q.shape[2] // h, v.shape[2] // h
    t, nb = block_t, s_pad // block_t
    key, value, per_head, state = _specs(t, dk, dv, nb, reverse=True)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(b, h, nb),
        in_specs=[key, key, value, key, per_head, state, value],
        out_specs=[key, key, value, key, per_head],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(g.shape, _F32),
                   jax.ShapeDtypeStruct(beta.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((dk, dv), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        **_named("kda_bwd"),
    )(q, k, v, g, beta, starts, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda(q, k, v, g, beta, interpret, block_t):
    return _kda_fwd(q, k, v, g, beta, interpret, block_t)[0]


def _kda_fwd(q, k, v, g, beta, interpret, block_t):
    b, s, h, _ = q.shape
    t, s_pad = _block(s, block_t)
    wide = functools.partial(_wide, s_pad=s_pad)
    o, starts = _fwd_call(
        wide(q), wide(k), wide(v), wide(g.astype(_F32)),
        _per_head(beta, s_pad), interpret=interpret, block_t=t)
    return o[:, :s].reshape(b, s, h, v.shape[-1]), (q, k, v, g, beta, starts)


def _kda_bwd(interpret, block_t, res, do):
    q, k, v, g, beta, starts = res
    b, s, h, dk = q.shape
    t, s_pad = _block(s, block_t)
    wide = functools.partial(_wide, s_pad=s_pad)
    dq, dk_, dv, dg, db = _bwd_call(
        wide(q), wide(k), wide(v), wide(g.astype(_F32)),
        _per_head(beta, s_pad), starts, wide(do.astype(v.dtype)),
        interpret=interpret, block_t=t)
    narrow = lambda x, like: x[:, :s].reshape(like.shape).astype(like.dtype)
    db = db[:, :, 0, :s].transpose(0, 2, 1).astype(beta.dtype)
    return narrow(dq, q), narrow(dk_, k), narrow(dv, v), narrow(dg, g), db


_kda.defvjp(_kda_fwd, _kda_bwd)


def supports(q, v) -> bool:
    """Whether the kernels take these widths: whole lane tiles."""
    return q.shape[-1] % LANE == 0 and v.shape[-1] % LANE == 0


def kda_kernels(q, k, v, g, beta, *, impl: str = "mosaic"):
    """`ops.kda.kda_chunked` as Pallas kernels, with a hand-written
    backward: same arguments, same result up to rounding.  ``impl="mosaic"``
    compiles for the TPU and nowhere else; ``"interpret"`` runs the kernel
    bodies under the Pallas interpreter."""
    if not supports(q, v):
        raise ValueError(
            f"the KDA kernels take head widths that are multiples of {LANE}, "
            f"got Dk {q.shape[-1]} and Dv {v.shape[-1]}")
    return _kda(q, k, v, g, beta, use_interpreter(impl), BLOCK_T)
