"""Kimi Delta Attention (KDA): a gated delta rule with a per-channel decay,
in its chunked form.

Per head the layer keeps a state ``S`` of ``[d_k, d_v]`` and reads one token
at a time (``g_t <= 0`` is the log-decay of each of the ``d_k`` channels,
``beta_t`` in (0, 1) the write strength)::

    S   <- Diag(exp g_t) S
    S   <- S + beta_t k_t (v_t - S^T k_t)^T
    o_t  = S^T q_t

Written so, a sequence of 8192 tokens is 8192 dependent steps of matrix-vector
size.  `kda_chunked` computes the same thing chunk by chunk (the WY form of
the delta rule): inside a chunk of ``C`` tokens everything is a matrix
product, and only the state is carried from chunk to chunk by a `lax.scan`
of ``S / C`` steps.  With ``G`` the running sum of ``g`` inside the chunk::

    Akk[i, j] = sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])      (j <  i)
    Aqk[i, j] = sum_d q_i[d] k_j[d] exp(G_i[d] - G_j[d])      (j <= i)
    (I + Akk Diag(beta)) U = V - (K * exp G) S0               (unit lower-triangular)
    O   = (Q * exp G) S0 + Aqk Diag(beta) U
    S_C = Diag(exp G_C) S0 + (K * exp(G_C - G))^T Diag(beta) U

**No exponent is ever positive.**  ``exp(G_i - G_j)`` does not factor into a
row term times a column term safely: ``exp(-G_j)`` overflows f32 once a
channel has decayed by e^88 inside a chunk, which a decay of 1.4 a token
does in 64 tokens.  So a chunk is cut into sub-chunks of ``c`` tokens.  A
block below the diagonal factors through the first row ``r`` of its row
sub-chunk, ``exp(G_i - G_r) * exp(G_r - G_j)``, both at most 1 because
``j < r <= i``; a block on the diagonal is computed element by element
(``c * c * d_k`` exponentials, all of non-positive arguments).

Plain `jax.numpy`: differentiable by JAX, no kernel.  Memory: the state is
saved once a chunk for the backward pass (``S / C * d_k * d_v`` a head, not
``S * d_k * d_v``), everything else is recomputed (`jax.checkpoint` round
the scan's body and round the intra-chunk matrices, which run a few chunks
at a time so that the element-by-element diagonal blocks never exist for
the whole sequence at once).

`kda_chunked` is what runs off the chip and what every kernel test compares
with.  On the chip, at head widths of whole lane tiles, the same recurrence
runs as the Pallas kernels of `ops/kda_pallas.py`; `kda_attention`, at the
end of this file, picks between them from what the program can observe.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

from . import kda_pallas

CHUNK = 32       # tokens a step of the state scan
SUB_CHUNK = 8    # side of the blocks computed element by element
GROUP = 4        # chunks whose intra-chunk matrices are alive together
# From a sweep on the v5e, one layer forward and backward at [1, 8192, 32,
# 128] in bf16 (my chip runs, PR 28), as (chunk, sub-chunk, group) -> ms.
# With `solve_triangular` on the whole chunk: (64, 16, 8) 91, of which 36 in
# the solves and 23 in the diagonal blocks; (128, 16, 4) 125.  With the
# blocked inverse below: (64, 16, 8) 70, (64, 8, 8) 64, (64, 8, 4) 52,
# (64, 8, 2) 48, (32, 8, 8) 47, **(32, 8, 4) 43**, (32, 4, 8) 49,
# (16, 8, 16) 44, (128, 8, 4) 81.  The state scan itself is 5 ms of that;
# the rest is the intra-chunk work, which likes a small working set.


def _diagonal_blocks(m, size: int):
    """``[..., C, C] -> [..., C / size, size, size]``: the blocks on the
    diagonal."""
    *lead, c, _ = m.shape
    n = c // size
    blocks = jnp.diagonal(m.reshape(*lead, n, size, n, size),
                          axis1=-4, axis2=-2)               # [..., s, s, n]
    return jnp.moveaxis(blocks, -1, -3)


def _inverse_unit_lower(lower, block: int):
    """``(I + lower)^-1`` for a strictly lower-triangular ``[..., C, C]`` in
    f32.  The ``block``-sized blocks on the diagonal are inverted by forward
    substitution (`solve_triangular`, which XLA runs column by column on
    the TPU: on the whole 64 x 64 it took 40 % of the layer); neighbouring
    inverses are then merged pair by pair with matrix products,
    ``[[A, 0], [L, B]]^-1 = [[A^-1, 0], [-B^-1 L A^-1, B^-1]]``, until one
    block is left.  (The product form ``(I + X)(I + X^2)(I + X^4)...`` of
    the Neumann series needs no solve at all and is not used: its terms
    grow like binomial coefficients before they cancel, and keys that
    resemble each other inside a chunk make them large.)"""
    c = lower.shape[-1]
    highest = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)
    system = jnp.eye(c, dtype=lower.dtype) + lower
    diagonal = _diagonal_blocks(system, block)
    inverse = solve_triangular(
        diagonal, jnp.broadcast_to(jnp.eye(block, dtype=lower.dtype),
                                   diagonal.shape),
        lower=True, unit_diagonal=True)
    size = block
    while size < c:
        below = _diagonal_blocks(system, 2 * size)[..., size:, :size]
        first, second = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        corner = -highest(highest(second, below), first)
        inverse = jnp.concatenate([
            jnp.concatenate([first, jnp.zeros_like(first)], axis=-1),
            jnp.concatenate([corner, second], axis=-1)], axis=-2)
        size *= 2
    return inverse[..., 0, :, :]


def _intra_chunk(q, k, g_cum, *, dtype):
    """``Aqk`` (lower triangle with the diagonal) and ``Akk`` (strictly
    lower) of every chunk.  ``q, k: [..., C, Dk]`` in f32, ``g_cum`` the
    running sum of the log-decay inside the chunk.  Returns two
    ``[..., C, C]`` in f32."""
    *lead, c_len, dk = q.shape
    sub = SUB_CHUNK
    n_sub = c_len // sub
    blocks = lambda x: x.reshape(*lead, n_sub, sub, dk)
    x = jnp.stack([q, k], axis=-3)                          # [..., 2, C, Dk]
    xb = x.reshape(*lead, 2, n_sub, sub, dk)
    gb, kb = blocks(g_cum), blocks(k)
    first = gb[..., :1, :]                                  # [..., n, 1, Dk]

    # Below the diagonal: row sub-chunk a against every earlier column.
    rows = (xb * jnp.exp(gb - first)[..., None, :, :, :]).astype(dtype)
    col_pos = jnp.arange(c_len)
    earlier = col_pos[None, :] < (jnp.arange(n_sub) * sub)[:, None]  # [n, C]
    shift = first - g_cum[..., None, :, :]                  # [..., n, C, Dk]
    cols = (k[..., None, :, :] * jnp.exp(jnp.minimum(shift, 0.0))
            * earlier[..., None]).astype(dtype)
    below = jnp.einsum("...tnid,...njd->...tnij", rows, cols,
                       preferred_element_type=jnp.float32)  # [..., 2,n,c,C]
    below = below.reshape(*lead, 2, c_len, c_len)

    # On the diagonal: element by element, one reduction for q and k both.
    diff = gb[..., :, None, :] - gb[..., None, :, :]        # [..., n,c,c,Dk]
    i, j = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    decay = jnp.exp(jnp.where((j <= i)[..., None], diff, -jnp.inf))
    diag = jnp.sum(xb[..., :, None, :] * (kb[..., None, :, :] * decay)
                   [..., None, :, :, :, :], axis=-1)        # [..., 2,n,c,c]
    strict = jnp.stack([j <= i, j < i]).reshape(2, 1, sub, sub)
    diag = jnp.where(strict, diag, 0.0)
    eye = jnp.eye(n_sub, dtype=diag.dtype)
    on = jnp.einsum("...tnij,nm->...tnimj", diag, eye)
    on = on.reshape(*lead, 2, c_len, c_len)
    a = below + on
    return a[..., 0, :, :], a[..., 1, :, :]


def _chunk_operands(q, k, v, g, beta, *, dtype):
    """Everything one chunk needs besides the incoming state, for a few
    chunks at once (leading dims ``[..., C, D]``)."""
    q, k, v, g, beta = (x.astype(jnp.float32) for x in (q, k, v, g, beta))
    g_cum = jnp.cumsum(g, axis=-2)
    aqk, akk = _intra_chunk(q, k, g_cum, dtype=dtype)
    decay = jnp.exp(g_cum)
    k_in = k * decay                                        # reads S0
    solved = jnp.matmul(
        _inverse_unit_lower(akk * beta[..., None, :], SUB_CHUNK),
        jnp.concatenate([v, k_in], axis=-1),
        precision=lax.Precision.HIGHEST)
    u0, w = solved[..., :v.shape[-1]], solved[..., v.shape[-1]:]
    last = g_cum[..., -1:, :]
    return {
        "u0": u0.astype(dtype), "w": w.astype(dtype),
        "q_in": (q * decay).astype(dtype),
        "aqk": (aqk * beta[..., None, :]).astype(dtype),
        "k_out": (k * jnp.exp(last - g_cum) * beta[..., None]).astype(dtype),
        "decay_out": jnp.exp(last[..., 0, :]),
    }


def kda_chunked(q, k, v, g, beta):
    """The KDA recurrence over ``q, k: [B, S, H, Dk]``, ``v: [B, S, H, Dv]``,
    ``g: [B, S, H, Dk]`` (log-decay, <= 0) and ``beta: [B, S, H]``, from a
    zero state; returns ``o: [B, S, H, Dv]`` in v's dtype.

    The matrix products read v's dtype and accumulate in f32; decays, the
    triangular solve and the carried state are f32.  ``S`` need not be a
    multiple of `CHUNK`: the tail is padded with tokens that decay nothing
    and write nothing.  The rows of a batch are taken one after another
    (`lax.map`): one sequence of 8192 tokens fills the chip, and the
    intra-chunk temporaries of the backward pass, a few hundred MB a row,
    are then alive for one row at a time."""
    if q.shape[0] > 1:
        return lax.map(lambda row: kda_chunked(*(x[None] for x in row))[0],
                       (q, k, v, g, beta))
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    dtype, chunk = v.dtype, CHUNK
    if chunk % SUB_CHUNK or (chunk // SUB_CHUNK) & (chunk // SUB_CHUNK - 1):
        raise ValueError(f"CHUNK {chunk} is no power-of-two multiple of "
                         f"SUB_CHUNK {SUB_CHUNK}")
    n = -(-s // chunk)
    group = min(GROUP, n)
    n_pad = -(-n // group) * group

    def chunks(x):
        """[B, S, H, ...] -> [N/group, group, B, H, C, ...], padded with
        zeros (g = 0 decays nothing, beta = 0 writes nothing)."""
        if x.ndim == 3:
            x = x[..., None]
        x = jnp.pad(x, ((0, 0), (0, n_pad * chunk - s), (0, 0), (0, 0)))
        x = x.reshape(b, n_pad // group, group, chunk, h, x.shape[-1])
        return x.transpose(1, 2, 0, 4, 3, 5)

    prepare = jax.checkpoint(functools.partial(_chunk_operands, dtype=dtype))
    ops = lax.map(lambda a: prepare(a[0], a[1], a[2], a[3], a[4][..., 0]),
                  (chunks(q), chunks(k), chunks(v),
                   chunks(g.astype(jnp.float32)),
                   chunks(beta.astype(jnp.float32))))
    # [N/group, group, B, H, ...] -> [N, B, H, ...]: the scan's steps.
    ops = jax.tree.map(lambda x: x.reshape(n_pad, *x.shape[2:]), ops)

    @jax.checkpoint
    def step(state, c):
        read = state.astype(dtype)
        u = c["u0"].astype(jnp.float32) - jnp.matmul(
            c["w"], read, preferred_element_type=jnp.float32)
        u = u.astype(dtype)
        out = jnp.matmul(c["q_in"], read, preferred_element_type=jnp.float32) \
            + jnp.matmul(c["aqk"], u, preferred_element_type=jnp.float32)
        state = c["decay_out"][..., None] * state + jnp.matmul(
            jnp.swapaxes(c["k_out"], -1, -2), u,
            preferred_element_type=jnp.float32)
        return state, out.astype(v.dtype)

    _, out = lax.scan(step, jnp.zeros((b, h, dk, dv), jnp.float32), ops)
    out = out.transpose(1, 0, 3, 2, 4)                      # [B, N, C, H, Dv]
    return out.reshape(b, n_pad * chunk, h, dv)[:, :s]


def kda_attention(q, k, v, g, beta, *, impl: "str | None" = None):
    """The KDA recurrence by the implementation the program can see it
    needs; arguments and result as `kda_chunked`.

    With ``impl=None`` two things decide, neither of them a setting.  The
    widths: the kernels of `kda_pallas` take ``Dk`` and ``Dv`` that are whole
    lane tiles (the published 128); any other width is `kda_chunked`.  And
    the platform the program is lowered for (`lax.platform_dependent`, not
    the process's default backend): a TPU gets the Mosaic kernels with their
    hand-written backward, everything else `kda_chunked`, differentiated by
    JAX — neither stands in for the other on its own platform.  Tests name
    ``impl``: ``"ref"`` is `kda_chunked`, ``"mosaic"`` and ``"interpret"``
    are the kernels (`ops.pallas_kernels.use_interpreter`)."""
    if impl == "ref" or (impl is None and not kda_pallas.supports(q, v)):
        return kda_chunked(q, k, v, g, beta)
    if impl is not None:
        return kda_pallas.kda_kernels(q, k, v, g, beta, impl=impl)
    return _for_the_platform(q, k, v, g, beta)


@jax.jit
def _for_the_platform(q, k, v, g, beta):
    """Both implementations are traced, and differentiated, whatever the
    platform, and the one that is lowered is picked then: under `jax.jit`
    the layers of a model that call this at one shape share that work."""
    return lax.platform_dependent(
        q, k, v, g, beta, tpu=kda_pallas.kda_kernels, default=kda_chunked)
