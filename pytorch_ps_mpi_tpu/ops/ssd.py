"""State-space duality (Mamba-2's SSD): a linear recurrence whose decay is
one scalar a head and a token, computed a chunk of tokens at a time as
matrix products.

A row keeps, for each of ``H`` heads, a state ``S`` of ``[P, N]`` (f32) and
reads one token at a time::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S_0 = 0
    y_t = S_t C_t + D x_t

with ``x_t: [P]`` (the head's slice of the mixer's input), ``dt_t > 0`` (the
step, after its softplus), ``A < 0`` and ``D`` a head, and ``B_t, C_t: [N]``
shared by the ``H / G`` heads of each of ``G`` groups (head ``h`` reads group
``h // (H / G)``).  Because the decay is a scalar, the recurrence over one
chunk of ``Q`` tokens is a masked attention-like product (Dao & Gu 2024,
arXiv:2405.21060, section 6):

* inside a chunk, ``y_i = sum_{j <= i} L_ij (C_i . B_j) dt_j x_j`` with
  ``L_ij = exp(sum_{j < k <= i} dt_k A)``: ``C B^T`` a group and the masked
  product ``(L o C B^T)(dt x)`` a head;
* the chunk's own contribution to the state at its end, ``sum_j exp(sum_{j <
  k <= Q} dt_k A) dt_j x_j B_j^T``;
* those states carried from chunk to chunk, ``S_c = exp(sum_{chunk c-1} dt A)
  S_{c-1} + (chunk c-1's own)``, one state a chunk and no more (a `lax.scan`
  of ``n_chunks`` steps);
* the output's part from the state a chunk starts with, ``exp(sum_{k <= i}
  dt_k A) C_i . S_c``.

**Every exponent is a difference of running sums of ``dt A`` taken inside one
chunk, over tokens that exist, so it is never positive**: no ``exp(-G_j)``
that overflows where a chunk's decay underflows (`ops/kda.py` had to learn
that), and where it underflows its factor is 0.  Masked entries take their
exponent from ``-inf``, never from a positive difference times zero.

Two implementations compute it.  `ssd_chunked` is plain `jax.numpy`,
differentiated by JAX: its backward keeps the chunk-sized products and one
state a chunk, never one a token.  Its products take their operands in f32
and the platform's default precision for them (on the TPU, bf16 operands
with f32 accumulation, as the model's other products).  The Pallas kernels
of `ops/ssd_pallas.py` (`ssd_fwd`, `ssd_bwd`) build each chunk's decay
matrices and carry the state in VMEM, with a hand-written backward, at the
widths they take (heads of 64 in pairs, a state of 128).  `ssd`, at the
end of this file, picks between them from what the program can observe;
it sits under `jax.named_scope("ssd")` where its caller puts it, and so do
both kernels.  `ssd_loop` is the recurrence as written above, one token a
step, differentiated by JAX: the oracle of the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import ssd_pallas


def _groups(B, heads: int):
    """``[rows, S, G, N] -> [rows, S, H, N]``: each group's ``B`` or ``C``
    for each of its ``H / G`` heads."""
    return jnp.repeat(B, heads // B.shape[2], axis=2)


def ssd_loop(x, dt, A, B, C, D):
    """The recurrence one token a step, f32, differentiated by JAX.  ``x:
    [rows, S, H, P]``, ``dt: [rows, S, H]``, ``A, D: [H]``, ``B, C: [rows,
    S, G, N]`` -> ``y: [rows, S, H, P]`` f32."""
    x, dt, A, B, C, D = (a.astype(jnp.float32) for a in (x, dt, A, B, C, D))
    h = x.shape[2]
    B, C = _groups(B, h), _groups(C, h)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * A)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        y_t = jnp.einsum("rhpn,rhn->rhp", state, c_t) + D[:, None] * x_t
        return state, y_t

    rows, _, _, p = x.shape
    s0 = jnp.zeros((rows, h, p, B.shape[-1]), jnp.float32)
    _, y = lax.scan(token, s0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _chunked(a, chunk: int):
    """``[rows, S, ...] -> [rows, S / chunk, chunk, ...]`` in f32."""
    return a.astype(jnp.float32).reshape(
        a.shape[0], a.shape[1] // chunk, chunk, *a.shape[2:])


def _check_length(s: int, chunk: int) -> None:
    if s % chunk:
        raise ValueError(f"a length of {s} tokens is not a whole number of "
                         f"chunks of {chunk}")


def ssd_chunked(x, dt, A, B, C, D, *, chunk: int):
    """``y_t = S_t C_t + D x_t`` of the recurrence in the module's
    docstring, exact, in chunks of ``chunk`` tokens, in plain `jax.numpy`.  ``x: [rows, S, H,
    P]``, ``dt: [rows, S, H]`` (after its softplus), ``A, D: [H]`` (``A <
    0``), ``B, C: [rows, S, G, N]`` with ``G`` dividing ``H`` -> ``y:
    [rows, S, H, P]`` f32.  ``S`` has to be a whole number of chunks."""
    rows, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    _check_length(s, chunk)
    k = h // g                                   # heads a group
    x = _chunked(x, chunk)                       # [r, c, Q, H, P]
    dt = _chunked(dt, chunk)                     # [r, c, Q, H]
    B, C = _chunked(B, chunk), _chunked(C, chunk)   # [r, c, Q, G, N]
    A = A.astype(jnp.float32)
    cum = jnp.cumsum(dt * A, axis=2)             # [r, c, Q, H], falling
    total = cum[:, :, -1]                        # [r, c, H]
    u = (dt[..., None] * x).reshape(*x.shape[:3], g, k, p)   # dt x by group

    # Inside a chunk: (L o C B^T)(dt x), L_ij = exp(cum_i - cum_j), j <= i.
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = cum[:, :, :, None, :] - cum[:, :, None, :, :]   # [r, c, i, j, H]
    decay = jnp.exp(jnp.where(causal[:, :, None], diff, -jnp.inf))
    decay = jnp.moveaxis(decay, -1, 2).reshape(rows, -1, g, k, chunk, chunk)
    scores = jnp.einsum("rcign,rcjgn->rcgij", C, B)
    y = jnp.einsum("rcgkij,rcjgkp->rcigkp", decay * scores[:, :, :, None], u)

    # Each chunk's own part of the state at its end, exp(total - cum_j).
    to_end = jnp.exp(total[:, :, None] - cum).reshape(*cum.shape[:3], g, k)
    own = jnp.einsum("rcjgn,rcjgkp->rcgkpn", B, u * to_end[..., None])

    # The states chunks start from, carried one chunk at a time.
    def carry(state, inp):
        own_c, total_c = inp
        return jnp.exp(total_c)[..., None, None] * state + own_c, state

    zero = jnp.zeros((rows, g, k, p, n), jnp.float32)
    _, starts = lax.scan(carry, zero, (
        jnp.moveaxis(own, 1, 0),
        jnp.moveaxis(total.reshape(rows, -1, g, k), 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)          # [r, c, G, K, P, N]
    into = jnp.exp(cum).reshape(*cum.shape[:3], g, k)
    y = y + into[..., None] * jnp.einsum("rcign,rcgkpn->rcigkp", C, starts)

    y = y.reshape(rows, s, h, p)
    skip = D.astype(jnp.float32)[:, None] * x.reshape(rows, s, h, p)
    return y + skip


def ssd(x, dt, A, B, C, D, *, chunk: int):
    """The scan by the implementation the program can see it needs;
    arguments and result as `ssd_chunked`.

    Two things decide, neither of them a setting.  The shapes: the kernels
    of `ssd_pallas` take heads of 64 in pairs, a state of 128 and a length
    of whole chunks (`ssd_pallas.supports`); any other shape is
    `ssd_chunked`.  And the platform the program is lowered for
    (`lax.platform_dependent`, not the process's default backend): a TPU
    gets the Mosaic kernels, everything else `ssd_chunked`.  The kernels
    take their own chunk, `ssd_pallas.CHUNK`: the scan is exact for any."""
    _check_length(x.shape[1], chunk)
    if not ssd_pallas.supports(x, B, chunk):
        return ssd_chunked(x, dt, A, B, C, D, chunk=chunk)
    return _for_the_platform(x, dt, A, B, C, D, chunk=chunk)


@functools.partial(jax.jit, static_argnames="chunk")
def _for_the_platform(x, dt, A, B, C, D, *, chunk):
    """Both implementations are traced, and differentiated, whatever the
    platform, and the one that is lowered is picked then: under `jax.jit`
    the layers of a model that call this at one shape share that work."""
    return lax.platform_dependent(
        x, dt, A, B, C, D, tpu=ssd_pallas.ssd_kernels,
        default=functools.partial(ssd_chunked, chunk=chunk))


def carried_share(dt, A, *, chunk: int):
    """Mean over rows, heads and chunks of ``exp(sum_chunk dt A)``: the
    share of a head's state that crosses one whole chunk, i.e. how much the
    carried state still weighs after ``chunk`` tokens.  ``dt: [rows, S,
    H]``, ``A: [H]`` -> a scalar f32."""
    _check_length(dt.shape[1], chunk)
    total = jnp.sum(_chunked(dt, chunk) * A.astype(jnp.float32), axis=2)
    return jnp.mean(jnp.exp(total))
