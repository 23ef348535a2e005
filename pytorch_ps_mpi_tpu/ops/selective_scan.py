"""Selective state-space scan (Mamba-1, "S6"): a diagonal linear recurrence
whose step, input matrix and output matrix depend on the token.

A row keeps a state ``h`` of ``[d_inner, N]`` (f32) and reads one token at
a time::

    h_t = exp(dt_t[:, None] * A) * h_{t-1} + (dt_t * x_t)[:, None] * B_t[None]
    y_t = h_t @ C_t + D * x_t                      h_0 = 0

with ``x_t, dt_t: [d_inner]`` (``dt_t > 0`` the step, after its softplus),
``A: [d_inner, N]`` (negative), ``B_t, C_t: [N]`` and ``D: [d_inner]``.
Nothing in it is a matrix product: a token is ``d_inner * N`` independent
multiply-adds, and the states of a sequence (``[S, d_inner, N]`` f32, 2.7 GB
a row at 8192 x 5120 x 16) can be kept neither for the output nor for the
backward pass.

`selective_scan` is exact and keeps **one state a block of tokens**: the
forward walks the tokens block by block and saves the state each block
starts from; the backward (``jax.custom_vjp``, residuals = the inputs and
those states) takes the blocks from the last to the first, recomputes a
block's states from its start and runs the adjoint recurrence ``dh_{t-1} =
exp(dt_t A) * dh_t`` back through it, all of it written out by hand so that
nothing of ``[S, d_inner, N]`` ever exists.  The state is held as ``[rows,
N, d_inner]``: the channels on the 128 lanes, the 16 states on sublanes
(the other order, `selective_scan_loop`'s, is 7 times slower on the chip).

That scheme twice: as the Pallas kernels of `ops/selective_scan_pallas.py`
(`ssm_fwd`, `ssm_bwd`), which a program lowered for the TPU gets at sizes
that are whole tiles, and here as `_scan`, plain `jax.numpy` over a
`lax.scan` of blocks of a `lax.scan` of tokens, which everything else gets;
`selective_scan`, at the end of this file, picks from what the program can
observe.  `selective_scan_loop` is the recurrence as written above, one
token a step and differentiated by JAX: the oracle of the tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import selective_scan_pallas as ssm

BLOCK = 128    # tokens whose states are recomputed from the block's start
# One layer at [1, 8192, 5120, 16], x in bf16, on the v5e (my chip runs,
# PR 35; host clock, best of three; ms forward / forward and backward):
#
#   `_scan`, 64 / 128 / 256 tokens a block  3.35/11.21  3.47/11.17  3.48/11.27
#   ... token loops unrolled 1 / 8 / 16     3.44/11.15  3.47/11.17  3.35/11.14
#   `selective_scan_loop` ([rows, d_inner, N])   24.87 forward
#   the kernels, (tokens, channels) a grid step:
#    (64, 1280) 3.00/10.88   (128, 1280) 2.94/10.80   (64, 2560) 3.09/12.80
#    (64,  512) 4.02/11.97   (128,  640) 3.62/10.97   (32, 2560) 3.13/12.63
#    (64, 5120) 3.71/14.22
#
# Alone, the plain form is as fast as the kernels: XLA keeps the loops' state
# in VMEM and neither the block nor the unrolling matters (so there is no
# unrolling).  **Inside the cell's step it is not**: the same loops take 69.8
# ms a step where the kernels take 22.9 (`phi_ssm_ms_step`; the step 462.2
# against 414.9 ms): what the compiler places in VMEM depends on the program
# round the loop, a kernel's scratch does not.


def selective_scan_loop(x, dt, A, B, C, D):
    """The recurrence one token a step, f32, differentiated by JAX.
    ``x, dt: [rows, S, d_inner]``, ``A: [d_inner, N]``, ``B, C: [rows, S,
    N]``, ``D: [d_inner]`` -> ``y: [rows, S, d_inner]`` f32."""
    x, dt, A, B, C, D = (a.astype(jnp.float32) for a in (x, dt, A, B, C, D))

    def token(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bdn,bn->bd", h, c_t) + D * x_t

    h0 = jnp.zeros((x.shape[0], *A.shape), jnp.float32)
    _, y = lax.scan(token, h0, tuple(
        jnp.moveaxis(a, 1, 0) for a in (x, dt, B, C)))
    return jnp.moveaxis(y, 0, 1)


def _blocks(a, n: int, block: int):
    """``[rows, S, ...] -> [n, block, rows, ...]`` f32, zeros past ``S``
    (a step of zero leaves the state as it is)."""
    a = a.astype(jnp.float32)
    a = jnp.pad(a, ((0, 0), (0, n * block - a.shape[1]))
                + ((0, 0),) * (a.ndim - 2))
    return jnp.moveaxis(a, 1, 0).reshape(n, block, a.shape[0], *a.shape[2:])


def _unblocks(a, s: int):
    """``[n, block, rows, ...] -> [rows, S, ...]``."""
    a = a.reshape(a.shape[0] * a.shape[1], *a.shape[2:])[:s]
    return jnp.moveaxis(a, 0, 1)


def _step(At, h, x_t, dt_t, b_t):
    """One token: ``(exp(dt_t A), h_t)``; ``h: [rows, N, d_inner]``."""
    a = jnp.exp(dt_t[:, None, :] * At)
    return a, a * h + (dt_t * x_t)[:, None, :] * b_t[:, :, None]


def _scan_fwd(x, dt, At, B, C, block):
    """``(y [rows, S, d_inner] f32 without the D skip, the state each block
    starts from [n, rows, N, d_inner])``."""
    rows, s, _ = x.shape
    n = -(-s // block)

    def token(h, inp):
        x_t, dt_t, b_t, c_t = inp
        _, h = _step(At, h, x_t, dt_t, b_t)
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    def many(h, inp):
        h_end, y = lax.scan(token, h, inp)
        return h_end, (h, y)

    h0 = jnp.zeros((rows, *At.shape), jnp.float32)
    _, (starts, y) = lax.scan(many, h0, tuple(
        _blocks(a, n, block) for a in (x, dt, B, C)))
    return _unblocks(y, s), starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, At, B, C, block):
    return _scan_fwd(x, dt, At, B, C, block)[0]


def _scan_vjp_fwd(x, dt, At, B, C, block):
    y, starts = _scan_fwd(x, dt, At, B, C, block)
    return y, (x, dt, At, B, C, starts)


def _scan_vjp_bwd(block, res, dy):
    x, dt, At, B, C, starts = res
    rows, s, _ = x.shape
    n = starts.shape[0]

    def before(h, inp):
        """The state each token of a block starts from."""
        x_t, dt_t, b_t = inp
        return _step(At, h, x_t, dt_t, b_t)[1], h

    def token(carry, inp):
        dh, dA = carry
        x_t, dt_t, b_t, c_t, dy_t, h_prev = inp
        a, h = _step(At, h_prev, x_t, dt_t, b_t)
        dh = dh + dy_t[:, None, :] * c_t[:, :, None]
        dc_t = jnp.sum(dy_t[:, None, :] * h, axis=2)
        db_t = jnp.sum(dh * (dt_t * x_t)[:, None, :], axis=2)
        into_u = jnp.sum(dh * b_t[:, :, None], axis=1)     # d / d(dt_t x_t)
        into_a = dh * h_prev * a                           # d / d(dt_t A)
        ddt_t = jnp.sum(into_a * At, axis=1) + into_u * x_t
        dA = dA + jnp.sum(into_a * dt_t[:, None, :], axis=0)
        return (a * dh, dA), (into_u * dt_t, ddt_t, db_t, dc_t)

    def many(carry, inp):
        start, x_b, dt_b, b_b, c_b, dy_b = inp
        _, h_prev = lax.scan(before, start, (x_b, dt_b, b_b))
        return lax.scan(token, carry, (x_b, dt_b, b_b, c_b, dy_b, h_prev),
                        reverse=True)

    zero = jnp.zeros((rows, *At.shape), jnp.float32)
    (_, dA), grads = lax.scan(
        many, (zero, jnp.zeros_like(At)),
        (starts, *(_blocks(a, n, block) for a in (x, dt, B, C, dy))),
        reverse=True)
    dx, ddt, dB, dC = (_unblocks(g, s).astype(a.dtype)
                       for g, a in zip(grads, (x, dt, B, C)))
    return dx, ddt, dA.astype(At.dtype), dB, dC


_scan.defvjp(_scan_vjp_fwd, _scan_vjp_bwd)


def selective_scan(x, dt, A, B, C, D, *, impl: "str | None" = None):
    """``y_t = h_t C_t + D x_t`` of the recurrence in the module's
    docstring, exact, every step in f32 whatever the inputs' types.  ``x,
    dt: [rows, S, d_inner]``, ``A: [d_inner, N]``, ``B, C: [rows, S, N]``,
    ``D: [d_inner]`` -> ``y: [rows, S, d_inner]`` in ``x``'s type.
    Differentiable in all six; the backward keeps the inputs and one state
    a block of tokens and recomputes the rest.

    With ``impl=None`` two things decide, neither of them a setting: the
    sizes (the kernels of `selective_scan_pallas` take channels in whole lane
    tiles and states in whole sublane tiles: the published 5120 and 16) and
    the platform the program is lowered for (`lax.platform_dependent`): a
    TPU gets the Mosaic kernels, everything else the blocked plain form.
    Tests name ``impl``: ``"ref"`` is the plain form, ``"mosaic"`` and
    ``"interpret"`` are the kernels."""
    at = A.astype(jnp.float32).T
    if impl == "ref" or (impl is None and not ssm.supports(x, at)):
        y = _blocked(x, dt, at, B, C)
    elif impl is not None:
        y = ssm.ssm_kernels(x, dt, at, B, C, impl=impl)
    else:
        y = _for_the_platform(x, dt, at, B, C)
    return (y + D.astype(jnp.float32) * x.astype(jnp.float32)).astype(x.dtype)


def _blocked(x, dt, at, B, C):
    return _scan(x, dt, at, B, C, min(BLOCK, x.shape[1]))


@jax.jit
def _for_the_platform(x, dt, at, B, C):
    """Both implementations are traced, and differentiated, whatever the
    platform, and the one that is lowered is picked then; under `jax.jit`,
    so that the layers of a model that call this at one shape share that
    work."""
    return lax.platform_dependent(
        x, dt, at, B, C, tpu=ssm.ssm_kernels, default=_blocked)
