"""The selective scan (`ops/selective_scan.py`) as two Pallas TPU kernels,
`ssm_fwd` and `ssm_bwd`, with a hand-written backward.

Both run a grid ``(row, block of channels, block of tokens)`` whose last
axis is sequential and carries the state in VMEM: ``[N, channels]`` f32, the
``N`` states of a channel on sublanes and the channels on lanes.  A token is
then ``N * channels / 1024`` vector tiles of multiply-adds and one
exponential each, with nothing across the lanes: ``dt_t`` and ``x_t`` are
rows (broadcast along sublanes), ``B_t`` and ``C_t`` come in already spread
over a lane tile (``[S, N, 128]``, made by the caller: 67 MB a tensor at
8192 tokens, read once a block of channels) and are repeated along the lanes
for free, and ``y_t = sum_n h_t[n] C_t[n]`` is a sum over sublanes.  Tokens
are walked eight at a time (one f32 sublane tile of ``x``, ``dt``, ``y``).

`ssm_fwd` writes ``y`` and the state each block of tokens starts from.
`ssm_bwd` takes the blocks last to first: it recomputes a block's states
from its start into VMEM (``[T, N, channels]``), then walks the adjoint
``dh_{t-1} = exp(dt_t A) dh_t`` back through them, writing ``dx`` and
``ddt`` rows, adding ``dA`` up in VMEM over the whole sequence, and leaving
``dB`` and ``dC`` as sums over the lane tiles of its block of channels
(``[N, 128]`` a token): the last reduction, over 128 lanes and the blocks of
channels, is the caller's, in XLA, where it is one pass over 67 MB a block.

Mosaic names reach the device trace as `flash_attention._named` has them.
``impl="interpret"`` runs the same bodies under the Pallas interpreter.
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

LANE = 128
SUB = 8          # tokens a trip of the token loops: one f32 sublane tile
BLOCK_T = 64     # tokens a grid step (the backward holds their states)
BLOCK_D = 1280   # channels a grid step; both from a chip sweep, the table is
                 # in `ops/selective_scan.py`: wider blocks of channels spill
                 # (the state alone is 40 vector tiles at 2560), narrower ones
                 # read the spread B and C more often
_F32 = jnp.float32
_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _lanes(tile, d):
    """``[N, 128] -> [N, d]``: the lane tile repeated."""
    return tile if d == LANE else jnp.tile(tile, (1, d // LANE))


def _fold(v):
    """``[N, d] -> [N, 128]``: the sum of the lane tiles."""
    out = v[:, :LANE]
    for k in range(1, v.shape[1] // LANE):
        out = out + v[:, k * LANE:(k + 1) * LANE]
    return out


def _rows(rows):
    """``SUB`` rows ``[1, d]`` as one ``[SUB, d]`` tile, by selects."""
    d = rows[0].shape[1]
    at = lax.broadcasted_iota(jnp.int32, (SUB, d), 0)
    out = jnp.broadcast_to(rows[0], (SUB, d))
    for j in range(1, SUB):
        out = jnp.where(at == j, rows[j], out)
    return out


def _token(at, h, x_t, dt_t, bb):
    """``(exp(dt_t A), dt_t x_t, h_t)``; ``h, at: [N, d]``, rows ``[1, d]``."""
    a = jnp.exp(dt_t * at)
    u = dt_t * x_t
    return a, u, a * h + u * bb


def _fwd_kernel(x_ref, dt_ref, bb_ref, cb_ref, at_ref, y_ref, start_ref,
                h_ref):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        h_ref[...] = jnp.zeros_like(h_ref)

    start_ref[0, 0] = h_ref[...]
    at = at_ref[...]
    d = at.shape[1]

    def chunk(c, h):
        t0 = pl.multiple_of(c * SUB, SUB)
        x8, dt8 = x_ref[0, pl.ds(t0, SUB), :], dt_ref[0, pl.ds(t0, SUB), :]
        out = []
        for j in range(SUB):
            _, _, h = _token(at, h, x8[j:j + 1], dt8[j:j + 1],
                             _lanes(bb_ref[0, t0 + j], d))
            out.append(jnp.sum(h * _lanes(cb_ref[0, t0 + j], d), axis=0,
                               keepdims=True))
        y_ref[0, pl.ds(t0, SUB), :] = _rows(out)
        return h

    h_ref[...] = lax.fori_loop(0, x_ref.shape[1] // SUB, chunk, h_ref[...])


def _bwd_kernel(x_ref, dt_ref, bb_ref, cb_ref, at_ref, start_ref, dy_ref,
                dx_ref, ddt_ref, dbp_ref, dcp_ref, da_ref,
                dh_ref, da_acc, hs_ref):
    step, steps = pl.program_id(2), pl.num_programs(2)

    @pl.when(step == 0)
    def _zero():
        dh_ref[...] = jnp.zeros_like(dh_ref)
        da_acc[...] = jnp.zeros_like(da_acc)

    at = at_ref[...]
    d = at.shape[1]
    chunks = x_ref.shape[1] // SUB

    def before(c, h):
        """The state every token of the block starts from, into VMEM."""
        t0 = pl.multiple_of(c * SUB, SUB)
        x8, dt8 = x_ref[0, pl.ds(t0, SUB), :], dt_ref[0, pl.ds(t0, SUB), :]
        for j in range(SUB):
            hs_ref[t0 + j] = h
            _, _, h = _token(at, h, x8[j:j + 1], dt8[j:j + 1],
                             _lanes(bb_ref[0, t0 + j], d))
        return h

    lax.fori_loop(0, chunks, before, start_ref[0, 0])

    def chunk(i, dh):
        t0 = pl.multiple_of((chunks - 1 - i) * SUB, SUB)
        x8, dt8 = x_ref[0, pl.ds(t0, SUB), :], dt_ref[0, pl.ds(t0, SUB), :]
        dy8 = dy_ref[0, pl.ds(t0, SUB), :]
        dx, ddt = [None] * SUB, [None] * SUB
        for j in reversed(range(SUB)):
            x_t, dt_t, dy_t = x8[j:j + 1], dt8[j:j + 1], dy8[j:j + 1]
            bb = _lanes(bb_ref[0, t0 + j], d)
            h_prev = hs_ref[t0 + j]
            a, u, h = _token(at, h_prev, x_t, dt_t, bb)
            dh = dh + dy_t * _lanes(cb_ref[0, t0 + j], d)
            dcp_ref[0, 0, t0 + j] = _fold(dy_t * h)
            dbp_ref[0, 0, t0 + j] = _fold(dh * u)
            into_u = jnp.sum(dh * bb, axis=0, keepdims=True)
            into_a = dh * h_prev * a                    # d / d(dt_t A)
            ddt[j] = jnp.sum(into_a * at, axis=0, keepdims=True) \
                + into_u * x_t
            dx[j] = into_u * dt_t
            da_acc[...] += into_a * dt_t
            dh = a * dh
        dx_ref[0, pl.ds(t0, SUB), :] = _rows(dx)
        ddt_ref[0, pl.ds(t0, SUB), :] = _rows(ddt)
        return dh

    dh_ref[...] = lax.fori_loop(0, chunks, chunk, dh_ref[...])

    @pl.when(step == steps - 1)
    def _finish():
        da_ref[0] = da_acc[...]


def _vmem(nbytes: int) -> dict:
    """`compiler_params` with room for ``nbytes`` of blocks and scratch
    beyond the default scoped VMEM (16 MiB on the v5e)."""
    params = {"dimension_semantics": _SEMANTICS}
    if nbytes > 12 << 20:
        params["vmem_limit_bytes"] = min(2 * nbytes, 100 << 20)
    return {"compiler_params": pltpu.CompilerParams(**params)}


def _specs(t, db, n, nb, reverse):
    """Block specs for a grid ``(row, block of channels, block of tokens)``:
    rows of ``x`` / ``dt`` / ``y``, the spread ``B`` / ``C``, ``A``, the
    saved states, and the backward's partial ``dB`` / ``dC``; ``reverse``
    takes the blocks of tokens last to first."""
    at = (lambda i: nb - 1 - i) if reverse else (lambda i: i)
    wide = pl.BlockSpec((1, t, db), lambda r, j, i: (r, at(i), j))
    spread = pl.BlockSpec((1, t, n, LANE), lambda r, j, i: (r, at(i), 0, 0))
    a = pl.BlockSpec((n, db), lambda r, j, i: (0, j))
    state = pl.BlockSpec((1, 1, n, db), lambda r, j, i: (r, at(i), 0, j))
    partial = pl.BlockSpec((1, 1, t, n, LANE),
                           lambda r, j, i: (r, j, at(i), 0, 0))
    return wide, spread, a, state, partial


@functools.partial(jax.jit, static_argnames=("interpret", "t", "db"))
def _fwd_call(x, dt, bb, cb, at, *, interpret, t, db):
    """``x, dt: [R, S_pad, D]`` f32, ``bb, cb: [R, S_pad, N, 128]``, ``at:
    [N, D]``; returns ``y`` like ``x`` and the states at the start of every
    block of ``t`` tokens, ``[R, S_pad / t, N, D]``."""
    rows, s_pad, d = x.shape
    n, nb = at.shape[0], s_pad // t
    wide, spread, a, state, _ = _specs(t, db, n, nb, reverse=False)
    blocks = 4 * (3 * t * db + 2 * t * n * LANE + 2 * n * db)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(rows, d // db, nb),
        in_specs=[wide, wide, spread, spread, a],
        out_specs=[wide, state],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((rows, nb, n, d), _F32)],
        scratch_shapes=[pltpu.VMEM((n, db), _F32)],
        interpret=interpret,
        **_vmem(2 * blocks + 4 * n * db * 4),
        **_named("ssm_fwd"),
    )(x, dt, bb, cb, at)


@functools.partial(jax.jit, static_argnames=("interpret", "t", "db"))
def _bwd_call(x, dt, bb, cb, at, starts, dy, *, interpret, t, db):
    rows, s_pad, d = x.shape
    n, nb, nd = at.shape[0], s_pad // t, d // db
    wide, spread, a, state, partial = _specs(t, db, n, nb, reverse=True)
    da = pl.BlockSpec((1, n, db), lambda r, j, i: (r, 0, j))
    parts = jax.ShapeDtypeStruct((rows, nd, s_pad, n, LANE), _F32)
    blocks = 4 * (5 * t * db + 4 * t * n * LANE + 3 * n * db)
    scratch = 4 * (2 + t) * n * db
    return pl.pallas_call(
        _bwd_kernel,
        grid=(rows, nd, nb),
        in_specs=[wide, wide, spread, spread, a, state, wide],
        out_specs=[wide, wide, partial, partial, da],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct(x.shape, _F32), parts, parts,
                   jax.ShapeDtypeStruct((rows, n, d), _F32)],
        scratch_shapes=[pltpu.VMEM((n, db), _F32), pltpu.VMEM((n, db), _F32),
                        pltpu.VMEM((t, n, db), _F32)],
        interpret=interpret,
        **_vmem(2 * blocks + scratch + 8 * n * db * 4),
        **_named("ssm_bwd"),
    )(x, dt, bb, cb, at, starts, dy)


def _blocks(s: int, d: int, block_t: int, block_d: int):
    """Tokens and channels a grid step, and the padded length: a sequence
    shorter than a block is one block of whole sublane tiles; the channels
    a step are the largest whole-lane-tile divisor of ``d`` up to
    ``block_d``."""
    t = min(block_t, -(-s // SUB) * SUB)
    db = max(k for k in range(LANE, min(block_d, d) + 1, LANE) if d % k == 0)
    return t, -(-s // t) * t, db


def _padded(a, s_pad):
    """``[R, S, ...] -> [R, S_pad, ...]`` f32; a step of zero does nothing."""
    a = a.astype(_F32)
    return jnp.pad(a, ((0, 0), (0, s_pad - a.shape[1]))
                   + ((0, 0),) * (a.ndim - 2))


def _spread(a, s_pad):
    """``[R, S, N] -> [R, S_pad, N, 128]``: each entry over a lane tile."""
    a = _padded(a, s_pad)
    return jnp.broadcast_to(a[..., None], a.shape + (LANE,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _ssm(x, dt, at, b, c, interpret, block_t, block_d):
    return _ssm_fwd(x, dt, at, b, c, interpret, block_t, block_d)[0]


def _ssm_fwd(x, dt, at, b, c, interpret, block_t, block_d):
    s = x.shape[1]
    t, s_pad, db = _blocks(s, x.shape[2], block_t, block_d)
    y, starts = _fwd_call(
        _padded(x, s_pad), _padded(dt, s_pad), _spread(b, s_pad),
        _spread(c, s_pad), at.astype(_F32), interpret=interpret, t=t, db=db)
    return y[:, :s], (x, dt, at, b, c, starts)


def _ssm_bwd(interpret, block_t, block_d, res, dy):
    x, dt, at, b, c, starts = res
    s = x.shape[1]
    t, s_pad, db = _blocks(s, x.shape[2], block_t, block_d)
    dx, ddt, dbp, dcp, da = _bwd_call(
        _padded(x, s_pad), _padded(dt, s_pad), _spread(b, s_pad),
        _spread(c, s_pad), at.astype(_F32), starts, _padded(dy, s_pad),
        interpret=interpret, t=t, db=db)
    narrow = lambda p, like: jnp.sum(p, axis=(1, 4))[:, :s].astype(like.dtype)
    return (dx[:, :s].astype(x.dtype), ddt[:, :s].astype(dt.dtype),
            jnp.sum(da, axis=0).astype(at.dtype), narrow(dbp, b),
            narrow(dcp, c))


_ssm.defvjp(_ssm_fwd, _ssm_bwd)


def supports(x, at) -> bool:
    """Whether the kernels take these sizes: channels in whole lane tiles,
    states in whole sublane tiles."""
    return x.shape[-1] % LANE == 0 and at.shape[0] % SUB == 0


def ssm_kernels(x, dt, at, b, c, *, impl: str = "mosaic",
                block_t: int = BLOCK_T, block_d: int = BLOCK_D):
    """`selective_scan._scan` as Pallas kernels: ``x, dt: [R, S, D]``,
    ``at: [N, D]`` (``A`` transposed), ``b, c: [R, S, N]`` -> ``y: [R, S,
    D]`` f32 without the ``D`` skip, differentiable in all five.
    ``impl="mosaic"`` compiles for the TPU and nowhere else;
    ``"interpret"`` runs the kernel bodies under the Pallas interpreter."""
    if not supports(x, at):
        raise ValueError(
            f"the scan kernels take channels in multiples of {LANE} and "
            f"states in multiples of {SUB}, got {x.shape[-1]} and "
            f"{at.shape[0]}")
    return _ssm(x, dt, at, b, c, use_interpreter(impl), block_t, block_d)
