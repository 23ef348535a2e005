"""The state-space duality scan as Pallas TPU kernels, forward and backward.

What is computed is what `ops.ssd.ssd_chunked` computes (the module
docstring of `ops/ssd.py` has the recurrence and its chunked form), less the
skip ``D x``, which stays in plain `jax.numpy` round the call.  What differs
is where the intermediates live.  Two kernels, each a grid ``(row, group,
block of tokens)`` whose last axis runs in order and carries the group's
states in VMEM scratch:

* `ssd_fwd`: a block of `BLOCK_T` tokens of one group, a chunk of `CHUNK`
  tokens at a time, in VMEM: the running sums ``cum`` of ``dt A`` inside
  the chunk; ``C B^T`` once for the group; for each head the decay ``L_ij
  = exp(cum_i - cum_j)`` (``j <= i``, else ``exp(-inf)``) and the masked
  product ``(L o C B^T)(dt x)``; the part from the carried state,
  ``exp(cum_i) C S^T``; and the state at the chunk's end, ``exp(total) S +
  sum_j exp(total - cum_j) dt_j x_j B_j^T``.  Reads x, dt, ``dt A``, B, C
  once; writes y (f32) and the state each block starts from (f32), which
  is all the backward keeps besides its inputs.
* `ssd_bwd`: the same blocks last to first, carrying the gradient of the
  state in scratch.  It replays the block's chunks from the saved state to
  get the state each starts from, rebuilds each chunk's ``L`` and ``C
  B^T`` (transposed: no product transposes an operand a head), and writes
  dx, the gradient of ``dt`` through ``dt x`` and of ``dt A`` (a reverse
  running sum of both sides of ``cum_i - cum_j``), and dB, dC summed over
  the group's heads.  ``A``'s gradient, ``sum d(dt A) dt``, is JAX's,
  outside.

**Precision.**  ``dt``, the running sums, every exponent and decay and the
carried state are f32; every product takes its operands in x's dtype (bf16
in the cell: what the plain form's f32 operands get at the TPU's default
precision) and accumulates in f32; ``L o C B^T`` is formed in f32 and
rounded only as the product's operand.  The running sums are exact f32
sums (`_running_sum`), and the column and the row of ``cum_i - cum_j`` are
one array and its transpose, so every exponent is a difference of running
sums inside one chunk and never positive; masked entries come from
``-inf``.

**Layout.**  ``x`` as ``[rows, S, H * P]`` and ``B``, ``C`` as ``[rows, S,
G * N]`` (free reshapes): a block ``(1, T, H / G * P)`` at column block
``g`` is group ``g``'s heads, and ``(1, T, N)`` its ``B`` or ``C``.  Two
heads of ``P = 64`` share one 128-lane tile, and they are never taken
apart along the lanes (a slice across the lanes costs the v5e ~4.5 cycles
a tile, a select a third of one): a pair's products run over the whole
tile, and each head's result is selected by a mask of its half.  A pair's
states are held transposed, one ``[N, 128]`` slab, so that reading and
writing them are products of ``C`` and ``B^T`` as they come.  ``dt`` and
``dt A`` come a row a head, ``[rows, G, H / G, S]``, and are turned to
columns inside by a transpose of ``H / G`` rows.

``impl="interpret"`` runs the same kernel bodies under the Pallas
interpreter, by name, for the CPU tests; `ssd_chunked` is the oracle.
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

CHUNK = 128      # tokens of one L matrix: exact for any length, a tile size
BLOCK_T = 256    # tokens a grid step
# Swept on a TPU v5e at one layer of nemotron3-nano-sync-1chip (x [1, 8192,
# 64, 64] bf16), ms a call, forward / forward and backward, by chunk /
# block: 128/256 1.59 / 3.50, 128/512 1.56 / 3.60, 128/1024 1.59 / 3.68,
# 256/512 1.59 / 3.49; the plain form 3.30 / 9.05.  A step pays two
# forwards and a backward, so 128/256 and 256/512 tie (5.09 and 5.07 ms a
# layer) ahead of 128/512 (5.17): a longer block replays more chunks in
# the backward.  The chunk stays the configuration's 128.  (In a first
# version of the kernels chunks of 64 cost a quarter more than 128.)
HEAD = 64        # the head width P the kernels take: two heads a lane tile
STATE = 128      # the state width N the kernels take: one lane tile
LANE = 128

_F32 = jnp.float32
# dot_general contractions of [rows, cols] operands
_NN, _NT, _TN = ((1,), (0,)), ((1,), (1,)), ((0,), (0,))


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())), preferred_element_type=_F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _lane(a, k: int):
    """Lane ``k`` of ``a: [rows, K]`` as a column ``[rows, 1]``: a masked
    reduction, exact (one term is not zero)."""
    return jnp.sum(jnp.where(_iota(a.shape, 1) == k, a, 0.0), axis=1,
                   keepdims=True)


def _halves(a):
    """``a: [rows, 128]`` summed over each head's half of the lanes: two
    columns ``[rows, 1]``."""
    first = _iota(a.shape, 1) < HEAD
    return (jnp.sum(jnp.where(first, a, 0.0), axis=1, keepdims=True),
            jnp.sum(jnp.where(first, 0.0, a), axis=1, keepdims=True))


class _Pair:
    """Heads ``2m`` and ``2m + 1`` of a group on one lane tile: their
    per-head columns spread over their halves of the tile."""

    def __init__(self, m: int):
        self.heads = (2 * m, 2 * m + 1)
        self.lanes = slice(m * LANE, (m + 1) * LANE)
        self.first = _iota((1, LANE), 1) < HEAD          # lanes of head 2m

    def spread(self, cols):
        """``cols: [rows, K]`` -> ``[rows, 128]``: each half its head's."""
        h0, h1 = self.heads
        return jnp.where(self.first, _lane(cols, h0), _lane(cols, h1))

    def own(self, h: int):
        """The mask of head ``h``'s half of the lanes."""
        return self.first if h == self.heads[0] else jnp.logical_not(
            self.first)


def _running_sum(rows, reverse: bool = False):
    """The running sum of ``rows: [K, Q]`` (f32) along each row, or from
    the end with ``reverse``, as a product with a triangle of ones.  ``rows``
    is cut into three bf16 pieces whose sum is ``rows`` exactly; a piece
    times a one is exact, so three one-pass products accumulated in f32 are
    the f32 sum (`ops/kda_pallas.py:_running_sum`)."""
    before, at = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    tri = (before >= at) if reverse else (before <= at)
    tri = tri.astype(jnp.bfloat16)
    total = jnp.zeros(rows.shape, _F32)
    for _ in range(3):
        piece = rows.astype(jnp.bfloat16)
        total = total + _dot(piece, tri, _NN)
        rows = rows - piece.astype(_F32)
    return total


def _chunk(dt_ref, da_ref, b_ref, c_ref, c: int, dtype):
    """What every pair of chunk ``c`` of the block reads.  ``dt`` and ``dt
    A`` come a row a head; the running sums are taken here, and turned to
    a column a head by a transpose, so both sides of ``cum_i - cum_j`` hold
    the same f32 numbers.  ``B`` is also held transposed, once for the
    pairs, for the state's products."""
    rows = slice(c * CHUNK, (c + 1) * CHUNK)
    b = b_ref[0, rows, :].astype(_F32)
    cc = c_ref[0, rows, :].astype(dtype)
    cum_row = _running_sum(da_ref[0, 0, :, rows])         # [K, Q]
    cum_col = cum_row.T                                   # [Q, K]
    bb = b.astype(dtype)
    return {
        "rows": rows, "b": bb, "c": cc, "bt": b.T.astype(dtype),
        "scores": _dot(cc, bb, _NT),                     # C B^T, [Q, Q]
        "dt": dt_ref[0, 0, :, rows].T, "cum_col": cum_col,
        "cum_row": cum_row,
        "last": cum_col[CHUNK - 1:, :],                   # [1, K]
    }


def _decay(ch, h: int, transposed: bool = False):
    """``L_ij = exp(cum_i - cum_j)`` of head ``h`` for ``j <= i``, else 0;
    ``transposed``, ``L^T``, the token ``j`` on the sublanes."""
    col, row = _lane(ch["cum_col"], h), ch["cum_row"][h:h + 1, :]
    sub, lane = _iota((CHUNK, CHUNK), 0), _iota((CHUNK, CHUNK), 1)
    if transposed:
        return jnp.exp(jnp.where(lane >= sub, row - col, -jnp.inf))
    return jnp.exp(jnp.where(lane <= sub, col - row, -jnp.inf))


def _pair_inputs(x_ref, ch, pair, dtype):
    x = x_ref[0, ch["rows"], pair.lanes].astype(_F32)
    dt = pair.spread(ch["dt"])
    u = dt * x                                            # dt x, [Q, 128]
    cum = pair.spread(ch["cum_col"])
    total = pair.spread(ch["last"])                       # [1, 128]
    return {"x": x, "dt": dt, "u": u, "ub": u.astype(dtype),
            "into": jnp.exp(cum), "to_end": jnp.exp(total - cum),
            "keep": jnp.exp(total)}


def _advance(ch, a, s, dtype):
    """A pair's state, transposed (``[N, 128]``), at the chunk's end from
    ``s``, the one it starts from: ``exp(total) S + (dt x o exp(total -
    cum))^T B``."""
    written = (a["u"] * a["to_end"]).astype(dtype)
    return a["keep"] * s + _dot(ch["bt"], written, _NN)


def _fwd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, y_ref, start_ref, state):
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        state[...] = jnp.zeros_like(state)

    dtype = x_ref.dtype
    start_ref[0, 0, 0] = state[...]
    for c in range(x_ref.shape[1] // CHUNK):
        ch = _chunk(dt_ref, da_ref, b_ref, c_ref, c, dtype)
        for m in range(x_ref.shape[2] // LANE):
            pair = _Pair(m)
            a = _pair_inputs(x_ref, ch, pair, dtype)
            s = state[:, pair.lanes]                      # S^T, [N, 128]
            y = a["into"] * _dot(ch["c"], s.astype(dtype), _NN)
            for h in pair.heads:
                mixed = (_decay(ch, h) * ch["scores"]).astype(dtype)
                y = jnp.where(pair.own(h), y + _dot(mixed, a["ub"], _NN), y)
            y_ref[0, ch["rows"], pair.lanes] = y.astype(y_ref.dtype)
            state[:, pair.lanes] = _advance(ch, a, s, dtype)


def _bwd_kernel(x_ref, dt_ref, da_ref, b_ref, c_ref, start_ref, dy_ref,
                dx_ref, ddt_ref, dda_ref, db_ref, dc_ref, dstate):
    """The chunk's matrices are taken transposed here (``[j, i]``, the
    token read on the sublanes), so that no product has to transpose an
    operand a head: ``(L o C B^T)^T`` times the output's gradient is the
    gradient of ``dt x``."""
    @pl.when(pl.program_id(2) == 0)
    def _zero():
        dstate[...] = jnp.zeros_like(dstate)

    dtype = x_ref.dtype
    k = dt_ref.shape[2]
    head_lane = _iota((1, k), 1)
    head_row = _iota((k, 1), 0)
    last_row = _iota((CHUNK, 1), 0) == CHUNK - 1
    nc, pairs = x_ref.shape[1] // CHUNK, x_ref.shape[2] // LANE

    # The states the block's chunks start from, again from the block's.
    state = [start_ref[0, 0, 0, :, _Pair(m).lanes] for m in range(pairs)]
    starts = [state]
    for c in range(nc - 1):
        ch = _chunk(dt_ref, da_ref, b_ref, c_ref, c, dtype)
        state = [_advance(ch, _pair_inputs(x_ref, ch, _Pair(m), dtype), s,
                          dtype) for m, s in enumerate(state)]
        starts.append(state)

    for c in reversed(range(nc)):
        ch = _chunk(dt_ref, da_ref, b_ref, c_ref, c, dtype)
        ct = c_ref[0, ch["rows"], :].astype(_F32).T.astype(dtype)
        scores_t = _dot(ch["b"], ch["c"], _NT)            # B C^T, [j, i]
        db = jnp.zeros((CHUNK, STATE), _F32)
        dc = jnp.zeros((CHUNK, STATE), _F32)
        dscores_t = jnp.zeros((CHUNK, CHUNK), _F32)
        ddt = jnp.zeros((CHUNK, k), _F32)
        dcum_col = jnp.zeros((CHUNK, k), _F32)
        dcum_row = jnp.zeros((k, CHUNK), _F32)
        for m in range(pairs):
            pair = _Pair(m)
            a = _pair_inputs(x_ref, ch, pair, dtype)
            g = dy_ref[0, ch["rows"], pair.lanes].astype(_F32)
            gb = g.astype(dtype)
            s = starts[c][m]                              # S^T, [N, 128]
            sb = s.astype(dtype)
            ds = dstate[:, pair.lanes]          # d(the state at chunk's end)
            dsb = ds.astype(dtype)

            # y += exp(cum_i) C S^T
            g_in = g * a["into"]
            dc += _dot(g_in.astype(dtype), sb, _NT)
            d_into = _halves(g_in * _dot(ch["c"], sb, _NN))
            # S_end^T = exp(total) S^T + B^T (dt x o exp(total - cum))
            written = (a["u"] * a["to_end"]).astype(dtype)
            db += _dot(written, dsb, _NT)
            dwritten = _dot(ch["b"], dsb, _NN)            # [Q, 128]
            du = dwritten * a["to_end"]
            d_to_end = _halves(dwritten * a["u"] * a["to_end"])
            d_keep = _halves(jnp.sum(s * ds, axis=0, keepdims=True)
                             * a["keep"])
            dstate[:, pair.lanes] = a["keep"] * ds + _dot(
                ct, g_in.astype(dtype), _NN)

            # y += (L o C B^T)(dt x), a head at a time
            for i, h in enumerate(pair.heads):
                own = pair.own(h)
                decay_t = _decay(ch, h, transposed=True)
                mixed_t = (decay_t * scores_t).astype(dtype)
                dmixed_t = _dot(a["ub"], jnp.where(own, gb, 0), _NT)
                du = jnp.where(own, du + _dot(mixed_t, gb, _NN), du)
                dmixed_t = dmixed_t * decay_t             # d(B C^T) of head
                dscores_t += dmixed_t
                through = dmixed_t * scores_t             # (dL o L)^T
                total_h = jnp.sum(d_to_end[i], keepdims=True) + d_keep[i]
                col = d_into[i] - d_to_end[i] \
                    - jnp.sum(through, axis=1, keepdims=True) \
                    + jnp.where(last_row, total_h, 0.0)
                dcum_col = jnp.where(head_lane == h, dcum_col + col, dcum_col)
                dcum_row = jnp.where(
                    head_row == h,
                    dcum_row + jnp.sum(through, axis=0, keepdims=True),
                    dcum_row)
            dx_ref[0, ch["rows"], pair.lanes] = (du * a["dt"]).astype(
                dx_ref.dtype)
            ddt_pair = _halves(du * a["x"])
            for i, h in enumerate(pair.heads):
                ddt = jnp.where(head_lane == h, ddt + ddt_pair[i], ddt)

        dscores_t = dscores_t.astype(dtype)
        dc += _dot(dscores_t, ch["b"], _TN)
        db += _dot(dscores_t, ch["c"], _NN)
        rows = ch["rows"]
        db_ref[0, rows, :] = db.astype(db_ref.dtype)
        dc_ref[0, rows, :] = dc.astype(dc_ref.dtype)
        ddt_ref[0, 0, :, rows] = ddt.T
        # d(dt A) is the reverse running sum of d cum, both sides of it
        dda_ref[0, 0, :, rows] = _running_sum(dcum_row + dcum_col.T,
                                              reverse=True)


def _specs(t, wide, k, nb, reverse):
    """Block specs for a grid ``(row, group, block)``: x / y (``wide``
    lanes a group), B / C, the rows a head, and the states blocks start
    from; ``reverse`` takes the blocks last to first."""
    at = (lambda i: nb - 1 - i) if reverse else (lambda i: i)
    return {
        "x": pl.BlockSpec((1, t, wide), lambda b, g, i: (b, at(i), g)),
        "bc": pl.BlockSpec((1, t, STATE), lambda b, g, i: (b, at(i), g)),
        "row": pl.BlockSpec((1, 1, k, t), lambda b, g, i: (b, g, 0, at(i))),
        "state": pl.BlockSpec((1, 1, 1, STATE, wide),
                              lambda b, g, i: (b, g, at(i), 0, 0)),
    }


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _rows(a, groups: int):
    """``[r, S, H] -> [r, G, H / G, S]``: a row a head, by group."""
    r, s, h = a.shape
    return a.reshape(r, s, groups, h // groups).transpose(0, 2, 3, 1)


def _heads(a):
    """The inverse of `_rows`."""
    r, g, k, s = a.shape
    return a.transpose(0, 3, 1, 2).reshape(r, s, g * k)


def _layout(x, dt, da, B, C):
    """The kernels' operands from ``x: [r, S, H, P]``, ``dt, da: [r, S,
    H]``, ``B, C: [r, S, G, N]``."""
    r, s, h, p = x.shape
    g = B.shape[2]
    return (x.reshape(r, s, h * p), _rows(dt, g), _rows(da, g),
            B.reshape(r, s, g * STATE), C.reshape(r, s, g * STATE))


# Jitted, so that the layers of a model that call it at one shape trace the
# kernel's body once: tracing it again for each layer lengthens set-up.
@functools.partial(jax.jit, static_argnames="interpret")
def _fwd_call(x, dt, da, B, C, *, interpret):
    """y and the state each block starts from, transposed: ``[r, G, S /
    T, N, H / G * P]`` f32."""
    r, s, _ = x.shape
    g, k = dt.shape[1], dt.shape[2]
    wide, t = k * HEAD, BLOCK_T
    nb = s // t
    sp = _specs(t, wide, k, nb, reverse=False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(r, g, nb),
        in_specs=[sp["x"], sp["row"], sp["row"], sp["bc"], sp["bc"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((r, g, nb, STATE, wide), _F32)],
        scratch_shapes=[pltpu.VMEM((STATE, wide), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        **_named("ssd_fwd"),
    )(x, dt, da, B, C)


@functools.partial(jax.jit, static_argnames="interpret")
def _bwd_call(x, dt, da, B, C, starts, dy, *, interpret):
    r, s, _ = x.shape
    g, k = dt.shape[1], dt.shape[2]
    wide, t = k * HEAD, BLOCK_T
    nb = s // t
    sp = _specs(t, wide, k, nb, reverse=True)
    return pl.pallas_call(
        _bwd_kernel,
        grid=(r, g, nb),
        in_specs=[sp["x"], sp["row"], sp["row"], sp["bc"], sp["bc"],
                  sp["state"], sp["x"]],
        out_specs=[sp["x"], sp["row"], sp["row"], sp["bc"], sp["bc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(dt.shape, _F32),
                   jax.ShapeDtypeStruct(da.shape, _F32),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype)],
        scratch_shapes=[pltpu.VMEM((STATE, wide), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        interpret=interpret,
        **_named("ssd_bwd"),
    )(x, dt, da, B, C, starts, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _scan(x, dt, da, B, C, interpret):
    return _scan_fwd(x, dt, da, B, C, interpret)[0]


def _scan_fwd(x, dt, da, B, C, interpret):
    y, starts = _fwd_call(*_layout(x, dt, da, B, C), interpret=interpret)
    return y.reshape(x.shape), (x, dt, da, B, C, starts)


def _scan_bwd(interpret, res, dy):
    x, dt, da, B, C, starts = res
    r, s, h, p = x.shape
    dx, ddt, dda, db, dc = _bwd_call(
        *_layout(x, dt, da, B, C), starts,
        dy.astype(_F32).reshape(r, s, h * p), interpret=interpret)
    return (dx.reshape(x.shape), _heads(ddt).astype(dt.dtype), _heads(dda),
            db.reshape(B.shape), dc.reshape(C.shape))


_scan.defvjp(_scan_fwd, _scan_bwd)


def supports(x, B, chunk: int) -> bool:
    """Whether the kernels take these shapes: heads of `HEAD` in pairs on
    whole lane tiles, a state of `STATE`, and a length of whole blocks of
    `BLOCK_T` tokens (and of the caller's ``chunk``, which the plain form
    takes)."""
    s, h, p = x.shape[1:]
    g, n = B.shape[2:]
    return (p == HEAD and n == STATE and (h // g) % 2 == 0
            and s % chunk == 0 and s % BLOCK_T == 0)


def ssd_kernels(x, dt, A, B, C, D, *, impl: str = "mosaic"):
    """`ops.ssd.ssd_chunked` as Pallas kernels with a hand-written backward
    of the scan: same arguments (less ``chunk``: the kernels take theirs,
    `CHUNK`), same result up to rounding.  ``impl="mosaic"`` compiles for
    the TPU and nowhere else; ``"interpret"`` runs the kernel bodies under
    the Pallas interpreter."""
    if not supports(x, B, CHUNK):
        raise ValueError(
            f"the SSD kernels take heads of {HEAD} in pairs, a state of "
            f"{STATE} and a length of whole {BLOCK_T}-token blocks, "
            f"got x {x.shape} and B {B.shape}")
    dt = dt.astype(_F32)
    y = _scan(x, dt, dt * A.astype(_F32), B, C, use_interpreter(impl))
    return y + D.astype(_F32)[:, None] * x.astype(_F32)
