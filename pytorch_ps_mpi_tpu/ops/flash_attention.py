"""Flash attention — Pallas TPU kernels for the attention hot op.

Dense softmax attention materializes the ``[S, S]`` score matrix in HBM;
at long context that matrix IS the memory bill.  This module computes
exact attention with O(S · tile) live memory, in two or three Mosaic calls
whose names the device trace and the benchmark's readers know (`KERNELS`):

* **Forward** (`flash_fwd`, `_fwd_kernel`): grid ``(B·H, q_tiles,
  k_tiles)`` with the k sweep minor.  For each q tile the kernel holds a
  running row-max ``m``, normalizer ``l`` and unnormalized accumulator in
  VMEM scratch (TPU grids run sequentially, so scratch carries across the
  k sweep) and rescales them per visiting block of k: the same streaming
  softmax as `parallel.ring_attention`, here on one chip.  Operands go into
  the MXU as they come (bf16), products accumulate in f32, the softmax
  statistics are f32.
* **Backward**, under ``jax.custom_vjp``: `flash_bwd_dkdv` sweeps q per
  block of k (grid ``(B·H, k_tiles, q_tiles)``), recomputing ``P`` from the
  saved per-row logsumexp (``exp(s - lse)``, no second softmax) and
  accumulating in VMEM scratch, so the backward never materializes
  ``[S, S]`` either.  Where a head's whole q side is its one q tile (every
  length up to `_WHOLE_SIDE`, so every shape a cell runs) that call is the
  whole backward: it has ``dS`` and k in hand for every sub-block it enters
  and dq's rows are all in VMEM, so it adds ``dS k`` into a third
  accumulator across the k tiles and writes dq too — five matrix products a
  sub-block, each of s, ``exp``, dP and dS computed once.  Only past that
  length, with the q side in tiles, is `flash_bwd_dq` (k swept per block of
  q, the FlashAttention-2 decomposition: seven products) called for dq.

**Two levels of tiling, the inner one following the mask.**  The grid tile
(what a `BlockSpec` copies into VMEM) is large — a head's whole sequence
where that fits — so the grid has few steps; inside it each kernel walks
compute *sub-blocks* with loops whose bounds come from the tile's place in
the grid (`_k_segments`, `_q_segments`).  A sub-block wholly above the
causal diagonal, or wholly in the padding, is never entered; one wholly
under the diagonal runs the *interior* body, which builds no iota and makes
no compare or select; only the sub-blocks the diagonal or the padded tail
crosses run the masked body, and the ``< seq_len`` tests are compiled only
where something is padded.  ``causal=False`` is interior everywhere but
the tail.  Where a head is one tile the bounds are Python ints and the
loops unroll; otherwise they are device loops.  `tile_plan` is the one
source of both levels' sizes, from the shape, and counts with the kernels'
own bounds how many sub-blocks a head enters, masks and skips.

**A sliding window** (``window=W`` with ``causal``: row ``i`` sees the ``W``
keys ``j`` with ``0 <= i - j < W``, its own among them) gives the band a
lower edge, and the same bounds follow it: a sub-block wholly under the band
(``q0 - (k0 + sub_k - 1) >= W``) is never entered, one the lower edge
crosses runs the masked body with one more compare on the same iota
difference (``diff < k0 - q0 + W``), what lies between the two edges stays
interior.  `tile_plan` counts with the same bounds, and `flash_bwd_dkdv`
stays the whole backward.
``window=None`` is the program this module traced before it knew windows:
the same jaxpr, the same plan, the same counts.

**Across a rematerialised block.**  The forward's two residuals beyond
q, k and v, the output ``[B, S, H, Dv]`` and the row logsumexp ``[B, H, S]``
f32, carry the checkpoint names ``flash_out`` and ``flash_lse``, put on
inside the forward rule (a name on the call's result outside the
``custom_vjp`` would leave the residual unnamed).  A caller that remats its
block under `SAVE_FLASH` keeps them, and the backward pass runs `flash_fwd`
no second time; a block without a policy recomputes them as before, and
its program is the one it was (a name lowers to nothing).

Composition: `flash_attention` is a drop-in for
`parallel.ring_attention.dense_attention` (``[B, S, H, D]`` in/out,
``causal=``/``scale=``), so it plugs into `models.transformer.TransformerLM`
via ``attn=`` — and combines with ring attention by serving as the local
block math while ppermute hops cover the sequence axis.

The kernels compile through Mosaic for the TPU.  ``impl="interpret"`` runs
the same kernel bodies under the Pallas interpreter (bit-faithful to the
kernel logic, just slow) — by name only, for the CPU test mesh;
`dense_attention` remains the oracle in tests.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax._src.ad_checkpoint import name_p
from jax.ad_checkpoint import checkpoint_name
from jax.interpreters import mlir

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import use_interpreter

BLOCK = 128      # lane tile the row statistics ride; also the padding unit
NEG_INF = -1e30  # large-negative instead of -inf: keeps masked-row math
                 # finite without jnp.where laundering inside the kernel
KERNELS = ("flash_fwd", "flash_bwd_dkdv", "flash_bwd_dq")
# The `jax.checkpoint` / `nn.remat` policy that keeps the forward's named
# residuals (module docstring: across a rematerialised block).
SAVE_FLASH = jax.checkpoint_policies.save_only_these_names(
    "flash_out", "flash_lse")
# A name lowers to nothing, but JAX first emits each distinct (name, shape)
# as a private function, and each after the first takes a number off the
# module's symbol counter: every function lowered after it is renumbered
# (`_pad_92` for `_pad_91`), and a program without a policy no longer hashes
# as it did.  Lowered in place, uncached, a name takes no number.
mlir.register_lowering(name_p, lambda ctx, x, *, name: [x], cacheable=False)
_NT = (((1,), (1,)), ((), ()))   # a @ b.T, no transpose materialised
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


class Tiles(NamedTuple):
    """One kernel's two levels: the grid tile a `BlockSpec` brings into VMEM
    (``tile_q`` rows of q / dO / the row statistics, ``tile_k`` rows of
    k / v) and the compute sub-block the kernel walks inside it."""
    tile_q: int
    tile_k: int
    sub_q: int
    sub_k: int


class Counts(NamedTuple):
    """Sub-blocks of one head: ``entered`` (computed), ``masked`` (those of
    the entered that pay iota, compare and select: the diagonal and the
    padded tail) and ``skipped`` (above the diagonal or past the tail)."""
    entered: int
    masked: int
    skipped: int


class Plan(NamedTuple):
    tiles: dict    # kernel name -> Tiles
    counts: dict   # kernel name -> Counts


# Tiles and sub-blocks from a chip sweep (TPU v5e, bf16, causal; ms a call,
# 4–20 calls back to back on the host clock, the best of three rounds).
# "before" = the single-level tiles this table replaces (forward 512 x 1024,
# backward 512 x 512, from one forward sweep at [4, 4096, 8, 128]).  A row
# is (own, other | own, other): the kernel's own side is q for `flash_fwd`
# and `flash_bwd_dq`, k for `flash_bwd_dkdv`; tile first, sub-block after the
# bar.  * = a head is one tile, so every loop bound is static and unrolls.
# The two backward columns are the two-call backward, which since PR 34
# runs only past `_WHOLE_SIDE`; the one call's rows follow the table.
#
#   [BH, S_pad, D_pad / Dv_pad]            flash_fwd  _bwd_dq  _bwd_dkdv
#   [128, 1024, 128 / 128]  before          0.734   0.897   0.990
#    *(1024, 1024 |  128,  128)              0.401   0.513   0.541
#    *(1024, 1024 |  256,  256)              0.394   0.477   0.678
#    *(1024, 1024 |  512,  512)              0.400   0.477   0.593
#    *(1024, 1024 | 1024, 1024)              0.472   0.584   0.769
#     ( 512, 1024 |  256,  256)              0.810   0.764   1.126
#     ( 512,  512 |  512,  512)              0.629   0.906   0.861
#     ( 512,  512 |  128,  128)              1.945   1.847   2.240
#   [64, 2048, 128 / 128]  before           1.268   1.434   1.750
#    *(2048, 2048 |  128,  128)                -       -     0.878
#    *(2048, 2048 |  256,  256)              0.569   0.697   1.184
#    *(2048, 2048 |  512,  512)              0.607   0.733   0.972
#     (1024, 2048 |  512,  512)              0.797   0.929     -
#     (1024, 1024 |  512,  512)                -       -     1.265
#   [32, 4096, 128 / 128]  before           2.047   2.363   3.152
#     (1024, 4096 |  512,  512)              1.258   1.528   2.138
#     (2048, 4096 |  512,  512)                -       -     1.960
#     (2048, 2048 |  512,  512)              1.291   1.742   2.008
#     (1024, 2048 |  512,  512)              1.334   1.792   2.051
#     (1024, 1024 |  512,  512)              1.493   2.118   2.234
#     (1024, 1024 |  256,  256)              2.546   2.819   3.052
#   [64, 8192, 256 / 128]  before           18.98   23.91   30.99
#     (1024, 8192 |  512,  512)              11.50   16.82   20.84
#     (2048, 8192 |  512,  512)              11.45   16.84   19.89
#     (2048, 2048 |  512,  512)              12.70   19.38   20.97
#     (1024, 2048 |  512,  512)              13.43   19.99   21.32
#     (1024, 2048 |  256,  512)              14.26   21.01   24.38
#     (1024, 1024 |  256,  256)              22.85   24.48   29.88
#     (1024, 1024 |  128,  128)              55.99   58.72   77.62
#   [20, 8192, 256 / 256]  (PR 33: no cell ran a 256-wide v before)
#     (1024, 8192 |  512,  512)               4.77    6.34    8.60
#     (2048, 8192 |  512,  512)               4.77    6.30    8.28
#     ( 512, 8192 |  512,  512)               4.78    6.33     -
#     (4096, 8192 |  512,  512)                -       -      8.63
#     (1024, 4096 |  512,  512)               4.92    6.80     -
#     (2048, 4096 |  512,  512)                -       -      8.44
#     (2048, 2048 |  512,  512)               5.19    7.24    8.69
#     (1024, 2048 |  512,  512)               5.46    7.47     -
#
# What it taught.  (1) A device loop (traced bounds) costs far more than
# the work it skips unless its sub-blocks are large: 512 x 512 there; two
# sub-blocks an iteration bought nothing (forward, dq: -1 %) or lost (dkdv:
# +10 to +70 %).  (2) Where a head is one tile everything is static, the
# compiler schedules across sub-blocks and small ones win: at S_pad 2048 the
# same sub-blocks under a device loop take 1.3–1.4 times as long.  (3) The
# other side whole in VMEM (k / v for the q-major kernels, q / dO / row
# statistics for dkdv) beats any split of it up to S_pad 8192.  (4) The
# interior body alone, at the old tiles, is worth 13 % (dkdv 0.990 -> 0.861).
# (5) dkdv's 256 x 256 is worse than both its neighbours in every unrolled
# shape (its two transposed products; not looked into).  (6) With v as wide
# as q / k (256 / 256) the other side whole is twice the bytes of v and dO
# in VMEM (`_vmem` asks for its 96 MiB cap in dkdv) and still beats every
# split of it; own-side tiles of 512 to 2048 rows are within 1 % of each
# other, so `_LOOP_TILE` stands at this shape too.
#
# PR 34: the backward as one call, `flash_bwd_dkdv` writing dq too (q side
# whole, so tile_q = S_pad; `tile_k | sub_q, sub_k`), against the two calls
# above it at their tiles, "two" (same chip, same clock; ms a backward):
#
#   [BH, S_pad, D_pad / Dv_pad]      two     one call
#   [128, 1024, 128 / 128]          1.001   *(1024 |  128,  128)  0.997
#                                           *(1024 |  256,  256)  0.812
#                                           *(1024 |  512,  512)  0.732
#                                           *(1024 |  512,  256)  0.747
#                                           *(1024 |  256,  512)  0.743
#                                           *(1024 | 1024, 1024)  0.951
#   [64, 1536, 128 / 128]           0.950   *(1536 |  128,  128)  1.093
#                                           *(1536 |  256,  256)  0.865
#                                           *(1536 |  512,  512)  0.764
#   [64, 2048, 128 / 128]           1.531   *(2048 |  128,  128)  1.815
#                                           *(2048 |  256,  256)  1.416
#                                           *(2048 |  512,  512)  1.212
#                                           *(2048 | 1024,  512)  1.432
#   [32, 4096, 128 / 128]           3.416    (1024 |  512,  512)  2.588
#                                            (2048 |  512,  512)  2.373
#                                            (4096 |  512,  512)  2.432
#   [64, 8192, 256 / 128]           36.55    (1024 |  512,  512)  27.28
#                                            (2048 |  512,  512)  25.94
#                                            (4096 |  512,  512)  26.98
#                                            (2048 |  256,  512)  29.28
#                                            (2048 | 1024,  512)  26.87
#   [20, 8192, 256 / 256]           14.39    (1024 |  512,  512)  10.57
#                                            (2048 |  512,  512)  10.12
#                                            (4096 |  512,  512)  10.49
#                                            (2048 |  512,  256)  10.45
#                                            (2048 | 1024,  512)  11.08
#
# (7) One call is 0.70–0.80 of two at every shape, at 0.89–0.92 of the
# MXU's peak for its five products a sub-block.  (8) With the third
# accumulator the unrolled kernel wants 512 x 512 sub-blocks, not dkdv's
# old 128 x 128 (1.00–1.19 of two calls there: no gain at all), though it
# then enters 0.75 of GPT-2's square for the 0.5625 of before; the looped
# tiles stand.  dq's rows are read, added to and written back a sub-block.
#
# PR 35: under ``window=512`` at `[40, 8192, 128 / 128]` (the window layer of
# `phi4flash-sync-1chip`: q / k 64 wide padded to 128; same chip, same clock,
# best of five; the looped tiles `(1024, 8192 | ..)` and `(8192, 2048 | ..)`,
# sub-blocks varied; entered / masked of a head's sub-blocks beside them):
#
#   sub-blocks       flash_fwd   the backward   entered / masked
#   no window          6.29        11.55         136 /  16 of  256
#    512 x  512        2.85         4.34          31 /  31 of  256
#    256 x  256        3.63         4.69          93 /  62 of 1024
#    256 x  512        3.07         4.73          62 /  62 of  512
#    512 x  256        3.42         4.72          62 /  62 of  512
#    128 x  128        7.11         8.67         310 / 124 of 4096
#   1024 x  512        3.34         4.93          23 /  23 of  128
#
# (9) Under a window the looped sub-blocks stay 512 x 512: smaller ones enter
# fewer columns a row (768 for 1,024 at 256 x 256, a third of them mask-free)
# and still lose to their loops, as in (1).  The band's 31 sub-blocks are all
# masked and take 0.45 (forward) and 0.38 (backward) of the causal 136's time
# for 0.23 of their number.  A band walked in a fixed number of guarded trips
# instead of device loops was tried and bought 3-4 % of the two kernels
# (2.73 / 4.21), 0.05 ms of the cell's 8.1 ms window layer: not kept.
_WHOLE_HEAD = 2048   # S_pad up to which a head is one tile (swept to here)
_WHOLE_SIDE = 8192   # ... and the other operand is, beyond it (swept to here)
_STATIC_SUB = {"flash_fwd": (256, 256), "flash_bwd_dkdv": (512, 512)}
_LOOP_SUB = (512, 512)
_LOOP_TILE = {"flash_fwd": 1024, "flash_bwd_dq": 1024, "flash_bwd_dkdv": 2048}
# One level (sub-block = grid tile), the sizes from before the sweep: what
# the sweep did not cover falls here and is no slower than it was.
_ONE_LEVEL = {"flash_fwd": (512, 1024), "flash_bwd_dq": (512, 512),
              "flash_bwd_dkdv": (512, 512)}


def tile_plan(s_pad, d_pad, dv_pad, causal, *, window=None, true_len=None,
              blk_q=None, blk_k=None) -> Plan:
    """Grid tiles and sub-blocks of the calls a shape makes (the keys, in
    call order: no `flash_bwd_dq` where `flash_bwd_dkdv` holds the whole q
    side and writes dq itself), from what the code can see: the padded
    length, the two padded widths and the mask — and, counted with the
    kernels' own bounds, how many sub-blocks a head enters, masks and skips
    (``true_len`` under ``s_pad`` adds the padded tail, ``window`` the
    band's lower edge; the tiles are the same with and without one: see the
    sweep above).  ``blk_q`` / ``blk_k`` force the sub-block (the tests' way
    to small ones); the grid tile stays the shape's, in whole sub-blocks."""
    true_len = s_pad if true_len is None else true_len
    swept = causal and s_pad >= 1024 and max(d_pad, dv_pad) <= 256
    tiles, counts = {}, {}
    for kernel in KERNELS:
        if kernel == "flash_bwd_dq" and tiles["flash_bwd_dkdv"].tile_q >= s_pad:
            break   # its rows are whole in dkdv's tile, which writes dq
        if not swept:
            tq, tk = sq, sk = _ONE_LEVEL[kernel]
        elif s_pad <= _WHOLE_HEAD:
            tq, tk, (sq, sk) = s_pad, s_pad, _STATIC_SUB[kernel]
        else:   # its own side in tiles, the other side whole
            tq = tk = min(s_pad, _WHOLE_SIDE)
            sq, sk = _LOOP_SUB
            if kernel == "flash_bwd_dkdv":
                tk = _LOOP_TILE[kernel]
            else:
                tq = _LOOP_TILE[kernel]
        sq = min(sq if blk_q is None else blk_q, s_pad)
        sk = min(sk if blk_k is None else blk_k, s_pad)
        t = Tiles(-(-min(tq, s_pad) // sq) * sq, -(-min(tk, s_pad) // sk) * sk,
                  sq, sk)
        tiles[kernel] = t
        counts[kernel] = _count(kernel, t, s_pad, causal, true_len, window)
    return Plan(tiles, counts)


def _named(kernel: str) -> dict:
    """A stable name for one `pallas_call`, twice: `name=` (the Mosaic
    kernel's own name) and `metadata=`, which is what reaches the device
    trace — an event of a Mosaic call is named by its HLO instruction, and
    of the two only the metadata is in it
    (``frontend_attributes={kernel_metadata={"kernel":"flash_fwd"}}``)."""
    return {"name": kernel, "metadata": {"kernel": kernel}}


def _pad_to(x, size, axis):
    want = -(-x.shape[axis] // size) * size
    if want == x.shape[axis]:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, want - x.shape[axis])
    return jnp.pad(x, pad)


# -- which sub-blocks a kernel enters ----------------------------------------
# Every bound below is worked out on Python ints when the grid has one tile
# a head (the loops then unroll, and `tile_plan` counts with the same code)
# and on traced int32 scalars from the program ids otherwise.


def _static(*xs):
    return all(isinstance(x, int) for x in xs)


def _span(x, d, n, up=False):
    """``x / d`` rounded down (``up``: up), held to ``[0, n]``."""
    if _static(x):
        return min(n, max(0, -(-x // d) if up else x // d))
    return jnp.minimum(n, lax.div(jnp.maximum(x, 0) + (d - 1 if up else 0), d))


def _least(a, b):
    return min(a, b) if _static(a, b) else jnp.minimum(a, b)


def _most(a, b):
    return max(a, b) if _static(a, b) else jnp.maximum(a, b)


def _unless(cond, x, other):
    """``other`` where ``cond`` holds, else ``x``."""
    if isinstance(cond, bool):
        return other if cond else x
    return jnp.where(cond, other, x)


def _k_segments(q0, k0, n, t, causal, seq_len, q_tail, window=None):
    """For the q rows ``[q0, q0 + sub_q)`` and the ``n`` k sub-blocks of the
    tile that starts at column ``k0``: ``(start, stop, masked)`` runs of
    sub-blocks to enter, in order — the interior ones (wholly under the
    diagonal, no padded column), then the masked ones; what follows lies
    above the diagonal or in the padding and is never entered.  ``seq_len``
    is None when nothing is padded; ``q_tail`` (the backward) also masks
    padded q rows, whose logsumexp is ``NEG_INF``.  Under a ``window``
    (key ``j`` is seen from row ``i`` while ``i - j < window``) the run
    starts at the first sub-block that reaches into the band, and those the
    band's lower edge crosses come first, masked."""
    mid = hi = n
    if causal:
        mid = _span(q0 + 1 - k0, t.sub_k, n)
        hi = _span(q0 + t.sub_q - k0, t.sub_k, n, up=True)
    if seq_len is not None:
        mid = _least(mid, _span(seq_len - k0, t.sub_k, n))
        hi = _least(hi, _span(seq_len - k0, t.sub_k, n, up=True))
        if q_tail:
            mid = _unless(q0 + t.sub_q > seq_len, mid, 0)
            hi = _unless(q0 >= seq_len, hi, 0)
    mid = _least(mid, hi)
    if window is None:
        return [(0, mid, False), (mid, hi, True)]
    # the newest key of a sub-block under the band: q0 - (k + sub_k - 1) >=
    # window; its oldest key inside for the last row: q0 + sub_q - 1 - k <
    # window
    lo = _least(_span(q0 + 1 - window - k0, t.sub_k, n), hi)
    mid = _most(mid, lo)
    full = _least(_most(_span(q0 + t.sub_q - window - k0, t.sub_k, n, up=True),
                        lo), mid)
    return [(lo, full, True), (full, mid, False), (mid, hi, True)]


def _q_segments(k0, q0, n, t, causal, seq_len, window=None):
    """The same for `flash_bwd_dkdv`, which holds the k rows ``[k0, k0 +
    sub_k)`` and walks the ``n`` q sub-blocks of the tile that starts at row
    ``q0``: above the diagonal nothing, on it masked, under it interior,
    then (only where something is padded, or under a ``window``, whose
    lower edge the last rows cross) the masked tail; past the band
    nothing."""
    lo, mid, full, hi = 0, 0, n, n
    if causal:
        lo = _span(k0 - q0, t.sub_q, n)
        mid = _span(k0 + t.sub_k - 1 - q0, t.sub_q, n, up=True)
    if window is not None:
        full = _span(window + k0 - q0, t.sub_q, n)
        hi = _span(window + k0 + t.sub_k - 1 - q0, t.sub_q, n, up=True)
    if seq_len is not None:
        tail_full = _span(seq_len - q0, t.sub_q, n)
        tail_hi = _span(seq_len - q0, t.sub_q, n, up=True)
        if window is None:
            full, hi = tail_full, tail_hi
        else:
            full, hi = _least(full, tail_full), _least(hi, tail_hi)
        mid = _unless(k0 + t.sub_k > seq_len, mid, hi)   # padded columns
        hi = _unless(k0 >= seq_len, hi, 0)               # nothing but
        full = _least(full, hi)
    mid = _least(mid, hi)
    lo, full = _least(lo, mid), _most(mid, full)
    segments = [(lo, mid, True), (mid, full, False)]
    if seq_len is not None or window is not None:
        segments.append((full, hi, True))
    return segments


def _entered(kernel, t, s_q, s_k, causal, seq_len, window=None):
    """``(q0, k0, masked)`` of every sub-block of a head that ``kernel``
    enters, by the kernel's own bounds on Python ints."""
    if kernel == "flash_bwd_dkdv":
        for k0 in range(0, s_k, t.sub_k):
            for q0 in range(0, s_q, t.tile_q):
                for start, stop, masked in _q_segments(
                        k0, q0, t.tile_q // t.sub_q, t, causal, seq_len,
                        window):
                    for a in range(start, stop):
                        yield q0 + a * t.sub_q, k0, masked
    else:
        for q0 in range(0, s_q, t.sub_q):
            for k0 in range(0, s_k, t.tile_k):
                for start, stop, masked in _k_segments(
                        q0, k0, t.tile_k // t.sub_k, t, causal, seq_len,
                        q_tail=kernel == "flash_bwd_dq", window=window):
                    for c in range(start, stop):
                        yield q0, k0 + c * t.sub_k, masked


def _count(kernel, t, s_pad, causal, true_len, window=None) -> Counts:
    s_q, s_k = (-(-s_pad // t.tile_q) * t.tile_q,
                -(-s_pad // t.tile_k) * t.tile_k)
    masks = [masked for _, _, masked in _entered(
        kernel, t, s_q, s_k, causal, _seq_len(true_len, s_q, s_k), window)]
    return Counts(len(masks), sum(masks),
                  (s_q // t.sub_q) * (s_k // t.sub_k) - len(masks))


def _loop(start, stop, body):
    """``body(c)`` for c in ``[start, stop)``: unrolled where the bounds are
    Python ints, a device loop where they come from the program ids."""
    if _static(start, stop):
        for c in range(start, stop):
            body(c)
    else:
        lax.fori_loop(jnp.int32(start), jnp.int32(stop),
                      lambda c, _: body(c), None)


def _when(cond, fn):
    if isinstance(cond, bool):
        if cond:
            fn()
    else:
        pl.when(cond)(fn)


def _rows(c, size):
    """The ``c``-th run of ``size`` rows of a tile."""
    return pl.ds(c * size if _static(c) else pl.multiple_of(c * size, size),
                 size)


def _lanes(x, n):
    """A lane-replicated ``(rows, BLOCK)`` statistic at ``n`` columns."""
    return x if n == BLOCK else jnp.tile(x, (1, n // BLOCK))


def _masker(t, causal, seq_len, q_tail, window=None):
    """``keep(q0, k0)`` for the masked sub-block whose corner is row ``q0``,
    column ``k0``: one compare on an iota difference built once a grid step
    (one more under a ``window``), and the ``< seq_len`` tests only where
    something is padded."""
    row = lax.broadcasted_iota(jnp.int32, (t.sub_q, t.sub_k), 0)
    col = lax.broadcasted_iota(jnp.int32, (t.sub_q, t.sub_k), 1)
    diff = row - col

    def keep(q0, k0):
        mask = diff >= k0 - q0 if causal else None
        if window is not None:
            mask &= diff < k0 - q0 + window
        if seq_len is not None:
            tail = col < seq_len - k0             # padded K tail: no mass
            if q_tail:
                tail &= row < seq_len - q0
            mask = tail if mask is None else mask & tail
        return mask
    return keep


def _tile_ids(n_qt, n_kt, q_axis, k_axis):
    """The tile's place in the grid; the int 0 where a head has one tile, so
    that every bound after it is a Python int."""
    return (pl.program_id(q_axis) if n_qt > 1 else 0,
            pl.program_id(k_axis) if n_kt > 1 else 0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, seq_len, t, n_qt, n_kt, window=None):
    iq, ik = _tile_ids(n_qt, n_kt, 1, 2)
    dv = v_ref.shape[-1]

    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
    _when(ik == 0, _init)

    keep = _masker(t, causal, seq_len, q_tail=False, window=window)
    for a in range(t.tile_q // t.sub_q):
        rows = _rows(a, t.sub_q)
        q0, k0 = iq * t.tile_q + a * t.sub_q, ik * t.tile_k
        q = q_ref[0, rows, :]                 # (sub_q, D)

        def sub_block(c, masked):
            # Matmuls consume the native (bf16) operands — the MXU's fast
            # path — and accumulate in f32 via preferred_element_type; only
            # the softmax bookkeeping lives in f32.  m and l stay
            # lane-replicated (sub_q, BLOCK), so no lane is ever sliced.
            cols = _rows(c, t.sub_k)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            s = lax.dot_general(q, k, _NT,
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(keep(q0, k0 + c * t.sub_k), s, NEG_INF)
            m_prev, l_prev = m_ref[rows, :], l_ref[rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)   # <= 1, finite by NEG_INF
            p = jnp.exp(s - _lanes(m_new, t.sub_k))   # masked entries → 0
            l_ref[rows, :] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
            m_ref[rows, :] = m_new
            acc_ref[rows, :] = (
                acc_ref[rows, :] * _lanes(alpha, dv)
                + jnp.dot(p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32))

        for start, stop, masked in _k_segments(
                q0, k0, t.tile_k // t.sub_k, t, causal, seq_len,
                q_tail=False, window=window):
            _loop(start, stop, functools.partial(sub_block, masked=masked))

    def _finish():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / _lanes(safe, dv)).astype(o_ref.dtype)
        # Per-row logsumexp: the single residual the backward needs.
        # Lane-replicated to a (rows, BLOCK) tile: Mosaic requires output
        # blocks whose last two dims are (8k, 128k), so a per-row vector
        # rides a full lane tile (the in-tree kernel's MIN_BLOCK_SIZE
        # trick); the caller reads lane 0.
        lse_ref[0] = m_ref[...] + jnp.log(safe)
    _when(ik == n_kt - 1, _finish)


def _vmem(block_bytes, scratch_bytes, t):
    """`compiler_params` for a kernel whose double-buffered blocks, scratch
    and a sub-block's f32 intermediates pass the default scoped VMEM (16 MiB
    on the v5e); nothing where they do not."""
    need = 2 * block_bytes + scratch_bytes + 8 * 4 * t.sub_q * t.sub_k
    if need <= 12 << 20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(2 * need, 96 << 20))}


def _seq_len(true_len, *padded):
    """None where nothing is padded: the tail tests are then not compiled."""
    return None if all(true_len == s for s in padded) else true_len


def _kv_index(t, causal, n_kt):
    """Block index of k / v for the q-major kernels.  Above the diagonal the
    kernel enters nothing; holding the index at the last tile it needs
    spares the copy of a tile nobody reads."""
    if causal and n_kt > 1:
        return lambda b, i, j: (
            b, jnp.minimum(j, ((i + 1) * t.tile_q - 1) // t.tile_k), 0)
    return lambda b, i, j: (b, j, 0)


@functools.partial(jax.jit, static_argnames=(
    "t", "causal", "scale", "true_len", "interpret", "window"))
def _fwd_tiles(q3, k3, v3, *, t, causal, scale, true_len, interpret,
               window=None):
    """The forward call at the tiles ``t``, under `jax.jit` so that the
    layers of a model share one trace of the kernel."""
    bh, s_pad, d = q3.shape
    dv = v3.shape[-1]
    q3 = _pad_to(q3, t.tile_q, 1)
    k3, v3 = _pad_to(k3, t.tile_k, 1), _pad_to(v3, t.tile_k, 1)
    s_q, s_k = q3.shape[1], k3.shape[1]
    n_qt, n_kt = s_q // t.tile_q, s_k // t.tile_k
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        seq_len=_seq_len(true_len, s_q, s_k), t=t, n_qt=n_qt, n_kt=n_kt,
        window=window)
    kv = _kv_index(t, causal, n_kt)
    size = q3.dtype.itemsize
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_qt, n_kt),
        in_specs=[
            pl.BlockSpec((1, t.tile_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, t.tile_k, d), kv),
            pl.BlockSpec((1, t.tile_k, dv), kv),
        ],
        out_specs=[
            pl.BlockSpec((1, t.tile_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, t.tile_q, BLOCK), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_q, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, s_q, BLOCK), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((t.tile_q, dv), jnp.float32),     # acc
            pltpu.VMEM((t.tile_q, BLOCK), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((t.tile_q, BLOCK), jnp.float32),  # l
        ],
        interpret=interpret,
        **_vmem(size * (t.tile_q * (d + dv) + t.tile_k * (d + dv))
                + 4 * t.tile_q * BLOCK,
                4 * t.tile_q * (dv + 2 * BLOCK), t),
        **_named("flash_fwd"),
    )(q3, k3, v3)
    return out[:, :s_pad], lse[:, :s_pad]


def _fwd_call(q3, k3, v3, *, causal, scale, true_len, interpret,
              window=None, blk_q=None, blk_k=None):
    """``q3,k3: [BH, S_pad, D_pad]``, ``v3: [BH, S_pad, Dv_pad]`` already
    padded to BLOCK/lane tiles (``Dv_pad`` may differ from ``D_pad``: the
    accumulator and the output take v's width); returns ``(out [BH, S_pad,
    Dv_pad], lse [BH, S_pad, BLOCK])``.  ``true_len`` masks the padded K
    tail so it carries no softmax mass.  Tiles and sub-blocks are
    `tile_plan`'s; ``blk_q`` / ``blk_k`` force the sub-block."""
    plan = tile_plan(q3.shape[1], q3.shape[2], v3.shape[2], causal,
                     blk_q=blk_q, blk_k=blk_k, window=window)
    return _fwd_tiles(q3, k3, v3, t=plan.tiles["flash_fwd"], causal=causal,
                      scale=scale, true_len=true_len, interpret=interpret,
                      window=window)


def _to_bh(x):
    """[B, S, H, D] → [B*H, S, D]."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x3, b, h):
    bh, s, d = x3.shape
    return x3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, interpret, window=None):
    out, _ = _flash_fwd_res(q, k, v, causal, scale, interpret, window)
    return out


def _flash_fwd_res(q, k, v, causal, scale, interpret, window=None):
    b, s, h, d = q.shape
    q3 = _pad_to(_pad_to(_to_bh(q), BLOCK, 1), BLOCK, 2)
    k3 = _pad_to(_pad_to(_to_bh(k), BLOCK, 1), BLOCK, 2)
    v3 = _pad_to(_pad_to(_to_bh(v), BLOCK, 1), BLOCK, 2)
    out3, lse3 = _fwd_call(q3, k3, v3, causal=causal, scale=scale,
                           true_len=s, interpret=interpret, window=window)
    # the residuals `SAVE_FLASH` keeps across a rematerialised block
    out = checkpoint_name(_from_bh(out3[:, :s, :v.shape[-1]], b, h),
                          "flash_out")
    lse = checkpoint_name(lse3[:, :s, 0].reshape(b, h, s), "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_fwd_vjp(q, k, v, causal, scale, interpret, window):
    return _flash_fwd_res(q, k, v, causal, scale, interpret, window)


def _bwd_probs(q, k, v, do, lse, delta, keep, scale):
    """Shared bwd sub-block math: recomputed ``p`` from the saved logsumexp
    and ``ds`` — the (sub_q, sub_k) pieces both backward kernels need.
    ``lse`` and ``delta`` are lane-replicated ``(sub_q, BLOCK)``.  Masking
    (``keep`` is None in the interior) happens BEFORE the exp: padded q rows
    carry lse = -inf-ish, and ``exp(s - lse)`` would overflow where the
    forward's own mask kept it finite."""
    n = k.shape[0]
    e = lax.dot_general(q, k, _NT,
                        preferred_element_type=jnp.float32) * scale
    e = e - _lanes(lse, n)
    if keep is not None:
        e = jnp.where(keep, e, NEG_INF)
    p = jnp.exp(e)
    dp = lax.dot_general(do, v, _NT, preferred_element_type=jnp.float32)
    ds = p * (dp - _lanes(delta, n)) * scale
    return p, ds


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                     scale, causal, seq_len, t, n_qt, n_kt, window=None):
    """dk and dv of a k tile — and, where the head's whole q side is the one
    q tile (``n_qt == 1``), dq too: its rows are all in VMEM while the k
    tiles go by, so ``ds @ k`` adds into a third accumulator that is zeroed
    at the head's first k tile and written at its last, and `flash_bwd_dq`
    is not called (``refs``: the outputs, then their accumulators)."""
    iq, ik = _tile_ids(n_qt, n_kt, 2, 1)   # k tile major, q sweep minor
    whole = n_qt == 1
    if whole:
        dk_ref, dv_ref, dq_ref, dk_acc, dv_acc, dq_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs

    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
    _when(iq == 0, _init)

    def _init_dq():
        dq_acc[...] = jnp.zeros_like(dq_acc)
    _when(whole and ik == 0, _init_dq)

    keep = _masker(t, causal, seq_len, q_tail=True, window=window)
    for c in range(t.tile_k // t.sub_k):
        cols = _rows(c, t.sub_k)
        q0, k0 = iq * t.tile_q, ik * t.tile_k + c * t.sub_k
        k, v = k_ref[0, cols, :], v_ref[0, cols, :]

        def sub_block(a, masked):
            rows = _rows(a, t.sub_q)
            q, do = q_ref[0, rows, :], do_ref[0, rows, :]
            p, ds = _bwd_probs(
                q, k, v, do, lse_ref[0, rows, :], delta_ref[0, rows, :],
                keep(q0 + a * t.sub_q, k0) if masked else None, scale)
            dv_acc[cols, :] += lax.dot_general(
                p.astype(do.dtype), do, _TN,
                preferred_element_type=jnp.float32)
            ds = ds.astype(q.dtype)
            dk_acc[cols, :] += lax.dot_general(
                ds, q, _TN, preferred_element_type=jnp.float32)
            if whole:   # as `_bwd_dq_kernel`: k sub-blocks in ascending order
                dq_acc[rows, :] += jnp.dot(
                    ds, k, preferred_element_type=jnp.float32)

        for start, stop, masked in _q_segments(
                k0, q0, t.tile_q // t.sub_q, t, causal, seq_len, window):
            _loop(start, stop, functools.partial(sub_block, masked=masked))

    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
    _when(iq == n_qt - 1, _finish)

    def _finish_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
    _when(whole and ik == n_kt - 1, _finish_dq)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, causal, seq_len, t, n_qt, n_kt, window=None):
    iq, ik = _tile_ids(n_qt, n_kt, 1, 2)   # q tile major, k sweep minor

    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)
    _when(ik == 0, _init)

    keep = _masker(t, causal, seq_len, q_tail=True, window=window)
    for a in range(t.tile_q // t.sub_q):
        rows = _rows(a, t.sub_q)
        q0, k0 = iq * t.tile_q + a * t.sub_q, ik * t.tile_k
        q, do = q_ref[0, rows, :], do_ref[0, rows, :]
        lse, delta = lse_ref[0, rows, :], delta_ref[0, rows, :]

        def sub_block(c, masked):
            cols = _rows(c, t.sub_k)
            k, v = k_ref[0, cols, :], v_ref[0, cols, :]
            _, ds = _bwd_probs(
                q, k, v, do, lse, delta,
                keep(q0, k0 + c * t.sub_k) if masked else None, scale)
            dq_acc[rows, :] += jnp.dot(ds.astype(k.dtype), k,
                                       preferred_element_type=jnp.float32)

        for start, stop, masked in _k_segments(
                q0, k0, t.tile_k // t.sub_k, t, causal, seq_len,
                q_tail=True, window=window):
            _loop(start, stop, functools.partial(sub_block, masked=masked))

    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)
    _when(ik == n_kt - 1, _finish)


@functools.partial(jax.jit, static_argnames=(
    "t_dkdv", "t_dq", "causal", "scale", "true_len", "interpret", "window"))
def _bwd_tiles(q3, k3, v3, do3, lse2, delta2, *, t_dkdv, t_dq, causal, scale,
               true_len, interpret, window=None):
    """The backward at its tiles, under `jax.jit` like `_fwd_tiles`: one
    call where `tile_plan` names no `flash_bwd_dq` (``t_dq`` None: the q side
    is whole in `flash_bwd_dkdv`'s tile, which then writes dq too), else
    two.  Each pads its q-aligned and its k-aligned operands to
    whole tiles of its own: a block past the array would read undefined
    bytes on the chip (0 * non-finite garbage = NaN through the
    accumulators even though the mask zeroes p); outputs are sliced back."""
    bh, s_pad, d = q3.shape
    dv = v3.shape[-1]
    size = q3.dtype.itemsize

    def call(kernel, name, t, q_major, outs):
        """One backward call; ``outs`` are ``(side, width)`` of its outputs
        (and of their f32 accumulators), a side being ``"q"`` or ``"k"``."""
        q, do, lse, delta = (_pad_to(x, t.tile_q, 1)
                             for x in (q3, do3, lse2, delta2))
        k, v = _pad_to(k3, t.tile_k, 1), _pad_to(v3, t.tile_k, 1)
        s_q, s_k = q.shape[1], k.shape[1]
        n_qt, n_kt = s_q // t.tile_q, s_k // t.tile_k
        if q_major:      # grid (b, q tile, k tile)
            grid, qi = (bh, n_qt, n_kt), lambda b, i, j: (b, i, 0)
            ki = _kv_index(t, causal, n_kt)
        else:            # grid (b, k tile, q tile); as `_kv_index`, mirrored
            grid, ki = (bh, n_kt, n_qt), lambda b, j, i: (b, j, 0)
            if causal and n_qt > 1:
                qi = lambda b, j, i: (
                    b, jnp.maximum(i, j * t.tile_k // t.tile_q), 0)
            else:
                qi = lambda b, j, i: (b, i, 0)
        side = {"q": (t.tile_q, s_q, qi), "k": (t.tile_k, s_k, ki)}
        spec = lambda where, width: pl.BlockSpec(
            (1, side[where][0], width), side[where][2])
        blocks = (size * (t.tile_q + t.tile_k) * (d + dv)
                  + 2 * 4 * t.tile_q * BLOCK
                  + size * sum(side[w][0] * n for w, n in outs))
        return pl.pallas_call(
            functools.partial(
                kernel, scale=scale, causal=causal, t=t, n_qt=n_qt,
                n_kt=n_kt, seq_len=_seq_len(true_len, s_q, s_k),
                window=window),
            grid=grid,
            in_specs=[spec("q", d), spec("k", d), spec("k", dv),
                      spec("q", dv), spec("q", BLOCK), spec("q", BLOCK)],
            out_specs=[spec(w, n) for w, n in outs],
            out_shape=[jax.ShapeDtypeStruct((bh, side[w][1], n), q3.dtype)
                       for w, n in outs],
            scratch_shapes=[pltpu.VMEM((side[w][0], n), jnp.float32)
                            for w, n in outs],
            interpret=interpret,
            **_vmem(blocks, 4 * sum(side[w][0] * n for w, n in outs), t),
            **_named(name),
        )(q, k, v, do, lse, delta)

    whole = t_dq is None
    dk3, dv3, *rest = call(_bwd_dkdv_kernel, "flash_bwd_dkdv", t_dkdv, False,
                           [("k", d), ("k", dv)] + [("q", d)] * whole)
    dq3, = rest if whole else call(
        _bwd_dq_kernel, "flash_bwd_dq", t_dq, True, [("q", d)])
    return dq3[:, :s_pad], dk3[:, :s_pad], dv3[:, :s_pad]


def _bwd_call(q3, k3, v3, do3, lse2, delta2, *, causal, scale, true_len,
              interpret, window=None, blk_q=None, blk_k=None):
    """``q3,k3: [BH, S_pad, D_pad]``; ``v3,do3: [BH, S_pad, Dv_pad]``;
    ``lse2, delta2: [BH, S_pad, BLOCK]`` f32, lane-replicated (same MIN_BLOCK_SIZE trick as
    the forward's lse output — Mosaic wants (8k, 128k) tiles).  Returns
    ``(dq, dk, dv)`` padded like the inputs.  Tiles and sub-blocks are
    `tile_plan`'s; ``blk_q`` / ``blk_k`` force the sub-block."""
    plan = tile_plan(q3.shape[1], q3.shape[2], v3.shape[2], causal,
                     blk_q=blk_q, blk_k=blk_k, window=window)
    return _bwd_tiles(q3, k3, v3, do3, lse2, delta2,
                      t_dkdv=plan.tiles["flash_bwd_dkdv"],
                      t_dq=plan.tiles.get("flash_bwd_dq"), causal=causal,
                      scale=scale, true_len=true_len, interpret=interpret,
                      window=window)


def _flash_bwd(causal, scale, interpret, window, res, dout, dlse=None):
    """Pallas blockwise backward from the saved logsumexp: the dk / dv kernel
    sweeping q per block of k, which writes dq too where the q side is
    whole, else a dq kernel sweeping k per block of q (FlashAttention-2
    style); every live intermediate is one sub-block in VMEM.  ``dlse``
    (``[B, H, S]``, `_flash_lse` only) is the cotangent of the logsumexp:
    ``ds = p (dp - delta) + p dlse``, so it is taken off ``delta``."""
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    pad3 = lambda x: _pad_to(_pad_to(_to_bh(x), BLOCK, 1), BLOCK, 2)
    q3, k3, v3, do3, o3 = pad3(q), pad3(k), pad3(v), pad3(dout), pad3(out)
    s_pad = q3.shape[1]
    # delta = rowsum(dout * out): the only extra residual FA-2 needs.
    # Padded rows are all-zero -> delta 0 there; lse pads with NEG_INF so
    # the kernels' q_pos mask (not the pad value) is what keeps them inert.
    delta2 = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), -1)
    if dlse is not None:
        delta2 = delta2 - jnp.pad(
            dlse.reshape(b * h, s).astype(jnp.float32),
            ((0, 0), (0, s_pad - s)))
    lse2 = jnp.pad(lse.reshape(b * h, s), ((0, 0), (0, s_pad - s)),
                   constant_values=NEG_INF).astype(jnp.float32)
    rep = lambda x2: jnp.broadcast_to(x2[..., None], x2.shape + (BLOCK,))
    dq3, dk3, dv3 = _bwd_call(q3, k3, v3, do3, rep(lse2), rep(delta2),
                              causal=causal, scale=scale, true_len=s,
                              interpret=interpret, window=window)
    back = lambda x3, w: _from_bh(x3[:, :s, :w], b, h).astype(q.dtype)
    return back(dq3, d), back(dk3, d), back(dv3, v.shape[-1])


_flash.defvjp(_flash_fwd_vjp, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_lse(q, k, v, causal, scale, interpret, window=None):
    """`_flash` with the row statistics as a second output: the per-row
    logsumexp of the scaled, masked scores, ``[B, H, S]`` f32, which the
    forward kernel writes anyway for its backward.  With it two softmaxes
    over different key sets join exactly outside the kernels
    (`ops.eva_attention`: ``o = o_1 e^{lse_1 - lse} + o_2 e^{lse_2 - lse}``,
    ``lse = logaddexp(lse_1, lse_2)``), and it is differentiable: ``d lse /
    d s_j = p_j``, so its cotangent goes into the backward kernels through
    the one per-row term they already subtract from ``dP`` (``delta - dlse``
    in place of ``delta``): the kernels are the same, and `_flash` is the
    `jax.custom_vjp` it was."""
    return _flash_lse_fwd(q, k, v, causal, scale, interpret, window)[0]


def _flash_lse_fwd(q, k, v, causal, scale, interpret, window):
    out, res = _flash_fwd_res(q, k, v, causal, scale, interpret, window)
    return (out, res[-1]), res


def _flash_lse_bwd(causal, scale, interpret, window, res, cotangents):
    return _flash_bwd(causal, scale, interpret, window, res, *cotangents)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, window: int | None = None,
                    return_lse: bool = False, impl: str = "mosaic"):
    """Exact attention, O(S·BLOCK) memory.  ``q,k: [B, S, H, D]``,
    ``v: [B, S, H, Dv]`` → ``[B, S, H, Dv]``; ``Dv`` may differ from ``D``
    (latent attention trains with a 192-wide q / k and a 128-wide v), each
    padded to its own multiple of the 128-lane tile, so v is never widened
    to q's width — drop-in for `ring_attention.dense_attention`
    (`/root/reference` has no attention at all; this is the long-context
    hot-op layer of the TPU framework).  ``impl="interpret"`` runs the
    kernels under the Pallas interpreter (the CPU mesh, by name); the
    default lowers through Mosaic and fails to compile anywhere but on a
    TPU.  ``window=W`` (with ``causal``) keeps, for row ``i``, the ``W``
    keys ``j`` with ``0 <= i - j < W``, the row's own among them: the
    kernels then enter only the sub-blocks the band touches and mask the
    ones its two edges cross.  ``window=None`` is the program it was before
    there were windows, traced and compiled the same.  ``return_lse=True``
    returns ``(out, lse)`` with ``lse: [B, H, S]`` f32, each row's
    ``log sum_j exp(scale q . k_j)`` over the keys it sees, differentiable
    like ``out``; without it the call is the program it was."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    if window is not None and (not causal or window < 1):
        raise ValueError("a window of at least one key, under a causal mask")
    fn = _flash_lse if return_lse else _flash
    return fn(q, k, v, causal, scale, use_interpreter(impl), window)
