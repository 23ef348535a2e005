"""Pallas TPU kernels for the codec hot path (L2a compute).

The reference's compression pipeline is host-side C (c-blosc byte-shuffle +
blosclz, `/root/reference/mpi_comms.py:18-30`) applied to pickled gradients.
The TPU-native hot path never leaves HBM, so "compression" is an on-device
transform; these kernels are the custom-op layer for it:

* ``block_quantize`` — fused abs-max → scale → round → int8 cast, one VMEM
  pass per (block_rows, 128) tile with a **per-block scale** (finer-grained
  than the reference's per-tensor path, strictly lower quantization error).
  Single grid sweep: each grid step owns one tile, computes its own scale,
  writes its quantized tile and its scale slot — no second pass, no host
  round-trip.
* ``block_dequant_sum`` — the decode-sum hot op: given codes all-gathered
  across ranks (leading world dim), dequantize every rank's tile and
  accumulate the cross-rank **sum** (`/root/reference/ps.py:176` semantics)
  in one pass; the world loop rides the sequential TPU grid with an
  f32 VMEM accumulator.

Each kernel has a ``jnp`` reference with identical math (``*_ref``) and can
run under the Pallas interpreter.  Neither is ever chosen silently: the
dispatchers take ``impl`` — ``"mosaic"`` (the compiled TPU kernel, the
default), ``"interpret"`` or ``"ref"`` — and whoever owns the devices picks
it from their platform (`impl_for_platform`; `ops.codecs.get_codec` does so
for the optimizers).  ``tests/test_pallas_kernels.py`` asserts kernel ==
reference on the CPU; ``chip_smoke.py`` asserts it on the chip.

Layout contract: gradients of any rank/shape are flattened and zero-padded to
``(rows, 128)`` with ``rows`` a multiple of the sublane tile — zero padding is
harmless for abs-max and dequant-sum alike.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..errors import KernelPlatformError

LANE = 128
# Rows per kernel tile: 512*128 f32 = 256 KB in VMEM, comfortable double-buffer.
BLOCK_ROWS = 512

IMPLS = ("mosaic", "interpret", "ref")


def impl_for_platform(platform: str, *, cpu: str = "ref") -> str:
    """The kernel implementation for a program built on ``platform``
    devices (``mesh.devices.flat[0].platform``, never the process-global
    default backend): the Mosaic kernel on TPUs; on the CPU — the virtual
    test mesh — the implementation the caller names with ``cpu=`` (the
    ``jnp`` reference for the codec kernels, ``"interpret"`` where no
    reference exists).  Any other platform has no implementation here."""
    if platform == "tpu":
        return "mosaic"
    if platform == "cpu":
        return cpu
    raise KernelPlatformError(
        f"no Pallas kernel implementation for platform {platform!r} "
        f"(have tpu, and cpu by name)")


def check_impl(impl: str, platform: str) -> None:
    """Refuse a Mosaic kernel on devices that cannot run it (typed, at
    construction — before Pallas's own lowering error deep in a compile)."""
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS}, got {impl!r}")
    if impl == "mosaic" and platform != "tpu":
        raise KernelPlatformError(
            f"impl='mosaic' needs TPU devices, but this program is built "
            f"for {platform!r}; name impl='ref' or impl='interpret' to run "
            f"off the chip")


def use_interpreter(impl: str) -> bool:
    """``interpret=`` for a `pallas_call` under ``impl`` (not ``"ref"``)."""
    if impl not in ("mosaic", "interpret"):
        raise ValueError(
            f"impl must be 'mosaic' or 'interpret' here, got {impl!r}")
    return impl == "interpret"


def _qmax(bits: int) -> float:
    return float(2 ** (bits - 1) - 1)


def pad_to_blocks(flat: jax.Array, block_rows: int = BLOCK_ROWS):
    """Zero-pad a 1-D array and reshape to ``(n_blocks * block_rows, LANE)``.

    Returns ``(padded_2d, n_blocks)``.  Zero padding is exact for the codecs
    here: zeros quantize to zero and contribute nothing to block abs-max
    (scale) or to the decode sum.
    """
    n = flat.shape[0]
    per_block = block_rows * LANE
    n_blocks = max(1, -(-n // per_block))
    padded = jnp.zeros((n_blocks * per_block,), flat.dtype).at[:n].set(flat)
    return padded.reshape(n_blocks * block_rows, LANE), n_blocks


# ---------------------------------------------------------------------------
# block quantize (encode)
# ---------------------------------------------------------------------------


def _quantize_kernel(x_ref, q_ref, scale_ref, *, qmax: float):
    # scale_ref is the full (n_blocks, 1) SMEM array (scalar outputs can't be
    # tiled into sub-(8,128) blocks); each grid step writes its own slot.
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / qmax, 1.0)
    scale_ref[i, 0] = scale
    q = jnp.clip(jnp.round(x / scale), -qmax, qmax)
    q_ref[:] = q.astype(q_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bits", "block_rows",
                                             "interpret"))
def block_quantize_tpu(x2d: jax.Array, *, bits: int = 8,
                       block_rows: int = BLOCK_ROWS,
                       interpret: bool = False):
    """Pallas path: ``x2d`` is ``(n_blocks*block_rows, LANE)`` f32-ish.

    ``interpret=True`` runs the same kernel under the Pallas interpreter
    (CPU parity tests)."""
    n_blocks = x2d.shape[0] // block_rows
    qdtype = jnp.int8 if bits == 8 else jnp.int16
    kernel = functools.partial(_quantize_kernel, qmax=_qmax(bits))
    q, scales = pl.pallas_call(
        kernel,
        grid=(n_blocks,),
        in_specs=[pl.BlockSpec((block_rows, LANE), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, LANE), lambda i: (i, 0)),
            pl.BlockSpec((n_blocks, 1), lambda i: (0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x2d.shape, qdtype),
            jax.ShapeDtypeStruct((n_blocks, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x2d)
    return q, scales


def block_quantize_ref(x2d: jax.Array, *, bits: int = 8,
                       block_rows: int = BLOCK_ROWS):
    """jnp reference with identical math (``impl="ref"``, parity tests)."""
    qmax = _qmax(bits)
    qdtype = jnp.int8 if bits == 8 else jnp.int16
    n_blocks = x2d.shape[0] // block_rows
    blocks = x2d.astype(jnp.float32).reshape(n_blocks, block_rows * LANE)
    amax = jnp.max(jnp.abs(blocks), axis=1, keepdims=True)
    scales = jnp.where(amax > 0, amax / qmax, 1.0)
    q = jnp.clip(jnp.round(blocks / scales), -qmax, qmax).astype(qdtype)
    return q.reshape(x2d.shape), scales.astype(jnp.float32)


def block_quantize(x2d, *, bits=8, block_rows=BLOCK_ROWS, impl="mosaic"):
    if impl == "ref":
        return block_quantize_ref(x2d, bits=bits, block_rows=block_rows)
    return block_quantize_tpu(x2d, bits=bits, block_rows=block_rows,
                              interpret=use_interpreter(impl))


# ---------------------------------------------------------------------------
# block dequantize + cross-rank sum (decode_sum)
# ---------------------------------------------------------------------------


def _dequant_sum_kernel(q_ref, scale_ref, out_ref):
    # Grid = (n_blocks, world) with world *minor*: for a fixed block j the
    # rank index i sweeps consecutively, so the out tile stays resident in
    # VMEM while the cross-rank sum accumulates into it.
    j, i = pl.program_id(0), pl.program_id(1)
    x = q_ref[0].astype(jnp.float32) * scale_ref[i, j, 0]

    @pl.when(i == 0)
    def _init():
        out_ref[:] = x

    @pl.when(i > 0)
    def _acc():
        out_ref[:] += x


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def block_dequant_sum_tpu(q: jax.Array, scales: jax.Array, *,
                          block_rows: int = BLOCK_ROWS,
                          interpret: bool = False):
    """``q``: (world, rows, LANE) int8/int16; ``scales``: (world, n_blocks, 1).

    Returns f32 ``(rows, LANE)`` = sum over the world dim of q*scale.
    """
    world, rows, _ = q.shape
    n_blocks = rows // block_rows
    out = pl.pallas_call(
        _dequant_sum_kernel,
        grid=(n_blocks, world),
        in_specs=[
            pl.BlockSpec((1, block_rows, LANE), lambda j, i: (i, j, 0)),
            pl.BlockSpec((world, n_blocks, 1), lambda j, i: (0, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(q, scales)
    return out


def block_dequant_sum_ref(q, scales, *, block_rows: int = BLOCK_ROWS):
    world, rows, _ = q.shape
    n_blocks = rows // block_rows
    deq = (q.astype(jnp.float32).reshape(world, n_blocks, block_rows * LANE)
           * scales.reshape(world, n_blocks, 1))
    return deq.sum(axis=0).reshape(rows, LANE)


def block_dequant_sum(q, scales, *, block_rows=BLOCK_ROWS, impl="mosaic"):
    if impl == "ref":
        return block_dequant_sum_ref(q, scales, block_rows=block_rows)
    return block_dequant_sum_tpu(q, scales, block_rows=block_rows,
                                 interpret=use_interpreter(impl))


# ---------------------------------------------------------------------------
# cast decode + cross-rank sum (CastCodec's fused decode_sum)
# ---------------------------------------------------------------------------
# The generic Codec.decode_sum vmaps decode over the world dim and then
# sums: for the bf16-wire CastCodec that MATERIALIZES a full (world, n) f32
# intermediate in HBM — world x the dense gradient — before the reduction
# reads it back.  The fused kernel never does: each grid step loads ONE
# rank's bf16 tile, upcasts in VMEM, and accumulates into the f32 output
# tile (world minor in the grid, so the accumulator stays VMEM-resident) —
# wire bytes in, dense f32 out, one pass.  Same shape as
# `_dequant_sum_kernel` minus the scale plane.


def _cast_sum_kernel(x_ref, out_ref):
    # Grid = (n_blocks, world) with world *minor*: for a fixed block j the
    # rank index i sweeps consecutively and the out tile stays resident.
    i = pl.program_id(1)
    x = x_ref[0].astype(jnp.float32)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = x

    @pl.when(i > 0)
    def _acc():
        out_ref[:] += x


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cast_sum_tpu(x: jax.Array, *, block_rows: int = BLOCK_ROWS,
                 interpret: bool = False):
    """``x``: (world, rows, LANE) wire-dtype (bf16/f16/f32).

    Returns f32 ``(rows, LANE)`` = sum over the world dim, accumulated in
    f32 (only the per-rank *representation* is narrow, never the
    reduction).  ``interpret=True`` runs the same kernel under the Pallas
    interpreter — the CPU parity path.
    """
    world, rows, _ = x.shape
    n_blocks = rows // block_rows
    return pl.pallas_call(
        _cast_sum_kernel,
        grid=(n_blocks, world),
        in_specs=[pl.BlockSpec((1, block_rows, LANE), lambda j, i: (i, j, 0))],
        out_specs=pl.BlockSpec((block_rows, LANE), lambda j, i: (j, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANE), jnp.float32),
        interpret=interpret,
    )(x)


def cast_sum_ref(x, *, block_rows: int = BLOCK_ROWS):
    """jnp reference with identical math (``impl="ref"``, parity tests)."""
    return x.astype(jnp.float32).sum(axis=0)


def cast_sum(x, *, block_rows=BLOCK_ROWS, impl="mosaic"):
    if impl == "ref":
        return cast_sum_ref(x, block_rows=block_rows)
    return cast_sum_tpu(x, block_rows=block_rows,
                        interpret=use_interpreter(impl))


def rows_for_flat(n: int, block_rows: int = BLOCK_ROWS) -> int:
    """Per-tensor tile height for a flat n-element payload: the smallest
    sublane-aligned block that holds it, capped at ``block_rows`` (so a
    (128,) bias costs an 8x128 tile, not a full 512x128 block)."""
    need = -(-n // LANE)               # rows to hold n elements
    aligned = -(-need // 8) * 8        # sublane multiple
    return min(block_rows, max(8, aligned))


# ---------------------------------------------------------------------------
# sign bit-packing (1 bit/element on the wire)
# ---------------------------------------------------------------------------
# Bitwise pack/unpack lowers to a handful of VPU shifts/ors under XLA; a
# dedicated Pallas kernel adds nothing over the fused jnp form, so this is
# the jnp form (it runs on-device on both backends).


def pack_signs(flat: jax.Array) -> jax.Array:
    """``flat`` f32 ``(n,)`` with n % 8 == 0 → uint8 ``(n//8,)`` of sign bits
    (bit k of byte b = sign of element 8*b+k; 1 means >= 0)."""
    bits = (flat >= 0).astype(jnp.uint8).reshape(-1, 8)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    return jnp.sum(bits << shifts, axis=1).astype(jnp.uint8)


def unpack_signs(packed: jax.Array, n: int) -> jax.Array:
    """Inverse of `pack_signs`: uint8 ``(n//8,)`` → f32 ``(n,)`` of ±1."""
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (packed[:, None] >> shifts) & jnp.uint8(1)
    return (bits.astype(jnp.float32) * 2.0 - 1.0).reshape(-1)[:n]
