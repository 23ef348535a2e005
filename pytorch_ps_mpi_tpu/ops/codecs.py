"""Gradient codecs — the pluggable compression hook (L2a).

The reference's compression plug-point is an external ``codings`` object with
``.encode(tensor) -> code`` and ``.decode(code) -> ndarray``
(`/root/reference/ps.py:18,65-66,165-166`); codes ride the wire as
pickle+blosc bytes of *unknown size*, which forces the whole size-exchange
machinery (`mpi_comms.py:144-174`).

TPU-native redesign: a codec is a pair of **jit-traceable pure functions**
whose code is a pytree of **static-shape** arrays.  Variable-size compressed
payloads (the reference's hard problem, README.md:30-46) are handled the way
its Protocol B intended — a fixed maximum size chosen up front — but natively:
top-k keeps exactly ``k`` (values, indices) pairs per parameter, quantization
keeps the full shape at a narrower dtype.  No pickling, no sentinel bytes, no
size registry: the code pytree flattens straight into device buffers
(realizing the zero-copy intent of `/root/reference/serialization.py:22-23`).

Lossy codecs happen **before** the cross-rank sum, matching the reference
semantics (each rank's gradient is encoded, shipped, decoded, then summed —
`ps.py:165-176`), so compression error behaves identically.
"""

from __future__ import annotations

import math
from collections import OrderedDict
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Code = Any


class Codec:
    """Interface: ``encode(grad) -> code`` / ``decode(code, shape=, dtype=) ->
    grad``.

    All decodes take the dense ``shape``/``dtype`` keywords (codecs that don't
    need them ignore them), so the PS layer can drive any codec uniformly.
    ``decode_sum`` is the hot-path hook: given codes all-gathered across ranks
    (every leaf grows a leading world-size dim), produce the **sum** of the
    per-rank decoded gradients — the reference's decode-loop-then-``sum(grads)``
    (`/root/reference/ps.py:165-176`) fused into one op.  ``wire_bytes(shape,
    dtype)`` reports the on-wire payload size for the ``packaged_bytes`` metric
    (`/root/reference/ps.py:129-136`).
    """

    name = "codec"

    # Which implementation a Pallas-backed codec's kernels run
    # (`ops.pallas_kernels.IMPLS`); None for codecs with no kernel.
    # `get_codec` picks it from the platform of the devices the program is
    # built for, and refuses a Mosaic kernel on anything but a TPU.
    impl: "str | None" = None

    # Whether ``decode`` recovers a SINGLE contribution's gradient.  True
    # for every codec here; a sketch-style codec (FetchSGD-like count
    # sketches) whose only decodable quantity is the cross-contributor sum
    # sets this False, and the robust-aggregation layer then refuses any
    # reducer that needs per-contribution decodes (`ops.robust.
    # check_reducer_codec` raises the typed `ReducerCodecError` instead of
    # silently applying un-reduced gradients through ``decode_sum``).
    itemwise_decode = True

    def encode(self, grad: jax.Array) -> Code:
        raise NotImplementedError

    def decode(self, code: Code, *, shape=None, dtype=None) -> jax.Array:
        raise NotImplementedError

    def decode_sum(self, codes: Code, *, shape, dtype) -> jax.Array:
        decoded = jax.vmap(
            lambda c: self.decode(c, shape=shape, dtype=dtype))(codes)
        return decoded.sum(axis=0)

    def wire_bytes(self, shape, dtype) -> int:
        raise NotImplementedError

    def scale_code(self, code: Code, w) -> Code:
        """Scale the *decoded value* of a code by scalar ``w`` without
        decoding it — the hook the async PS's staleness weighting uses to
        damp stale gradients while keeping the fused decode-sum path.

        **Interface contract** (what makes the default implementation
        valid): a code pytree may carry at most ONE float-dtype "magnitude"
        axis per decoded element — decode must be *linear* in the floating
        leaves jointly scaled, i.e. ``decode(scale_code(c, w)) ==
        w * decode(c)``.  Integer leaves (indices, quantized planes) are
        left untouched.  A codec whose decode *multiplies two float leaves
        together* (e.g. a values × scale-factor factorization) violates
        this — the default would damp by ``w**2`` — and MUST override
        ``scale_code`` to scale exactly one factor.  Every registered codec
        is checked against this contract in ``tests/test_codecs.py::
        test_scale_code_is_linear_for_all_codecs``."""
        return jax.tree.map(
            lambda x: (x * jnp.asarray(w).astype(x.dtype)
                       if jnp.issubdtype(x.dtype, jnp.floating) else x),
            code)

    def __repr__(self) -> str:  # pragma: no cover
        return f"{type(self).__name__}()"


class IdentityCodec(Codec):
    """No compression — the default path.  With this codec the PS step's
    gather+decode+sum fuses into a single ``psum`` all-reduce."""

    name = "identity"

    def encode(self, grad):
        return grad

    def decode(self, code, *, shape=None, dtype=None):
        return code

    def wire_bytes(self, shape, dtype):
        return int(np.prod(shape)) * np.dtype(dtype).itemsize


class CastCodec(Codec):
    """Dtype-cast compression — ship gradients as bfloat16 (or float16).

    The cheapest wire lever: exactly one VPU cast each way, halves the
    all-gather payload of f32 gradients, and bf16 keeps f32's exponent
    range so no scale bookkeeping is needed.  The decode-sum accumulates
    in the dense dtype (f32), so only the per-rank *representation* is
    lossy, not the reduction.
    """

    def __init__(self, dtype=jnp.bfloat16, impl: str = "mosaic"):
        self.wire_dtype = jnp.dtype(dtype)
        self.impl = impl
        # Name tracks the wire dtype: the multihost handshake compares
        # codec names, and a float16 CastCodec must not pass as bf16.
        self.name = self.wire_dtype.name.replace("bfloat", "bf").replace(
            "float", "f")

    def encode(self, grad):
        return grad.astype(self.wire_dtype)

    def decode(self, code, *, shape=None, dtype=None):
        return code.astype(jnp.float32 if dtype is None else dtype)

    def decode_sum(self, codes, *, shape, dtype):
        """Fused wire-dtype -> f32-accumulate cross-rank sum.

        The inherited vmap-decode-then-sum materializes a (world, n) f32
        intermediate — world x the dense gradient in HBM — before reducing.
        The fused kernel (`ops.pallas_kernels.cast_sum`) upcasts each
        rank's bf16 tile in VMEM and accumulates straight into the f32
        output tile: wire bytes in, dense f32 out, one pass, no per-rank
        intermediates.  Accumulation is ALWAYS f32 (then cast to the dense
        dtype), so narrow wire dtypes never narrow the reduction.
        """
        from . import pallas_kernels as pk
        world = codes.shape[0]
        n = int(np.prod(shape))
        rows = pk.rows_for_flat(n)
        per_block = rows * pk.LANE
        n_blocks = max(1, -(-n // per_block))
        total = n_blocks * per_block
        flat = codes.reshape(world, -1)
        padded = jnp.zeros((world, total), flat.dtype).at[:, :n].set(flat)
        out = pk.cast_sum(padded.reshape(world, n_blocks * rows, pk.LANE),
                          block_rows=rows, impl=self.impl)
        dt = jnp.float32 if dtype is None else dtype
        return out.reshape(-1)[:n].reshape(shape).astype(dt)

    def wire_bytes(self, shape, dtype):
        return int(np.prod(shape)) * self.wire_dtype.itemsize


class TopKCodec(Codec):
    """Magnitude top-k sparsification.

    ``k`` is fixed per parameter shape at trace time (``fraction`` of the
    element count, floored at 1), so code shapes are static — the TPU answer
    to the reference's pad-to-max-bytes Protocol B (`mpi_comms.py:80-104`).
    Decode scatters the kept values back into a dense zero tensor.

    ``approx=True`` selects with ``lax.approx_max_k`` — the TPU-native
    selection primitive (Chern et al., arXiv:2206.14286) that replaces the
    full sort with a single-pass partial reduction on the VPU.  It returns
    ≥``recall_target`` of the true top-k (distinct indices, so the fused
    ``decode_sum`` scatter-add stays valid); the handful of swapped-in
    entries are the next-largest magnitudes, a negligible perturbation for
    a *lossy* codec already dropping 99% of entries — and EF (the
    ``error_feedback=True`` stream) absorbs even that, since anything not
    shipped lands in the residual.  The wire format is identical, so
    approx/exact interoperate freely across ranks.
    """

    name = "topk"

    def __init__(self, fraction: float = 0.01, k: int | None = None,
                 approx: bool = False, recall_target: float = 0.95):
        if k is not None and k <= 0:
            raise ValueError(f"k must be positive, got {k}")
        if k is None and not 0.0 < fraction <= 1.0:
            raise ValueError(f"fraction must be in (0, 1], got {fraction}")
        if not 0.0 < recall_target <= 1.0:
            raise ValueError(
                f"recall_target must be in (0, 1], got {recall_target}")
        self.fraction = fraction
        self.k = k
        self.approx = approx
        self.recall_target = recall_target

    def _k_for(self, n: int) -> int:
        k = self.k if self.k is not None else max(1, int(math.ceil(self.fraction * n)))
        return min(k, n)

    def encode(self, grad):
        n = grad.size
        k = self._k_for(n)
        flat = grad.reshape(-1)
        if self.approx and k < n:
            _, idx = jax.lax.approx_max_k(
                jnp.abs(flat), k, recall_target=self.recall_target)
        else:
            _, idx = jax.lax.top_k(jnp.abs(flat), k)
        idx = idx.astype(jnp.int32)
        return {"values": flat[idx], "indices": idx}

    def decode(self, code, *, shape=None, dtype=None):
        values, idx = code["values"], code["indices"]
        if shape is None:
            raise ValueError("TopKCodec.decode needs the dense shape")
        n = int(np.prod(shape))
        dense = jnp.zeros((n,), dtype=dtype if dtype is not None else values.dtype)
        dense = dense.at[idx].set(values)
        return dense.reshape(shape)

    def decode_sum(self, codes, *, shape, dtype):
        # Per-rank indices from top_k are distinct, so one scatter-add over the
        # rank-flattened (values, indices) equals the sum of per-rank decodes.
        values = codes["values"].reshape(-1)
        idx = codes["indices"].reshape(-1)
        n = int(np.prod(shape))
        dense = jnp.zeros((n,), dtype=dtype).at[idx].add(values.astype(dtype))
        return dense.reshape(shape)

    def wire_bytes(self, shape, dtype):
        k = self._k_for(int(np.prod(shape)))
        return k * (np.dtype(dtype).itemsize + 4)  # value + int32 index


class QuantizeCodec(Codec):
    """Symmetric per-tensor linear quantization to a narrow integer dtype.

    Default int8: ``scale = max|g| / 127``; code = ``{q: int8[shape],
    scale: f32[]}``.  8× wire reduction for f32 gradients with one scalar of
    metadata — the dense-compression counterpart to blosc's byte pipeline
    (`/root/reference/mpi_comms.py:18-30`), but computed on-device.
    """

    name = "quantize"

    def __init__(self, bits: int = 8):
        if bits not in (8, 16):
            raise ValueError("bits must be 8 or 16")
        self.bits = bits
        self.qdtype = jnp.int8 if bits == 8 else jnp.int16
        self.qmax = float(2 ** (bits - 1) - 1)

    def encode(self, grad):
        amax = jnp.max(jnp.abs(grad))
        scale = jnp.where(amax > 0, amax / self.qmax, 1.0).astype(jnp.float32)
        q = jnp.clip(jnp.round(grad / scale), -self.qmax, self.qmax)
        return {"q": q.astype(self.qdtype), "scale": scale}

    def decode(self, code, *, shape=None, dtype=jnp.float32):
        dtype = jnp.float32 if dtype is None else dtype
        return (code["q"].astype(dtype) * code["scale"].astype(dtype))

    def wire_bytes(self, shape, dtype):
        return int(np.prod(shape)) * (self.bits // 8) + 4


class SignCodec(Codec):
    """1-bit sign compression with mean-|g| scale (signSGD-with-majority
    flavor; here: scale * sign so the cross-rank sum stays meaningful).

    The sign plane is bit-packed on device (`ops.pallas_kernels.pack_signs`,
    8 signs/byte) so the all-gathered payload is a true 1-bit/element wire
    format — 32× smaller than the f32 gradient."""

    name = "sign"

    def encode(self, grad):
        from .pallas_kernels import pack_signs
        flat = grad.reshape(-1)
        n = flat.shape[0]
        pad = (-n) % 8
        if pad:
            # Pad with +1s; decode slices them off before use.
            flat = jnp.concatenate([flat, jnp.ones((pad,), flat.dtype)])
        scale = jnp.mean(jnp.abs(grad)).astype(jnp.float32)
        return {"sign": pack_signs(flat), "scale": scale}

    def decode(self, code, *, shape=None, dtype=jnp.float32):
        from .pallas_kernels import unpack_signs
        if shape is None:
            raise ValueError("SignCodec.decode needs the dense shape")
        dtype = jnp.float32 if dtype is None else dtype
        n = int(np.prod(shape))
        sign = unpack_signs(code["sign"], n).astype(dtype)
        return (sign * code["scale"].astype(dtype)).reshape(shape)

    def wire_bytes(self, shape, dtype):
        n = int(np.prod(shape))
        return (n + (-n) % 8) // 8 + 4


class BlockQuantizeCodec(Codec):
    """Per-block int8/int16 quantization backed by a fused Pallas TPU kernel.

    The TPU-first upgrade of `QuantizeCodec`: gradients are tiled into
    ``block_rows*128``-element blocks, each with its own scale — finer scale
    granularity means strictly lower quantization error than per-tensor, and
    the whole encode (abs-max → scale → round → cast) is one VMEM pass per
    tile (`ops.pallas_kernels.block_quantize`).  ``decode_sum`` fuses
    dequantize with the cross-rank sum (`block_dequant_sum`), the decode-loop-
    then-sum of the reference (`/root/reference/ps.py:165-176`) as a single
    kernel sweep.  ``impl="ref"`` runs the same math as fused jnp (the CPU
    mesh; parity-tested), ``impl="interpret"`` the kernels under the Pallas
    interpreter.
    """

    name = "blockq"

    def __init__(self, bits: int = 8, block_rows: int | None = None,
                 impl: str = "mosaic"):
        from . import pallas_kernels as pk
        if bits not in (8, 16):
            raise ValueError("bits must be 8 or 16")
        self.bits = bits
        self.impl = impl
        self.block_rows = block_rows if block_rows is not None else pk.BLOCK_ROWS

    def _rows_for(self, n: int) -> int:
        """Per-tensor block height: small tensors get the smallest sublane-
        aligned block that holds them, so a (128,) bias pads to 8*128 elems,
        not a full 512*128 block (which would inflate its wire size ~64x)."""
        from . import pallas_kernels as pk
        need = -(-n // pk.LANE)            # rows to hold n elements
        aligned = -(-need // 8) * 8        # sublane multiple
        return min(self.block_rows, max(8, aligned))

    def encode(self, grad):
        from . import pallas_kernels as pk
        n = grad.size
        rows = self._rows_for(n)
        x2d, _ = pk.pad_to_blocks(grad.reshape(-1), rows)
        q, scales = pk.block_quantize(x2d, bits=self.bits, block_rows=rows,
                                      impl=self.impl)
        return {"q": q, "scales": scales}

    def decode(self, code, *, shape=None, dtype=None):
        if shape is None:
            raise ValueError("BlockQuantizeCodec.decode needs the dense shape")
        stacked = {"q": code["q"][None], "scales": code["scales"][None]}
        return self.decode_sum(stacked, shape=shape, dtype=dtype)

    def decode_sum(self, codes, *, shape, dtype):
        from . import pallas_kernels as pk
        n = int(np.prod(shape))
        out2d = pk.block_dequant_sum(codes["q"], codes["scales"],
                                     block_rows=self._rows_for(n),
                                     impl=self.impl)
        dtype = jnp.float32 if dtype is None else dtype
        return out2d.reshape(-1)[:n].reshape(shape).astype(dtype)

    def wire_bytes(self, shape, dtype):
        from . import pallas_kernels as pk
        n = int(np.prod(shape))
        rows = self._rows_for(n)
        per_block = rows * pk.LANE
        n_blocks = max(1, -(-n // per_block))
        return n_blocks * per_block * (self.bits // 8) + n_blocks * 4


def get_codec(spec, platform: str) -> Codec:
    """Resolve a codec from an instance or a name string, for a program
    built on ``platform`` devices.  A name gets the kernel implementation
    that platform runs (`pallas_kernels.impl_for_platform`: Mosaic on TPUs,
    the jnp reference on the CPU mesh); an instance keeps the ``impl`` it
    was constructed with and is refused (`KernelPlatformError`) when that
    is the Mosaic kernel and the devices are not TPUs."""
    # pallas_kernels (and with it the Pallas/Mosaic import, about a second)
    # is loaded only for the codecs that have a kernel.
    if spec is None:
        return IdentityCodec()
    if isinstance(spec, Codec):
        if spec.impl is not None:
            from .pallas_kernels import check_impl
            check_impl(spec.impl, platform)
        return spec
    table = {"identity": IdentityCodec, "bf16": CastCodec,
             "topk": TopKCodec,
             "topk_approx": lambda: TopKCodec(approx=True),
             "quantize": QuantizeCodec,
             "sign": SignCodec, "blockq": BlockQuantizeCodec}
    if spec not in table:
        raise ValueError(f"unknown codec {spec!r}; have {sorted(table)}")
    if spec in ("bf16", "blockq"):
        from .pallas_kernels import impl_for_platform
        return table[spec](impl=impl_for_platform(platform))
    return table[spec]()


# ---------------------------------------------------------------------------
# The server->reader WIRE codec (protocol v12) — host-side, numpy-only.
#
# The gradient codecs above are jit-traceable device functions; the
# parameter wire runs on SERVER CONNECTION THREADS (`multihost_async.
# _parm_payload`), where a jax dispatch per leaf would serialize every
# conn thread through the device queue.  These are their host-side
# counterparts: pure numpy, GIL-friendly, applied to the served tree
# once per version before `serializer.encode_segments`.  Frames carry a
# one-byte codec id (`WIRE_CODEC_IDS`), so readers decode from the
# frame alone — no out-of-band codec agreement, and a v11 peer is
# already refused at HELO by the protocol-version byte.
#
# Wire representations (per f32 leaf; every other dtype passes through
# untouched — a lossy cast of an int64 step counter would corrupt it):
#   bf16:  {"__psw_b16": uint16[shape]}   — round-to-nearest-even high
#          halves of the f32 bits (bf16 IS the top 16 bits of f32, so
#          decode is a pure bit shift; no ml_dtypes dependency).
#   int8:  {"__psw_q": int8[nblk, B], "__psw_s": f32[nblk],
#           "__psw_sh": int64[ndim]}      — flat 4096-element blocks,
#          symmetric per-block scale (the host twin of
#          `BlockQuantizeCodec`; 1-D blocks, so a small bias never pays
#          the TPU 128-lane padding).
# The marker keys are namespaced (``__psw_``) so a real state tree
# can never be mistaken for a wire tree during decode.
# ---------------------------------------------------------------------------

WIRE_CODEC_IDS = {"identity": 0, "bf16": 1, "int8": 2}
WIRE_CODEC_NAMES = {v: k for k, v in WIRE_CODEC_IDS.items()}
_WIRE_BLOCK = 4096


def wire_codec_id(name: str) -> int:
    """Resolve a wire-codec name to its frame id byte (loud on drift)."""
    if name not in WIRE_CODEC_IDS:
        raise ValueError(
            f"unknown wire codec {name!r}; have {sorted(WIRE_CODEC_IDS)}")
    return WIRE_CODEC_IDS[name]


def _f32_to_bf16_bits(a: np.ndarray) -> np.ndarray:
    """f32 -> bf16 as raw uint16 bits, round-to-nearest-even (the
    hardware rounding), NaN payloads quieted instead of rounding into
    an inf."""
    a = np.ascontiguousarray(a, np.float32)
    u = a.view(np.uint32)
    bias = np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
    r = ((u + bias) >> np.uint32(16)).astype(np.uint16)
    nan = np.isnan(a)
    if nan.any():
        r = np.where(nan,
                     ((u >> np.uint32(16)).astype(np.uint16)
                      | np.uint16(0x0040)), r)
    return r


def _bf16_bits_to_f32(bits: np.ndarray) -> np.ndarray:
    return (np.ascontiguousarray(bits, np.uint16).astype(np.uint32)
            << np.uint32(16)).view(np.float32)


def _wire_block_for(n: int) -> int:
    """Per-leaf quantization block length: small leaves get the
    smallest 64-aligned block that holds them (a (5,) bias must not
    pad to a full 4096-element block and inflate its wire size ~800x —
    the same reasoning as `BlockQuantizeCodec._rows_for`).  Derived
    from the element count alone, so encoder and decoder agree without
    shipping it."""
    return min(_WIRE_BLOCK, max(64, -(-n // 64) * 64))


def _f32_to_blockq(a: np.ndarray):
    flat = np.ascontiguousarray(a, np.float32).reshape(-1)
    n = flat.size
    blk = _wire_block_for(n)
    nblk = max(1, -(-n // blk))
    pad = nblk * blk - n
    if pad:
        flat = np.concatenate([flat, np.zeros(pad, np.float32)])
    blocks = flat.reshape(nblk, blk)
    amax = np.abs(blocks).max(axis=1)
    scales = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q = np.clip(np.rint(blocks / scales[:, None]), -127, 127).astype(np.int8)
    return q, scales


def _blockq_to_f32(q: np.ndarray, scales: np.ndarray,
                   shape) -> np.ndarray:
    n = int(np.prod(shape, dtype=np.int64))
    out = (q.astype(np.float32) * scales[:, None]).reshape(-1)[:n]
    return out.reshape(shape)


def _is_wire_leaf(x) -> bool:
    return isinstance(x, dict) and ("__psw_b16" in x or "__psw_q" in x)


def encode_wire_tree(name: str, tree):
    """Apply the wire codec to every f32 leaf of a (numpy) pytree —
    the encode-once half the server runs per served version.  Identity
    returns the tree unchanged (no copy: the segmented encoder's
    zero-copy views keep aliasing the served arrays)."""
    import jax

    if wire_codec_id(name) == 0:
        return tree

    def enc(leaf):
        a = np.asarray(leaf)
        if a.dtype != np.float32:
            return a
        if name == "bf16":
            return {"__psw_b16": _f32_to_bf16_bits(a)}
        q, scales = _f32_to_blockq(a)
        sh = np.asarray(a.shape, np.int64)
        if q.nbytes + scales.nbytes + sh.nbytes >= a.nbytes:
            # Sub-block leaf: the padded int8 form would INFLATE the
            # wire — ship it raw f32 (decode dispatches per leaf on
            # the marker dict, so a mixed tree stays self-describing).
            return a
        return {"__psw_q": q, "__psw_s": scales, "__psw_sh": sh}

    return jax.tree_util.tree_map(enc, tree)


def decode_wire_tree(codec, tree):
    """Invert `encode_wire_tree` from the frame's codec id (or name):
    every marker-dict leaf expands back to a dense f32 array; pass-
    through leaves return as-is.  The decoded values are exactly the
    server's post-roundtrip representation — what the delta ring diffs
    against, so a patched reader stays bitwise in sync."""
    import jax

    name = (WIRE_CODEC_NAMES.get(codec, None)
            if isinstance(codec, int) else codec)
    if name is None:
        raise ValueError(f"unknown wire codec id {codec!r}")
    if wire_codec_id(name) == 0:
        return tree

    def dec(leaf):
        if not _is_wire_leaf(leaf):
            return leaf
        if "__psw_b16" in leaf:
            return _bf16_bits_to_f32(leaf["__psw_b16"])
        return _blockq_to_f32(leaf["__psw_q"], leaf["__psw_s"],
                              tuple(int(d) for d in leaf["__psw_sh"]))

    return jax.tree_util.tree_map(dec, tree, is_leaf=_is_wire_leaf)


def tree_raw_nbytes(tree) -> int:
    """Total leaf payload bytes of a (numpy) pytree — the f32-baseline
    numerator of the ``parm_bytes_raw``/``parm_bytes_wire`` ratio."""
    import jax

    return int(sum(np.asarray(leaf).nbytes
                   for leaf in jax.tree_util.tree_leaves(tree)))


# -- delta framing (protocol v12, the DELT delta path) ----------------------
#
# A delta leaf is {"__psd_i": uint32 flat indices, "__psd_v": changed
# values} against the reader's known version of the SAME decoded tree;
# a leaf whose shape/dtype changed (never in steady state) ships whole
# as {"__psd_full": array}.  Patching writes the server's decoded-
# current values at the changed positions, so the patched reader tree
# is bitwise the full-snapshot decode — delta vs full is a pure wire-
# size decision.


def diff_wire_delta(base_tree, cur_tree):
    """Per-leaf sparse diff ``base -> cur`` over two same-structure
    (numpy) trees: ``(delta_tree, payload_bytes)``.  Bytes count the
    index+value payloads only (framing is per-frame constant), so the
    server can compare against the full snapshot's wire size and fall
    back when the tree churned too much for a delta to win."""
    delta = OrderedDict()
    nbytes = 0
    for n2, cur in cur_tree.items():
        cur = np.asarray(cur)
        base = np.asarray(base_tree[n2]) if n2 in base_tree else None
        if (base is None or base.shape != cur.shape
                or base.dtype != cur.dtype):
            delta[n2] = {"__psd_full": cur}
            nbytes += cur.nbytes
            continue
        changed = (base != cur).reshape(-1)
        idx = np.flatnonzero(changed).astype(np.uint32)
        vals = cur.reshape(-1)[idx]
        delta[n2] = {"__psd_i": idx, "__psd_v": vals}
        nbytes += idx.nbytes + vals.nbytes
    return delta, nbytes


def apply_wire_delta(base_tree, delta_tree):
    """Patch a reader's decoded tree with a `diff_wire_delta` payload —
    unchanged leaves alias the base (no copy), patched leaves are fresh
    arrays (the reader's cached tree may be arena views)."""
    out = OrderedDict()
    for n2, d in delta_tree.items():
        if "__psd_full" in d:
            out[n2] = np.asarray(d["__psd_full"])
            continue
        base = np.asarray(base_tree[n2])
        idx = np.asarray(d["__psd_i"])
        if idx.size == 0:
            out[n2] = base
            continue
        flat = np.array(base, copy=True).reshape(-1)
        flat[idx] = d["__psd_v"]
        out[n2] = flat.reshape(base.shape)
    return out
