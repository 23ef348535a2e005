"""Flash attention — Pallas TPU kernel for the attention hot op.

Dense softmax attention materializes the ``[S, S]`` score matrix in HBM;
at long context that matrix IS the memory bill.  This module computes
exact attention with O(S · BLOCK) live memory:

* **Forward** (`_fwd_kernel`): one Pallas kernel, grid ``(B·H, q_blocks,
  k_blocks)`` with the k sweep minor — for each 128-row q tile the kernel
  holds a running row-max ``m``, normalizer ``l`` and unnormalized
  accumulator in VMEM scratch (TPU grids run sequentially, so scratch
  carries across the k sweep), rescaling per visiting k tile: the same
  streaming softmax as `parallel.ring_attention`, here at tile granularity
  on one chip.  Scores ride the MXU via ``jnp.dot`` in f32.
* **Backward**: two Pallas kernels (FlashAttention-2 decomposition) under
  ``jax.custom_vjp`` — `_bwd_dkdv_kernel` sweeps q tiles per k tile
  (grid ``(B·H, k_blocks, q_blocks)``), `_bwd_dq_kernel` sweeps k tiles
  per q tile — each recomputing ``P`` from the saved per-row logsumexp
  (``exp(s - lse)``, no second softmax) and accumulating in VMEM scratch,
  so the backward never materializes ``[S, S]`` either.  Fully-masked
  causal tiles skip their MXU work in both kernels, same as the forward.

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

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_kernels import use_interpreter

BLOCK_Q = 512    # q tile rows per grid step (VMEM acc: BLOCK_Q x D f32)
BLOCK_K = 1024   # k/v tile rows per grid step (scores: BLOCK_Q x BLOCK_K)
# Backward tiles are square and smaller: the bwd body keeps ~4 blk_q x blk_k
# f32 intermediates (s, p, dp, ds) live at once, so 512x512 (4 x 1 MB)
# fits VMEM with double buffering where the fwd's 512x1024 would not.
BWD_BLOCK_Q = 512
BWD_BLOCK_K = 512
# Tile sizes from an on-chip sweep at [4, 4096, 8, 128] bf16 causal:
# (512, 1024) 1.36 ms/call vs (512, 512) 2.94, (256, 512) 3.34,
# (1024, 512) 2.37, (512, 2048) 1.57 — bigger k tiles amortize the
# rescale/bookkeeping VPU work between MXU calls; XLA dense: 4.6 ms.
BLOCK = 128      # lane tile the lse output rides; also the padding unit
NEG_INF = -1e30  # large-negative instead of -inf: keeps masked-row math
                 # finite without jnp.where laundering inside the kernel


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


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, seq_len, n_k, blk_q, blk_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _accumulate():
        # Matmuls consume the native (bf16) operands — the MXU's fast path —
        # and accumulate in f32 via preferred_element_type; only the
        # softmax bookkeeping lives in f32.
        q = q_ref[0]                          # (BLK_Q, D)
        k = k_ref[0]                          # (BLK_K, D)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

        k_pos = ik * blk_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < seq_len                # padded K tail: no mass
        if causal:
            q_pos = iq * blk_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask &= q_pos >= k_pos
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, 0]                  # (BLOCK,)
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)       # <= 1, finite by NEG_INF
        p = jnp.exp(s - m_new[:, None])       # masked entries → 0
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jnp.dot(p.astype(v.dtype), v,
                                  preferred_element_type=jnp.float32))
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    if causal:
        # Tiles strictly above the diagonal are fully masked: skip their
        # MXU work entirely (≈half the grid at long context).  The tile
        # intersects the diagonal iff its first q row >= its first k row
        # minus (blk_k - 1), i.e. some (q_pos >= k_pos) pair exists.
        pl.when((iq + 1) * blk_q - 1 >= ik * blk_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(ik == n_k - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)
        # Per-row logsumexp: the single residual the backward needs.
        # Lane-replicated to a (BLOCK, BLOCK) tile: Mosaic requires output
        # blocks whose last two dims are (8k, 128k), so a per-row vector
        # rides a full lane tile (the in-tree kernel's MIN_BLOCK_SIZE
        # trick); the caller reads lane 0.
        lse = (m_ref[:, 0] + jnp.log(safe)).astype(jnp.float32)
        lse_ref[0] = jnp.broadcast_to(lse[:, None], lse_ref.shape[1:])


def _fwd_call(q3, k3, v3, *, causal, scale, true_len, interpret,
              blk_q=None, blk_k=None):
    """``q3,k3: [BH, S_pad, D_pad]``, ``v3: [BH, S_pad, Dv_pad]`` already
    padded to BLOCK/lane tiles (``Dv_pad`` may differ from ``D_pad``: the
    accumulator and the output take v's width); returns ``(out [BH, S_pad,
    Dv_pad], lse [BH, S_pad])``.  ``true_len`` masks the padded K tail so
    it carries no softmax mass.

    Tile sizes clamp to the (padded) sequence: big BLOCK_Q×BLOCK_K tiles
    amortize grid-step overhead and keep the MXU fed (the 128×128 version
    measured ~2.4× slower than XLA dense at S=4096); short sequences fall
    back to one tile."""
    bh, s_pad, d = q3.shape
    dv = v3.shape[-1]
    blk_q = min(BLOCK_Q if blk_q is None else blk_q, s_pad)
    blk_k = min(BLOCK_K if blk_k is None else blk_k, s_pad)
    n_q, n_k = -(-s_pad // blk_q), -(-s_pad // blk_k)
    s_pad_q, s_pad_k = n_q * blk_q, n_k * blk_k
    if s_pad_q != s_pad:
        q3 = _pad_to(q3, blk_q, 1)
    if s_pad_k != s_pad:
        k3, v3 = _pad_to(k3, blk_k, 1), _pad_to(v3, blk_k, 1)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               seq_len=true_len, n_k=n_k,
                               blk_q=blk_q, blk_k=blk_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, dv), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, BLOCK), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad_q, dv), q3.dtype),
            jax.ShapeDtypeStruct((bh, s_pad_q, BLOCK), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, dv), jnp.float32),     # acc
            pltpu.VMEM((blk_q, BLOCK), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((blk_q, BLOCK), jnp.float32),  # l
        ],
        interpret=interpret,
        **_named("flash_fwd"),
    )(q3, k3, v3)
    return out, lse


def _to_bh(x):
    """[B, S, H, D] → [B*H, S, D]."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_bh(x3, b, h):
    bh, s, d = x3.shape
    return x3.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, interpret):
    out, _ = _flash_fwd_res(q, k, v, causal, scale, interpret)
    return out


def _flash_fwd_res(q, k, v, causal, scale, interpret):
    b, s, h, d = q.shape
    q3 = _pad_to(_pad_to(_to_bh(q), BLOCK, 1), BLOCK, 2)
    k3 = _pad_to(_pad_to(_to_bh(k), BLOCK, 1), BLOCK, 2)
    v3 = _pad_to(_pad_to(_to_bh(v), BLOCK, 1), BLOCK, 2)
    out3, lse3 = _fwd_call(q3, k3, v3, causal=causal, scale=scale,
                           true_len=s, interpret=interpret)
    out = _from_bh(out3[:, :s, :v.shape[-1]], b, h)
    lse = lse3[:, :s, 0].reshape(b, h, s)
    return out, (q, k, v, out, lse)


def _flash_fwd_vjp(q, k, v, causal, scale, interpret):
    return _flash_fwd_res(q, k, v, causal, scale, interpret)


def _bwd_probs(q, k, do, v, lse_col, delta_col, *, scale, causal, seq_len,
               q0, k0):
    """Shared bwd tile math: recomputed ``p`` from the saved logsumexp and
    ``ds`` — the (blk_q, blk_k) pieces both backward kernels need.  Masking
    happens BEFORE the exp: padded q rows carry lse = -inf-ish, and
    ``exp(s - lse)`` would overflow where the forward's own mask kept it
    finite."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    k_pos = k0 + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    q_pos = q0 + lax.broadcasted_iota(jnp.int32, s.shape, 0)
    mask = (k_pos < seq_len) & (q_pos < seq_len)
    if causal:
        mask &= q_pos >= k_pos
    p = jnp.exp(jnp.where(mask, s - lse_col, NEG_INF))
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_col) * scale
    return p, ds


def _bwd_dkdv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dk_ref, dv_ref, dk_acc, dv_acc,
                     *, scale, causal, seq_len, n_q, blk_q, blk_k):
    j, i = pl.program_id(1), pl.program_id(2)   # k tile major, q sweep minor

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _bwd_probs(
            q, k, do, v, lse_ref[0][:, :1], delta_ref[0][:, :1],
            scale=scale, causal=causal, seq_len=seq_len,
            q0=i * blk_q, k0=j * blk_k)
        dv_acc[...] += jnp.dot(p.astype(do.dtype).T, do,
                               preferred_element_type=jnp.float32)
        dk_acc[...] += jnp.dot(ds.astype(q.dtype).T, q,
                               preferred_element_type=jnp.float32)

    if causal:
        pl.when((i + 1) * blk_q - 1 >= j * blk_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(i == n_q - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc,
                   *, scale, causal, seq_len, n_k, blk_q, blk_k):
    i, j = pl.program_id(1), pl.program_id(2)   # q tile major, k sweep minor

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _accumulate():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _bwd_probs(
            q, k, do, v, lse_ref[0][:, :1], delta_ref[0][:, :1],
            scale=scale, causal=causal, seq_len=seq_len,
            q0=i * blk_q, k0=j * blk_k)
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    if causal:
        pl.when((i + 1) * blk_q - 1 >= j * blk_k)(_accumulate)
    else:
        _accumulate()

    @pl.when(j == n_k - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_call(q3, k3, v3, do3, lse2, delta2, *, causal, scale, true_len,
              interpret, blk_q=None, blk_k=None):
    """``q3,k3: [BH, S_pad, D_pad]``; ``v3,do3: [BH, S_pad, Dv_pad]``;
    ``lse2, delta2: [BH, S_pad, BLOCK]`` f32, lane-replicated (same MIN_BLOCK_SIZE trick as
    the forward's lse output — Mosaic wants (8k, 128k) tiles, the kernels
    read lane 0).  Returns ``(dq, dk, dv)`` padded like the inputs."""
    bh, s_pad, d = q3.shape
    dv = v3.shape[-1]
    blk_q = min(BWD_BLOCK_Q if blk_q is None else blk_q, s_pad)
    blk_k = min(BWD_BLOCK_K if blk_k is None else blk_k, s_pad)
    n_q, n_k = -(-s_pad // blk_q), -(-s_pad // blk_k)
    # Same guard as _fwd_call: when s_pad is not a multiple of the clamped
    # tile, edge blocks would read past the array (undefined bytes on real
    # TPUs; 0 * non-finite garbage = NaN through the accumulators even
    # though the position mask zeroes p).  Pad the q-aligned and k-aligned
    # operands to their own tile multiples; outputs are sliced back below.
    if n_q * blk_q != s_pad:
        q3, do3 = _pad_to(q3, blk_q, 1), _pad_to(do3, blk_q, 1)
        lse2, delta2 = _pad_to(lse2, blk_q, 1), _pad_to(delta2, blk_q, 1)
    if n_k * blk_k != s_pad:
        k3, v3 = _pad_to(k3, blk_k, 1), _pad_to(v3, blk_k, 1)
    common = dict(scale=scale, causal=causal, seq_len=true_len,
                  blk_q=blk_q, blk_k=blk_k)

    dk3, dv3 = pl.pallas_call(
        functools.partial(_bwd_dkdv_kernel, n_q=n_q, **common),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, blk_k, dv), lambda b, j, i: (b, j, 0)),  # v
            pl.BlockSpec((1, blk_q, dv), lambda b, j, i: (b, i, 0)),  # dout
            pl.BlockSpec((1, blk_q, BLOCK), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_q, BLOCK), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, dv), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n_k * blk_k, d), k3.dtype),
            jax.ShapeDtypeStruct((bh, n_k * blk_k, dv), v3.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, dv), jnp.float32),
        ],
        interpret=interpret,
        **_named("flash_bwd_dkdv"),
    )(q3, k3, v3, do3, lse2, delta2)

    dq3 = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, **common),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, blk_k, dv), lambda b, i, j: (b, j, 0)),  # v
            pl.BlockSpec((1, blk_q, dv), lambda b, i, j: (b, i, 0)),  # dout
            pl.BlockSpec((1, blk_q, BLOCK), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, BLOCK), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, n_q * blk_q, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
        **_named("flash_bwd_dq"),
    )(q3, k3, v3, do3, lse2, delta2)
    return dq3[:, :s_pad], dk3[:, :s_pad], dv3[:, :s_pad]


def _flash_bwd(causal, scale, interpret, res, dout):
    """Pallas blockwise backward from the saved logsumexp (FlashAttention-2
    style: a dk/dv kernel sweeping q tiles, a dq kernel sweeping k tiles);
    every live intermediate is one (blk_q, blk_k) tile in VMEM."""
    q, k, v, out, lse = res
    b, s, h, d = q.shape
    pad3 = lambda x: _pad_to(_pad_to(_to_bh(x), BLOCK, 1), BLOCK, 2)
    q3, k3, v3, do3, o3 = pad3(q), pad3(k), pad3(v), pad3(dout), pad3(out)
    s_pad = q3.shape[1]
    # delta = rowsum(dout * out): the only extra residual FA-2 needs.
    # Padded rows are all-zero -> delta 0 there; lse pads with NEG_INF so
    # the kernels' q_pos mask (not the pad value) is what keeps them inert.
    delta2 = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32), -1)
    lse2 = jnp.pad(lse.reshape(b * h, s), ((0, 0), (0, s_pad - s)),
                   constant_values=NEG_INF).astype(jnp.float32)
    rep = lambda x2: jnp.broadcast_to(x2[..., None], x2.shape + (BLOCK,))
    dq3, dk3, dv3 = _bwd_call(q3, k3, v3, do3, rep(lse2), rep(delta2),
                              causal=causal, scale=scale, true_len=s,
                              interpret=interpret)
    back = lambda x3, w: _from_bh(x3[:, :s, :w], b, h).astype(q.dtype)
    return back(dq3, d), back(dk3, d), back(dv3, v.shape[-1])


_flash.defvjp(_flash_fwd_vjp, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: float | None = None, impl: str = "mosaic"):
    """Exact attention, O(S·BLOCK) memory.  ``q,k: [B, S, H, D]``,
    ``v: [B, S, H, Dv]`` → ``[B, S, H, Dv]``; ``Dv`` may differ from ``D``
    (latent attention trains with a 192-wide q / k and a 128-wide v), each
    padded to its own multiple of the 128-lane tile, so v is never widened
    to q's width — drop-in for `ring_attention.dense_attention`
    (`/root/reference` has no attention at all; this is the long-context
    hot-op layer of the TPU framework).  ``impl="interpret"`` runs the
    kernels under the Pallas interpreter (the CPU mesh, by name); the
    default lowers through Mosaic and fails to compile anywhere but on a
    TPU."""
    d = q.shape[-1]
    scale = (d ** -0.5) if scale is None else scale
    return _flash(q, k, v, causal, scale, use_interpreter(impl))
