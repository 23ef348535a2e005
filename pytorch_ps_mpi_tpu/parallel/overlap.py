"""Overlapped bucket-scheduled gradient sync — comm issued INSIDE backward.

The reference hides communication behind computation by hand: backward hooks
enqueue each parameter's encode+``Igatherv`` on a thread pool the moment its
gradient is produced (`/root/reference/ps.py:63-66,98-101,125-127`), so MPI
traffic for late-layer gradients rides under the still-running early-layer
backward.  Our fused SPMD step so far synchronized *after* ``jax.grad``
returned: the gradient collectives sit behind a data dependency on the whole
gradient tree, and for the identity/psum path XLA's all-reduce combiner then
merges every bucket into ONE end-of-backward tuple all-reduce (PJRT exposes
no combiner-threshold option to stop it) — zero overlap, idle ICI while the
MXU works through backward, and idle MXU while the wire drains.

This module is the reference's pipelining intent rebuilt for XLA: the
gradient pytree is partitioned into size-targeted buckets (the same greedy
same-dtype packing as the post-backward exchange, ``_plan_buckets``), and a
``jax.custom_vjp`` identity hook wraps each bucket's *parameters* before the
forward.  The hook's forward is free; its backward receives the bucket's
cotangents and issues the bucket's collective RIGHT THERE — so each bucket's
reduce-scatter (identity codec) or encode→all-gather→fused-decode-sum (lossy
codecs) enters the backward dataflow graph as soon as its last contributing
layer's cotangents exist, not after the full backward.  XLA's latency-hiding
scheduler can then interleave bucket k's wire time with bucket k-1's
remaining backward FLOPs — the thread pool's overlap, compiled.

Two reducers for the identity path:

* ``rs_ag`` (default) — each bucket lowers as explicit reduce-scatter +
  all-gather.  Mathematically the same sum an all-reduce performs on the
  wire, but the all-reduce COMBINER pass does not touch rs/ag ops, so the
  per-bucket collectives survive into the final schedule instead of being
  re-merged into one end-of-backward op.  Whether that buys time on the
  chip is not measured.
* ``psum`` — one all-reduce per bucket; cheapest dispatch on backends with
  no combiner pathology (the virtual-CPU test mesh), and still issued
  inside backward.

**Which lowering runs where** (PERF.md §6, PRs 32, 37 and 39).  This engine
is ``sync_mode="overlap"``, which no default selects.  On the CPU mesh of the
tests it is what the paragraphs above say.  On the v5e it buys nothing as it
lowers today: the compiler rewrites the reduce-scatters to all-reduces, runs
every all-reduce synchronously on the one core wherever it is placed (PR 32
made this engine the default and `gpt2m-sync-dp4` moved 38,771 → 38,773
tokens/s/chip), and places the all-gathers, synchronous too, behind the
backward.  What does run beside the backward there is a
``collective-permute``: the default ``sync_mode="bucketed"`` therefore sums
each bucket through a ring of `lax.ppermute` hops where `MPI_PS` sees several
TPU chips on its data axis (`collectives._ring_tree`,
`ps.MPI_PS._exchange_ring`), and through ``lax.psum`` everywhere else.  The
hooks here stay for the codec paths and until a `simplicity` issue decides
(ROADMAP D2).

The bucket-size knob trades schedule granularity against per-collective
efficiency; ``auto_bucket_bytes`` picks it from the payload, the world size
and the v5e's published HBM bandwidth, and every constructed plan is
recorded through `utils.timing.record_overlap_schedule` so a run's chosen
schedule is inspectable after the fact.
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax

from ..utils.timing import record_overlap_schedule, step_scope
from . import collectives
from .collectives import _allreduce_rs_ag, _plan_buckets

Params = "OrderedDict[str, jax.Array]"

# Bounds for the tuned bucket size: below ~1 MiB a bucket's wire time stops
# amortizing collective issue overhead; above ~32 MiB the first bucket
# finishes so late there is little backward left to hide it under.
MIN_BUCKET_BYTES = 1 << 20
MAX_BUCKET_BYTES = 32 << 20
TARGET_BUCKETS = 16


@dataclass(frozen=True)
class OverlapPlan:
    """A bucket schedule over named gradient leaves.

    ``buckets`` holds tuples of parameter names; every bucket is same-dtype
    (a `_plan_buckets` invariant) and its total payload is <= ``bucket_bytes``
    except for single oversized leaves, which get their own bucket.
    """

    buckets: tuple  # tuple[tuple[str, ...], ...]
    bucket_bytes: int
    total_bytes: int
    auto_tuned: bool = False

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    def describe(self) -> dict:
        """JSON-able schedule record for instrumentation."""
        return {
            "n_buckets": self.n_buckets,
            "bucket_bytes": int(self.bucket_bytes),
            "total_bytes": int(self.total_bytes),
            "auto_tuned": bool(self.auto_tuned),
            "bucket_sizes": [len(b) for b in self.buckets],
        }


# Per-hop latency scale for the tuner's amortization floor: an rs+ag over
# a world-sized ring serializes ~(world-1) hops of link latency per
# collective; O(10us) per hop is the v5e-class ballpark.
PER_HOP_LATENCY_S = 10e-6
# The v5e's published HBM bandwidth; ICI per link is taken as roughly an
# order of magnitude under it.
HBM_BYTES_PER_S = 819e9


def auto_bucket_bytes(total_bytes: int, *, world: int = 8) -> int:
    """Pick a bucket size from the payload and the world size.

    Two constraints, both a pure function of the arguments:

    * **granularity** — aim for ~`TARGET_BUCKETS` buckets so the scheduler
      has enough pieces to pipeline (one bucket = no overlap; hundreds =
      per-op dispatch overhead, the per-param pathology all over again);
    * **latency floor** — a bucket must carry enough bytes that its wire
      time (at an ICI bandwidth estimated as a tenth of the published
      HBM peak) dominates the collective's serial latency, which grows
      with the ring: ~(world-1) hops of per-hop latency for the rs+ag
      lowering.  Below that, splitting finer buys overlap the latency
      immediately eats.
    """
    ici_bytes_per_s = HBM_BYTES_PER_S / 10.0
    hops = max(int(world) - 1, 1)
    latency_floor = int(ici_bytes_per_s * PER_HOP_LATENCY_S * hops)
    granularity = max(1, int(total_bytes) // TARGET_BUCKETS)
    raw = max(granularity, latency_floor)
    return int(min(max(raw, MIN_BUCKET_BYTES), MAX_BUCKET_BYTES))


def plan_overlap(named_arrays, bucket_bytes: int | None = None, *,
                 world: int = 8, record: bool = True,
                 solo_bytes: int = 0) -> OverlapPlan:
    """Partition named gradient leaves into an `OverlapPlan`.

    ``named_arrays`` is a name->array mapping (params; gradients share
    shapes/dtypes).  ``bucket_bytes=None``/0 auto-tunes
    (`auto_bucket_bytes`).  ``solo_bytes`` (default 0 = the pack-everything
    plan) lets large leaves stand alone; the right default DIFFERS by
    consumer, so this planner keeps packing — the custom-vjp hook
    engine wants GRANULARITY (more buckets = more schedule pieces to
    interleave; its concats compile into the step, and shrinking the
    bucket count measurably LOWERED the AOT overlap fraction), and the
    async bucket STREAM's per-frame cost is absorbed by the
    ready-group coalescer — while the FLAT bucketed collectives
    (`collectives.psum_tree_bucketed` and friends) pay the packing
    memcpy at runtime and default solo ON there (`_solo_default`, the
    gradsync < 20 ms lever).  The constructed schedule is recorded
    through `utils.timing.record_overlap_schedule` unless
    ``record=False``.
    """
    items = list(named_arrays.items())
    names = [n for n, _ in items]
    leaves = [x for _, x in items]
    total = sum(x.size * jnp.dtype(x.dtype).itemsize for x in leaves)
    tuned = not bucket_bytes
    if tuned:
        bucket_bytes = auto_bucket_bytes(total, world=world)
    plan_idx = _plan_buckets(leaves, bucket_bytes, int(solo_bytes))
    plan = OverlapPlan(
        buckets=tuple(tuple(names[i] for i in idxs) for idxs in plan_idx),
        bucket_bytes=int(bucket_bytes), total_bytes=int(total),
        auto_tuned=tuned)
    if record:
        record_overlap_schedule(plan.describe())
    return plan


# ---------------------------------------------------------------------------
# The per-bucket hook
# ---------------------------------------------------------------------------


def _bucket_hook(sync_fn: Callable):
    """Identity on the forward; ``sync_fn`` on the bucket's cotangents.

    This is the whole overlap mechanism: wrapping a bucket's params in this
    hook places ``sync_fn``'s collectives in the backward dataflow graph at
    the exact point where the bucket's cotangents are produced — the JAX
    spelling of the reference's per-parameter backward hook
    (`/root/reference/ps.py:63-66`)."""

    @jax.custom_vjp
    def hook(tree):
        return tree

    def fwd(tree):
        return tree, None

    def bwd(_, cot):
        return (sync_fn(cot),)

    hook.defvjp(fwd, bwd)
    return hook


def _sync_identity(cot: "OrderedDict", axis, world: int, reducer: str):
    """One flat cross-rank SUM for a same-dtype bucket: concat → reduce →
    slice back.  ``rs_ag`` keeps the collective out of the all-reduce
    combiner's reach (see module docstring); ``psum`` is one fused
    all-reduce."""
    names = list(cot)
    out = OrderedDict()
    # Inside the backward, so the operations' `op_name` also carries
    # `transpose(`: `utils.timing.step_phase` lets the step's own scope win.
    with step_scope("exchange"):
        flat = (jnp.concatenate([cot[n].reshape(-1) for n in names])
                if len(names) > 1 else cot[names[0]].reshape(-1))
        if reducer == "psum":
            summed = lax.psum(flat, axis)
        else:
            summed = _allreduce_rs_ag(flat, axis, world)
        off = 0
        for n in names:
            sz = cot[n].size
            out[n] = summed[off:off + sz].reshape(cot[n].shape)
            off += sz
    return out


def _sync_codec(cot: "OrderedDict", axis, codec):
    """Codec-encoded bucket exchange: encode each leaf, all-gather the
    bucket's codes as ONE flat transfer per code dtype, fused decode-sum
    per leaf — the reference's encode→Igatherv→decode-loop→sum
    (`/root/reference/ps.py:140-176`) scoped to one bucket, inside
    backward."""
    meta = {n: (g.shape, g.dtype) for n, g in cot.items()}
    codes = OrderedDict((n, codec.encode(g)) for n, g in cot.items())
    # A bucket is already size-targeted; gather its codes in one flat
    # transfer per dtype (1 << 62 disables the inner re-bucketing).
    with step_scope("exchange"):
        gathered = collectives.allgather_tree_bucketed(
            codes, axis, bucket_bytes=1 << 62)
    return OrderedDict(
        (n, codec.decode_sum(gathered[n], shape=meta[n][0],
                             dtype=meta[n][1]))
        for n in cot)


def _blockq_bucket_encode(cot: "OrderedDict", codec):
    """The encode half of `_sync_blockq_fused`: the bucket's cotangents as
    ONE flat payload through ONE quantize sweep.  Returns ``(q, scales,
    rows)``; split out so the parity tests (CPU and chip) can compare the
    codes themselves bit for bit."""
    from ..ops import pallas_kernels as pk

    flat = (jnp.concatenate([g.reshape(-1) for g in cot.values()])
            if len(cot) > 1 else next(iter(cot.values())).reshape(-1))
    rows = codec._rows_for(flat.size)
    x2d, _ = pk.pad_to_blocks(flat, rows)
    q, scales = pk.block_quantize(x2d, bits=codec.bits, block_rows=rows,
                                  impl=codec.impl)
    return q, scales, rows


def _sync_blockq_fused(cot: "OrderedDict", axis, codec):
    """The FUSED bucket exchange for the block-quantize codec (ISSUE 16,
    the sync-path MFU residual): ONE concat → ONE Pallas quantize sweep
    over the whole bucket, vs `_sync_codec`'s one kernel launch plus
    per-leaf lane padding per gradient leaf.  The quantize kernel takes
    the same place in the backward dataflow graph the identity path's
    collective does — anchored on the bucket's cotangents — so XLA can
    run bucket k's encode under bucket k-1's remaining backward FLOPs,
    and the gather moves exactly the bucket's wire bytes (q + scales)
    instead of per-leaf padded tiles.  Parity contract
    (``tests/test_overlap.py``): the same codes, bit for bit, as the same
    math run as separate host-boundary programs under every
    ``codec.impl``, and the same f32 sum to within the FMA contraction
    XLA may apply inside one program and not another."""
    from ..ops import pallas_kernels as pk

    q, scales, rows = _blockq_bucket_encode(cot, codec)
    with step_scope("exchange"):
        gathered = collectives.allgather_tree_bucketed(
            {"q": q, "scales": scales}, axis, bucket_bytes=1 << 62)
    out2d = pk.block_dequant_sum(gathered["q"], gathered["scales"],
                                 block_rows=rows, impl=codec.impl)
    summed = out2d.reshape(-1)
    out = OrderedDict()
    off = 0
    for n, g in cot.items():
        out[n] = (summed[off:off + g.size].reshape(g.shape)
                  .astype(g.dtype))
        off += g.size
    return out


def make_bucket_sync_fn(*, axis, world: int, codec=None,
                        reducer: str = "rs_ag",
                        fused_encode: bool = False) -> Callable:
    """The per-bucket sync closure (applied to every bucket's cotangent
    sub-tree).  ``codec=None`` (or an identity codec — the caller decides)
    uses the flat-sum reducers; otherwise each bucket rides the codec's
    encode/gather/decode-sum.

    ``fused_encode=True`` (ISSUE 16) swaps in the fused twin: the
    identity path is ALREADY one fused flat sum per bucket, so the knob
    is definitionally bitwise-equal there, and the block-quantize codec
    gets `_sync_blockq_fused` (one quantize sweep per bucket).  Other
    codecs refuse loudly — a knob that silently fell back to the
    per-leaf path would claim a fusion it never ran."""
    if reducer not in ("rs_ag", "psum"):
        raise ValueError(f"unknown overlap reducer {reducer!r}; "
                         "have ('rs_ag', 'psum')")
    if not fused_encode:
        if codec is None:
            return lambda cot: _sync_identity(cot, axis, world, reducer)
        return lambda cot: _sync_codec(cot, axis, codec)
    if codec is None:
        return lambda cot: _sync_identity(cot, axis, world, reducer)
    from ..ops.codecs import BlockQuantizeCodec

    if not isinstance(codec, BlockQuantizeCodec):
        raise ValueError(
            f"fused_encode supports the identity and blockq codecs; "
            f"got {type(codec).__name__} — run it unfused, or switch "
            f"the sync codec to 'blockq'")
    return lambda cot: _sync_blockq_fused(cot, axis, codec)


def attach(params: "OrderedDict", plan: OverlapPlan,
           sync_fn: Callable) -> "OrderedDict":
    """Wrap each bucket's params in its hook; returns a same-structure
    OrderedDict whose leaves are hook outputs.  Differentiating a loss of
    the returned tree yields ALREADY-SYNCED gradients for the originals,
    with each bucket's collectives embedded mid-backward."""
    hooked: dict[str, Any] = dict(params)
    for names in plan.buckets:
        sub = OrderedDict((n, params[n]) for n in names)
        out = _bucket_hook(sync_fn)(sub)
        hooked.update(out)
    return OrderedDict((n, hooked[n]) for n in params)


def wrap_loss(loss_fn: Callable, plan: OverlapPlan,
              sync_fn: Callable) -> Callable:
    """``loss_fn(params, *rest)`` -> same loss, but gradients of the wrapped
    function w.r.t. ``params`` come back cross-rank SUMMED (the reference's
    `ps.py:176` semantics), with the sync collectives issued inside the
    backward pass."""

    def wrapped(params, *rest):
        return loss_fn(attach(params, plan, sync_fn), *rest)

    return wrapped


# ---------------------------------------------------------------------------
# Async gradient production (ISSUE 15): bucket-streamed grad+encode
# ---------------------------------------------------------------------------
# The sync engine above inserts each bucket's COLLECTIVE into the backward
# dataflow via per-bucket custom_vjp hooks; the async PS path has no
# collective — its per-bucket operation is the codec ENCODE, and an encode
# is an OUTPUT, not an insertion.  A custom_vjp bwd must return cotangents
# of the primal input's structure, so it cannot smuggle encoded codes out
# of the backward pass — and it does not need to: grouping the step's
# outputs per bucket gives each bucket's encode a data dependency on ONLY
# its own leaves' cotangents, which anchors it at exactly the point in the
# backward dataflow graph where the sync hooks put their collectives.
# XLA's latency-hiding scheduler may then run bucket k's encode while
# bucket k-1's backward FLOPs are still in flight, and the HOST can
# ``device_get`` bucket 0's codes (blocking only on that bucket's slice of
# the program) and put it on the wire while later buckets still compute —
# the streaming half `multihost_async.AsyncPSWorker.push_buckets` drives.


def split_tree(tree: "OrderedDict", plan: OverlapPlan) -> tuple:
    """Slice a name-keyed tree into the plan's bucket sub-trees (every
    param exactly once, plan order — `plan_overlap` covers all names)."""
    return tuple(OrderedDict((n, tree[n]) for n in names)
                 for names in plan.buckets)


def iter_ready_groups(subs, to_host: Callable):
    """Ready-group coalescing — THE flush-before-blocking rule both
    bucket-stream senders share (the worker's GRAD stream and the
    aggregator's AGGR fanout): walk device sub-trees in stream order,
    and before blocking on one that is still COMPUTING, yield the
    already-materialized run as one group (its frames coalesce into one
    gather-send while the device finishes — the overlap window); a
    fully-materialized stream yields one group (one syscall, not one
    thread wakeup per frame).  ``to_host`` materializes one sub-tree
    (device_get + any caller-side bookkeeping)."""
    group: list = []
    for sub in subs:
        leaves = jax.tree_util.tree_leaves(sub)
        ready = all(getattr(l, "is_ready", lambda: True)()
                    for l in leaves)
        if not ready and group:
            yield group
            group = []
        group.append(to_host(sub))
    if group:
        yield group


def merge_buckets(buckets, order) -> "OrderedDict":
    """Inverse of `split_tree`: re-key bucket sub-trees into one tree in
    canonical ``order`` (the decoder's param order, so a bucketed and a
    whole-tree gradient present identically downstream)."""
    flat: dict = {}
    for sub in buckets:
        flat.update(sub)
    return OrderedDict((n, flat[n]) for n in order)


def make_async_bucket_step(loss_fn: Callable, code, plan: OverlapPlan,
                           grad_transform=None, *, fused: bool = True):
    """The bucket-streamed async worker program: ``(params, batch) ->
    (loss, bucket_codes)`` where ``bucket_codes`` is one encoded sub-tree
    per plan bucket.

    ``fused=True`` (the default) compiles the per-bucket encodes INTO the
    grad program — one jitted step whose encodes sit at their buckets'
    cotangent production points (see the section comment above; for the
    Pallas-backed codecs the encode kernel itself fuses into the backward
    schedule, `ops.pallas_kernels.block_quantize`).  ``fused=False`` is
    the host-boundary fallback the fused path is parity-tested against:
    the jitted step returns DENSE per-bucket gradients and each bucket is
    encoded by a second jitted program at the host boundary — what the
    whole-tree worker did, bucketed.  Both paths produce bitwise-identical
    codes (``tests/test_bucket_stream.py``); with a single-bucket plan the
    fused path is the exact `async_ps.make_worker_step` program modulo the
    1-tuple wrapper.

    ``grad_transform`` is the Byzantine injection hook, applied to the
    RAW whole gradient tree before bucketing — attacks ride any bucket
    plan faithfully, like any codec."""
    if fused:
        def fused_step(params, batch):
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
            if grad_transform is not None:
                grads = grad_transform(grads)
            buckets = tuple(
                OrderedDict((n, code.encode(grads[n])) for n in names)
                for names in plan.buckets)
            return loss, buckets

        return jax.jit(fused_step)

    def grad_step(params, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        if grad_transform is not None:
            grads = grad_transform(grads)
        return loss, tuple(OrderedDict((n, grads[n]) for n in names)
                           for names in plan.buckets)

    grad_fn = jax.jit(grad_step)
    # ONE jitted encode program serves every bucket (name-independence:
    # it takes a list of leaves, so the jit cache keys on shapes/dtypes,
    # not bucket identity — B same-shaped buckets share one compile).
    enc_fn = jax.jit(lambda leaves: [code.encode(g) for g in leaves])

    def host_step(params, batch):
        loss, dense = grad_fn(params, batch)
        buckets = tuple(
            OrderedDict(zip(sub.keys(), enc_fn(list(sub.values()))))
            for sub in dense)
        return loss, buckets

    return host_step
