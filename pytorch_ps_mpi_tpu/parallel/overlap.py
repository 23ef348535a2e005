"""The async bucket stream's plan and worker program.

An async worker (`multihost_async.AsyncPSWorker`) and the two-level
aggregator (`shard/hierarchy.py`) may send a gradient as a stream of
buckets instead of one whole tree, so that the host puts bucket 0 on the
wire while the device still computes the later ones — the reference's
encode-during-backward pipelining (its backward hooks and encode thread
pool), moved to the host boundary.  This module holds what they share:

* `plan_overlap` — the bucket schedule, an `OverlapPlan` of parameter-name
  groups packed greedily by dtype (`collectives._plan_buckets`), its size
  from `auto_bucket_bytes` unless the caller gives one;
* `make_async_bucket_step` — the worker's ``(params, batch) -> (loss,
  bucket_codes)`` program, each bucket's encode anchored on its own
  cotangents;
* `split_tree` / `merge_buckets` — a tree into the plan's buckets and
  back; `iter_ready_groups` — the flush-before-blocking rule both senders
  use.

The sync step (`ps.MPI_PS`) does not use it: its gradient sum is one
exchange after the backward, chosen from ``bucket_mb`` and the mesh
(`collectives.psum_tree_bucketed`).
"""

from __future__ import annotations

from collections import OrderedDict
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from .collectives import _plan_buckets

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

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)


# Per-hop latency scale for the tuner's amortization floor: a collective
# over a world-sized ring serializes ~(world-1) hops of link latency per
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
      with the ring: ~(world-1) hops of per-hop latency.  Below that,
      splitting finer buys overlap the latency immediately eats.
    """
    ici_bytes_per_s = HBM_BYTES_PER_S / 10.0
    hops = max(int(world) - 1, 1)
    latency_floor = int(ici_bytes_per_s * PER_HOP_LATENCY_S * hops)
    granularity = max(1, int(total_bytes) // TARGET_BUCKETS)
    raw = max(granularity, latency_floor)
    return int(min(max(raw, MIN_BUCKET_BYTES), MAX_BUCKET_BYTES))


def plan_overlap(named_arrays, bucket_bytes: int | None = None, *,
                 world: int = 8, solo_bytes: int = 0) -> OverlapPlan:
    """Partition named gradient leaves into an `OverlapPlan`.

    ``named_arrays`` is a name->array mapping (params; gradients share
    shapes/dtypes).  ``bucket_bytes=None``/0 auto-tunes
    (`auto_bucket_bytes`).  ``solo_bytes`` (default 0 = the pack-everything
    plan) lets large leaves stand alone; the right default DIFFERS by
    consumer, so this planner keeps packing — the bucket STREAM's
    per-frame cost is absorbed by the ready-group coalescer — while the
    FLAT bucketed collectives (`collectives.psum_tree_bucketed` and
    friends) pay the packing memcpy at runtime and default solo ON there
    (`_solo_default`, the gradsync < 20 ms lever).
    """
    items = list(named_arrays.items())
    names = [n for n, _ in items]
    leaves = [x for _, x in items]
    total = sum(x.size * jnp.dtype(x.dtype).itemsize for x in leaves)
    if not bucket_bytes:
        bucket_bytes = auto_bucket_bytes(total, world=world)
    plan_idx = _plan_buckets(leaves, bucket_bytes, int(solo_bytes))
    return OverlapPlan(
        buckets=tuple(tuple(names[i] for i in idxs) for idxs in plan_idx),
        bucket_bytes=int(bucket_bytes), total_bytes=int(total))


# ---------------------------------------------------------------------------
# Async gradient production (ISSUE 15): bucket-streamed grad+encode
# ---------------------------------------------------------------------------
# The async PS path has no collective: its per-bucket operation is the
# codec ENCODE, an OUTPUT of the step.  Grouping the step's outputs per
# bucket gives each bucket's encode a data dependency on ONLY its own
# leaves' cotangents, which anchors it at the point in the backward
# dataflow graph where those cotangents are made.  XLA's latency-hiding
# scheduler may then run bucket k's encode while bucket k-1's backward
# FLOPs are still in flight, and the HOST can ``device_get`` bucket 0's
# codes (blocking only on that bucket's slice of the program) and put it
# on the wire while later buckets still compute — the streaming half
# `multihost_async.AsyncPSWorker.push_buckets` drives.


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
