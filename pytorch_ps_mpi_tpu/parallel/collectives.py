"""Collectives shim — the TPU-native replacement for the reference's L1 layer.

The reference's communication layer (`/root/reference/mpi_comms.py`) solves one
central problem: MPI collectives need receive counts up front, but pickled+
compressed gradients have unknown sizes.  It solves it twice — Protocol A
(``Iallgather`` the per-rank byte size, then ``Iallgatherv`` the payloads,
`mpi_comms.py:144-174`) and Protocol B (fixed ``max_bytes`` slots with a
``0x29``-sentinel to find the payload end, `mpi_comms.py:60-117`).

Under XLA both protocols *dissolve*: every array shape is static at trace time,
so receive sizes are known to the compiler and the collective is a single fused
op over the ICI mesh.  What this module keeps from the reference is the
*surface*: non-blocking semantics (dispatch returns immediately; ``.wait()`` is
the ``MPI.Request.Wait()`` analogue, realized by JAX's async dispatch +
``block_until_ready``), pytree payloads (the reference sends arbitrary
picklable objects; we send arbitrary pytrees of arrays), and per-call timing
dicts mirroring ``igather``'s (`mpi_comms.py:73-93`).

Two tiers:

* **In-step primitives** (``psum_tree`` / ``allgather_tree`` / ...) — used
  inside a ``shard_map``-ed train step; they take an axis *name* and operate on
  the per-shard view.  This is the hot path: the PS optimizer's gradient sync
  compiles into these.
* **Host API** (``igather`` / ``ibroadcast`` / ``iallgather`` / ``ialltoall``)
  — standalone jitted collectives on sharded pytrees, mirroring the reference's
  free functions (`mpi_comms.py:60-133`) including the ``(result, request)``
  non-blocking shape.  Used by tests and by the async PS host loop.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..utils.bytes import bytes_of
from .mesh import PS_AXIS

Tree = Any

# ---------------------------------------------------------------------------
# In-step primitives (call inside shard_map; `axis` is the mesh axis name)
# ---------------------------------------------------------------------------


def psum_tree(tree: Tree, axis: str = PS_AXIS) -> Tree:
    """Sum every leaf across the PS axis.

    The reference's ``d_p = sum(grads)`` over all ranks' decoded gradients
    (`/root/reference/ps.py:176`) — **sum, not mean** — fused into one XLA
    all-reduce instead of size-exchange + Iallgatherv + host loop.
    """
    return jax.tree.map(lambda x: lax.psum(x, axis), tree)


def pmean_tree(tree: Tree, axis: str = PS_AXIS) -> Tree:
    return jax.tree.map(lambda x: lax.pmean(x, axis), tree)


def allgather_tree(tree: Tree, axis: str = PS_AXIS, *, tiled: bool = False) -> Tree:
    """All-gather every leaf across the PS axis (new leading dim = world size).

    Replaces the reference's two-phase ``Iallgather`` sizes → ``Iallgatherv``
    payloads protocol (`/root/reference/mpi_comms.py:144-174`); counts are
    static under XLA so the size exchange does not exist.
    """
    return jax.tree.map(lambda x: lax.all_gather(x, axis, tiled=tiled), tree)


def bcast_tree(tree: Tree, axis: str = PS_AXIS, *, root: int = 0) -> Tree:
    """Every rank receives root's value — ``Ibcast`` analogue
    (`/root/reference/mpi_comms.py:127-133`).

    Lowered as a masked all-reduce (zero every rank's contribution except
    root's, then psum): per-link traffic is ~2N regardless of world size,
    vs the ~W·N of the naive all_gather-then-index lowering — the cheap
    root-push the async PS parameter broadcast rides.  (A chunked-ppermute
    ring pipeline would reach ~N, at W-1 sequential hops of latency; the
    single fused psum is the better trade at gradient/param sizes.)
    """
    def one(x):
        contrib = jnp.where(lax.axis_index(axis) == root, x,
                            jnp.zeros_like(x))
        # psum promotes sub-word dtypes (bool -> int32); restore the input
        # dtype so broadcast is dtype-preserving like the gather lowering was.
        return lax.psum(contrib, axis).astype(x.dtype)
    return jax.tree.map(one, tree)


def reduce_scatter_tree(tree: Tree, axis: str = PS_AXIS) -> Tree:
    """Sum across ranks, each rank keeps its shard (leading dim split)."""
    return jax.tree.map(
        lambda x: lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True), tree)


def alltoall_tree(tree: Tree, axis: str = PS_AXIS) -> Tree:
    """Transpose rank/leading-dim — the ``Ialltoallv`` the reference explores
    in `test_mpi.py:11-25`, static-shape edition."""
    return jax.tree.map(
        lambda x: lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=True),
        tree)


def ppermute_tree(tree: Tree, axis: str, perm: list[tuple[int, int]]) -> Tree:
    """Point-to-point permutation over the ring — building block for the async
    PS parameter broadcast (README.md:56-77 AsySG-InCon) and ring pipelines."""
    return jax.tree.map(lambda x: lax.ppermute(x, axis, perm), tree)


def ring_shift_tree(tree: Tree, axis: str = PS_AXIS, *, shift: int = 1,
                    size: int | None = None) -> Tree:
    """Shift every leaf one hop around the ring (ICI-friendly ppermute)."""
    n = size if size is not None else lax.axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return ppermute_tree(tree, axis, perm)


def rank(axis: str = PS_AXIS):
    """``comm.Get_rank()`` analogue inside a shard_map'ed step."""
    return lax.axis_index(axis)


# ---------------------------------------------------------------------------
# Bucketed collectives — few large transfers instead of one per leaf
# ---------------------------------------------------------------------------
#
# The reference posts one non-blocking collective PER PARAMETER
# (`/root/reference/ps.py:140-147`) because each parameter's pickled payload
# is a separate MPI message.  Transliterated to XLA that becomes one
# all-gather/all-reduce per code leaf (~130 for ResNet-18), each too small to
# fill the ICI links and each a separate scheduling barrier (the compiled
# v5e-8 schedule ran all 130 synchronously).  The TPU-idiomatic form is a
# few LARGE flat transfers: concatenate same-dtype
# leaves into buckets of ~bucket_bytes, run ONE collective per bucket, and
# slice the results back out.  Fewer, larger collectives saturate ICI and
# give XLA's latency-hiding scheduler few enough pieces to hoist compute
# between start/done pairs.  Packing/slicing is pure data movement: results
# are mathematically identical to the per-leaf form (the same elementwise
# sum), and bitwise-identical on the tested CPU backend; on TPU a backend
# is free to segment a ring reduction by buffer offset, which bucketing
# changes, so cross-rank float reduction ORDER is not guaranteed bitwise.

DEFAULT_BUCKET_BYTES = 4 << 20  # 4 MiB: ~ICI bandwidth-delay product scale

# Solo threshold as a fraction of the bucket budget: a leaf already
# carrying bucket_bytes/16 (256 KiB at the default) amortizes a
# collective's issue latency on its own (~25 us of wire at 10 GB/s vs
# ~10 us/hop), so packing it into a shared bucket buys nothing and pays
# the concatenate-in / slice-out memcpy both ways (about half of an
# eight-rank exchange's time on the host CPU; not measured on the chip).
_SOLO_DIVISOR = 16


def _plan_buckets(leaves, bucket_bytes: int, solo_bytes: int = 0):
    """Greedy same-dtype packing: lists of leaf indices, each list's total
    payload <= bucket_bytes (a single oversized leaf gets its own bucket).
    Deterministic in leaf order, so jit retraces stably.

    ``solo_bytes`` (0 = off, the legacy plan): leaves at or above the
    threshold get their own bucket instead of sharing one — packing
    exists to amortize per-collective dispatch/latency over many SMALL
    leaves, and a leaf that already amortizes it alone only pays the
    concat/slice memcpy for sharing.  The resulting collectives compute
    the same elementwise sums (grouping never changes per-element
    operand order), so results are bitwise-equal to the packed plan on
    the tested CPU backend."""
    by_dtype: "dict[Any, list[int]]" = {}
    plan: list[list[int]] = []
    for i, x in enumerate(leaves):
        nb = x.size * jnp.dtype(x.dtype).itemsize
        if solo_bytes and nb >= solo_bytes:
            plan.append([i])
            continue
        by_dtype.setdefault(jnp.dtype(x.dtype), []).append(i)
    for idxs in by_dtype.values():
        cur: list[int] = []
        cur_bytes = 0
        for i in idxs:
            nb = leaves[i].size * jnp.dtype(leaves[i].dtype).itemsize
            if cur and cur_bytes + nb > bucket_bytes:
                plan.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nb
        if cur:
            plan.append(cur)
    return plan


# Auto-solo floor: below ~64 KiB a leaf does NOT amortize its own
# collective/frame dispatch, so solo-ing it would multiply issue cost —
# the exact failure packing exists to prevent.  The auto threshold
# therefore never drops below this, however small the bucket budget.
_SOLO_FLOOR = 64 << 10


def _solo_default(bucket_bytes: int, solo_bytes: "int | None") -> int:
    """Resolve the solo threshold: None = auto (bucket_bytes /
    `_SOLO_DIVISOR`, floored at `_SOLO_FLOOR`), 0 = disabled (pack
    everything, the legacy plan)."""
    if solo_bytes is None:
        return max(_SOLO_FLOOR, int(bucket_bytes) // _SOLO_DIVISOR)
    return int(solo_bytes)


def _bucketed_leafwise(tree: Tree, collective, bucket_bytes: int,
                       solo_bytes: int = 0) -> Tree:
    """Run ``collective`` (flat 1-D array -> array, possibly growing leading
    dims like all_gather's world dim) over dtype-bucketed concatenations of
    the tree's leaves, then slice each leaf's segment back out of the last
    axis and restore its shape (keeping any grown leading dims)."""
    leaves, treedef = jax.tree.flatten(tree)
    out: list[Any] = [None] * len(leaves)
    for idxs in _plan_buckets(leaves, bucket_bytes, solo_bytes):
        if len(idxs) == 1:
            i = idxs[0]
            res = collective(leaves[i].reshape(-1))
            shape = leaves[i].shape
            out[i] = res.reshape(res.shape[:-1] + shape)
            continue
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        _unpack(out, leaves, idxs, collective(flat))
    return jax.tree.unflatten(treedef, out)


def _unpack(out: list, leaves, idxs, res) -> None:
    """Slice each leaf's segment of a packed bucket's result ``res`` back
    out of its last axis into ``out``, in the leaf's shape (keeping any
    leading dims the collective grew)."""
    off = 0
    for i in idxs:
        n = leaves[i].size
        seg = res[..., off:off + n]
        out[i] = seg.reshape(seg.shape[:-1] + leaves[i].shape)
        off += n


def _axis_world(axis) -> int:
    """Static total world size along one axis name or a tuple of names."""
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    w = 1
    for a in names:
        w *= lax.axis_size(a)
    return w


# ---------------------------------------------------------------------------
# The bucketed sum as a ring of collective-permute hops
# ---------------------------------------------------------------------------
#
# On the v5e an ``all-reduce`` runs on the one core and stops it: the
# compiled step holds no ``-start`` / ``-done`` pair for it under any compiler
# option tried (PERF.md §6, PR 39), and `gpt2m-sync-dp4` spent 28.4 ms of a
# 201.6 ms step in twelve of them with nothing beside (ledger, PR 37).  A
# ``collective-permute`` is asynchronous there, so the same sum spelt as a
# ring of `lax.ppermute` hops is in flight while the backward's fusions run.
# `MPI_PS` takes this lowering where it sees several TPU chips on its one
# data axis (`ps.MPI_PS._exchange_ring`); the CPU and one chip keep
# ``lax.psum`` and the program they had.

# A leaf over this is cut along its rows into pieces of at most this size,
# one ring each: the ring's chunks in flight are a few MB whatever the leaf.
_RING_PIECE_BYTES = 32 << 20
# The bucket that the backward makes first keeps XLA's all-reduce if it is
# over this.  For a language model that is the head's gradient (206 MB in
# GPT-2), made beside the loss where the step's memory peaks and laid out
# column-major, so that its row slices are relayouts: with it on the ring
# `gpt2m-sync-dp4`'s step took 219.3 ms against 196.0 and the step program's
# temporaries 10.91 GB against 9.62 (my chip run, PR 39; the all-reduce
# form's 9.54), and `peak_hbm_gib` is bounded at 1 %.
_RING_FIRST_MAX_BYTES = 64 << 20


def _ring_unit(shape, dtype, world: int) -> int:
    """Rows (of axis 0) that a ring's ``2 * world`` chunks are cut in
    multiples of, so that every chunk is whole tiles of the array as the TPU
    lays it out (a row slice is then no relayout): 8 sublanes of 32 bits for
    a matrix, 8 x 128 elements for a vector, any row for more dimensions
    (the tiles are the last two)."""
    packed = max(1, 4 // jnp.dtype(dtype).itemsize)
    tile = {1: 1024 * packed, 2: 8 * packed}.get(len(shape), 1)
    return 2 * world * tile


def _ring_turn(axis, world: int):
    """``turn[k]`` is the rank ``k`` places on from this one, for ``k`` in
    ``0 .. world - 1``: the one piece of index arithmetic every hop of every
    bucket shares, made once a step."""
    return (lax.axis_index(axis)
            + jnp.arange(world, dtype=jnp.int32)) % world


@partial(jax.jit, static_argnums=(2, 3))
def _allreduce_ring(x, turn, axis, world: int):
    """All-reduce one array as rings of `lax.ppermute` hops: the same
    cross-rank sum as ``lax.psum`` up to the order of the additions.

    The array is cut along its rows (axis 0, in its own shape: flattening a
    matrix is a relayout on the TPU) into pieces of at most
    `_RING_PIECE_BYTES`, each piece into two halves that travel in opposite
    senses, so that both directions of every link carry a share, and each
    half into ``world`` chunks: ``world - 1`` hops in which a rank passes a
    chunk on and adds its own to what arrives (the reduce-scatter), then
    ``world - 1`` hops that pass the finished chunks round (the all-gather),
    each written over the input's own rows.  Every rank therefore holds the
    one sum that one rank computed: replicas stay bitwise equal.  The rows
    past the last whole chunk (`_ring_unit`; all of a small array) are summed
    by ``lax.psum``.  ``turn`` is `_ring_turn`'s vector.

    Applied to gradients and never differentiated, so it has no
    ``custom_vjp``.  Under `jax.jit`: a model's layers share their shapes,
    so the hops are traced and lowered once a shape and not once a leaf
    (GPT-2 medium: 6 bodies for 99 buckets; spelt out a leaf they were
    26,000 lines of StableHLO and 7.8 s of every set-up, warm or cold:
    PERF.md §6, PR 39).  A module constant read in here is frozen into the
    first trace of a shape."""
    rows = x.shape[0] if x.ndim else 0
    unit = _ring_unit(x.shape, x.dtype, world)
    if world == 1 or rows < unit:
        return lax.psum(x, axis)
    row_bytes = x.size // rows * x.dtype.itemsize
    piece = max(unit, _RING_PIECE_BYTES // (row_bytes * unit) * unit)
    out, lo = x, 0
    while rows - lo >= unit:
        n = min(piece, (rows - lo) // unit * unit)
        chunk = n // (2 * world)
        for half, sense in enumerate((1, -1)):
            perm = [(i, (i + sense) % world) for i in range(world)]

            def row(k, at=lo + half * (n // 2), sense=sense):
                """The first row of the chunk that is ``k`` places along
                this half's sense from the rank's own."""
                return at + turn[sense * k % world] * chunk

            carry = lax.dynamic_slice_in_dim(x, row(0), chunk, 0)
            for hop in range(1, world):
                carry = (lax.ppermute(carry, axis, perm)
                         + lax.dynamic_slice_in_dim(x, row(-hop), chunk, 0))
            # carry is the finished chunk ``world - 1`` places back
            for hop in range(world - 1, 2 * world - 1):
                out = lax.dynamic_update_slice_in_dim(
                    out, carry, row(-hop), 0)
                if hop < 2 * world - 2:
                    carry = lax.ppermute(carry, axis, perm)
        lo += n
    if lo < rows:
        # last, like the chunks: a write that is the first thing done to
        # ``x`` is fused into what makes ``x`` and copies the whole of it
        out = lax.dynamic_update_slice_in_dim(
            out, lax.psum(x[lo:], axis), lo, 0)
    return out


def _made_order(leaves) -> "list[int]":
    """For each leaf a number that sorts the leaves in the order in which
    the program makes them, where they are values of a trace in progress
    (each carries the count at which the trace made its variable: a gradient
    of the head has a lower one than a gradient of the embedding); the
    reverse of the order they came in where they say nothing (concrete
    arrays, another JAX): a backward pass makes the gradients of the
    parameters used last first."""
    counts = [getattr(getattr(x, "val", None), "count", None) for x in leaves]
    if all(isinstance(c, int) for c in counts):
        return counts
    return [-i for i in range(len(leaves))]


def _ring_tree(tree: Tree, axis, world: int, bucket_bytes: int,
               solo_bytes: int) -> Tree:
    """`psum_tree_bucketed` through `_allreduce_ring`: a leaf that is a
    bucket of its own rides in its own shape, the small ones concatenated.

    **The buckets go one after another, in the order the backward makes
    them** (`_made_order`), each held back (`lax.optimization_barrier`) until
    the one before it is summed.  Nothing in the arithmetic asks for that;
    the scheduler does: it works from the end of the program back, finds
    every bucket's hops ready there, beside the optimizer's update, and
    places them all behind the backward, where nothing hides them.  Chained,
    only the last bucket's hops are ready at the end, and each earlier
    bucket's come up for placement together with the backward work that
    makes the next.  The first bucket keeps the all-reduce if it is over
    `_RING_FIRST_MAX_BYTES`."""
    leaves, treedef = jax.tree.flatten(tree)
    made = _made_order(leaves)
    plan = sorted(_plan_buckets(leaves, bucket_bytes, solo_bytes),
                  key=lambda idxs: max(made[i] for i in idxs))
    out: list[Any] = [None] * len(leaves)
    turn = _ring_turn(axis, world)

    def keep(idxs, summed):
        if len(idxs) == 1:
            out[idxs[0]] = summed
        else:
            _unpack(out, leaves, idxs, summed)

    before = None
    for idxs in plan:
        bucket = (leaves[idxs[0]] if len(idxs) == 1 else
                  jnp.concatenate([leaves[i].reshape(-1) for i in idxs]))
        if (idxs is plan[0] and bucket.size * bucket.dtype.itemsize
                > _RING_FIRST_MAX_BYTES):
            keep(idxs, lax.psum(bucket, axis))
            continue
        if before is not None:
            bucket, done = lax.optimization_barrier((bucket, before[1]))
            keep(before[0], done)
        before = (idxs, _allreduce_ring(bucket, turn, axis, world))
    if before is not None:
        keep(*before)
    return jax.tree.unflatten(treedef, out)


def psum_tree_bucketed(tree: Tree, axis: str = PS_AXIS, *,
                       bucket_bytes: "int | None" = DEFAULT_BUCKET_BYTES,
                       solo_bytes: "int | None" = None,
                       ring: bool = False) -> Tree:
    """`psum_tree` with dtype-bucketed flat all-reduces — the same
    elementwise sum (bitwise-equal on the tested CPU backend; cross-rank
    reduction order on TPU is backend-scheduled, see module comment),
    ~#buckets collectives instead of ~#leaves.
    ``bucket_bytes=None``/0 is the per-leaf lowering (one dispatch point:
    call sites pass their knob through unconditionally).
    ``ring=True`` (internal: `MPI_PS` sets it from what it sees of its
    mesh, no caller passes it by hand) sums each bucket through
    `_allreduce_ring`'s collective-permute hops instead (`_ring_tree`).
    **The two lowerings, and where each runs**: ``lax.psum`` on the CPU, on
    one chip and wherever a caller asks for nothing else: one all-reduce a
    bucket, which XLA's combiner regroups (twelve for GPT-2 medium) and the
    v5e runs synchronously on its one core, each where its last operand is
    made; the ring where `MPI_PS` passes ``ring=True``, on several TPU
    chips, because a collective-permute is the one collective the v5e's
    compiler keeps in flight beside the backward (an asynchronous
    all-reduce is made and turned back, an all-gather and a reduce-scatter
    are synchronous: PERF.md §6).
    ``solo_bytes`` (None = auto, ``bucket_bytes // 16``; 0 = legacy
    pack-everything): leaves at/above the threshold skip the shared
    bucket and sum solo — the concat-in/slice-out memcpy around a leaf
    that already amortizes its collective is pure overhead (measured
    ~2x the whole step on the w8 gradsync payload; same bitwise sum
    either way, see `_plan_buckets`)."""
    if ring and bucket_bytes:
        return _ring_tree(tree, axis, _axis_world(axis), bucket_bytes,
                          _solo_default(bucket_bytes, solo_bytes))
    if not bucket_bytes:
        return psum_tree(tree, axis)
    return _bucketed_leafwise(
        tree, lambda x: lax.psum(x, axis), bucket_bytes,
        _solo_default(bucket_bytes, solo_bytes))


def allgather_tree_bucketed(tree: Tree, axis: str = PS_AXIS, *,
                            bucket_bytes: "int | None" = DEFAULT_BUCKET_BYTES,
                            solo_bytes: "int | None" = None) -> Tree:
    """`allgather_tree` (untiled: leaves grow a leading world dim) with
    dtype-bucketed flat all-gathers.  ``bucket_bytes=None``/0 is the
    per-leaf lowering; ``solo_bytes`` as in `psum_tree_bucketed` (large
    leaves gather solo — same gathered bytes, no packing memcpy)."""
    if not bucket_bytes:
        return allgather_tree(tree, axis)
    return _bucketed_leafwise(
        tree, lambda x: lax.all_gather(x, axis), bucket_bytes,
        _solo_default(bucket_bytes, solo_bytes))


def reduce_scatter_flats_bucketed(
        tree: Tree, axis, *, world: int,
        bucket_bytes: "int | None" = DEFAULT_BUCKET_BYTES) -> Tree:
    """Bucketed ZeRO gradient sync: every leaf is a padded flat
    ``(world * chunk_leaf,)`` whose tile ``r`` belongs to rank ``r``;
    returns ``(chunk_leaf,)`` leaves holding the cross-rank SUM of this
    rank's tile.  Bucketing concatenates the per-rank tiles of many leaves
    into one ``(world, total)`` block so a single ``psum_scatter`` serves
    them all — the same elementwise sum as the per-leaf lowering (bitwise-
    equal on the tested CPU backend; TPU reduction order is backend-
    scheduled, see module comment), pure data movement around it.
    Large leaves go solo per the shared `_plan_buckets` threshold."""
    def per_leaf(x):
        return lax.psum_scatter(x, axis, scatter_dimension=0, tiled=True)

    leaves, treedef = jax.tree.flatten(tree)
    if not bucket_bytes:
        return jax.tree.unflatten(treedef, [per_leaf(x) for x in leaves])
    out: list[Any] = [None] * len(leaves)
    for idxs in _plan_buckets(leaves, bucket_bytes,
                              _solo_default(bucket_bytes, None)):
        if len(idxs) == 1:
            out[idxs[0]] = per_leaf(leaves[idxs[0]])
            continue
        rows = [leaves[i].reshape(world, -1) for i in idxs]
        cat = jnp.concatenate(rows, axis=1)           # (world, total)
        mine = per_leaf(cat.reshape(-1))              # (total,)
        off = 0
        for i in idxs:
            chunk = leaves[i].size // world
            out[i] = mine[off:off + chunk]
            off += chunk
    return jax.tree.unflatten(treedef, out)




# ---------------------------------------------------------------------------
# Host API — non-blocking collectives on sharded pytrees
# ---------------------------------------------------------------------------


class PendingTree:
    """Non-blocking collective handle — the ``MPI.Request`` analogue.

    JAX dispatch is asynchronous: the arrays inside ``result`` are futures the
    moment the collective is *posted*.  ``wait()`` blocks until transfer
    completion (``Request.Wait()``, `/root/reference/mpi_comms.py:110,167`) and
    records ``comm_wait`` wall-clock into the timing dict, mirroring
    `/root/reference/ps.py:160-162`.
    """

    def __init__(self, result: Tree, timings: dict[str, float]):
        self.result = result
        self.timings = timings
        self._done = False

    def wait(self) -> Tree:
        start = time.perf_counter()
        jax.block_until_ready(self.result)
        if not self._done:
            self.timings["comm_wait"] = time.perf_counter() - start
            self._done = True
        return self.result

    # Convenience: Request-like spelling.
    Wait = wait


def _sharded_collective(mesh: Mesh, axis: str, body, out_replicated: bool):
    # check_vma=False: all_gather/bcast outputs are value-replicated across the
    # axis but JAX's varying-axes type system can't prove it statically.
    out_spec = P() if out_replicated else P(axis)
    return jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=P(axis), out_specs=out_spec,
                      check_vma=False))


def _timed_dispatch(fn, tree, *, name: str) -> PendingTree:
    timings: dict[str, float] = {"msg_bytes": bytes_of(tree)}
    start = time.perf_counter()
    out = fn(tree)
    timings[f"{name}_time"] = time.perf_counter() - start  # dispatch latency
    return PendingTree(out, timings)


def iallgather(tree: Tree, mesh: Mesh, *, axis: str = PS_AXIS) -> PendingTree:
    """All ranks exchange their shard; every rank ends with the stacked
    ``[size, ...]`` leaves.  Replaces ``Iallgather`` sizes + ``Iallgatherv``
    payloads (`/root/reference/mpi_comms.py:144-174`).

    ``tree`` leaves must have leading dim == world size, sharded (or shardable)
    across ``axis`` — slice ``r`` is rank ``r``'s payload.
    """
    fn = _sharded_collective(
        mesh, axis, partial(allgather_tree, axis=axis, tiled=True),
        out_replicated=True)
    return _timed_dispatch(fn, tree, name="iallgather")


def igather(tree: Tree, mesh: Mesh, *, axis: str = PS_AXIS,
            root: int = 0, root_only: bool = False) -> PendingTree:
    """Gather-to-root — the ``Igatherv`` + sentinel-framing protocol
    (`/root/reference/mpi_comms.py:60-117`), static-shape edition.

    Two lowerings:

    * ``root_only=False`` (default) — SPMD all-gather: XLA's SPMD model has
      no root-only collective (every rank runs the same program with uniform
      shapes), so the idiomatic lowering is an all-gather and every rank
      materializes the stack.  The root-only contract is preserved at the
      API level: ``wait()`` returns the stacked payloads the way ``irecv``
      did on rank 0 (`mpi_comms.py:107-117`).
    * ``root_only=True`` — true root-only memory/traffic asymmetry, the
      shape of the reference's ``Igatherv`` (`mpi_comms.py:88,109`: payload
      lands on rank 0 only; workers pay send-side cost only).  Host-driven
      on the single-controller runtime (the same dispatch model as the
      async PS, which is what this building block exists for): each rank's
      shard is device-to-device transferred to the root device and the
      stack is materialized **there alone** — non-root devices never hold
      the ``world × payload`` buffer.  Requires all of ``mesh``'s devices
      on ``axis`` to be addressable from this controller.
    """
    if not root_only:
        del root  # SPMD all-gather: every rank materializes the result.
        return iallgather(tree, mesh, axis=axis)

    ax = mesh.axis_names.index(axis)
    world = mesh.shape[axis]
    # Devices along `axis` (other mesh axes, if any, are at index 0 —
    # the gather is defined per PS group, like MPI's communicator).
    dev_index = [0] * mesh.devices.ndim
    devs = []
    for r in range(world):
        dev_index[ax] = r
        devs.append(mesh.devices[tuple(dev_index)])
    root_dev = devs[root]

    timings: dict[str, float] = {"msg_bytes": bytes_of(tree)}
    start = time.perf_counter()

    def gather_leaf(x):
        # Contract (same as `iallgather`): leading dim == world, slice r is
        # rank r's payload.  Pull every rank's slice to the root device —
        # the send-side D2D transfers — and stack there.
        #
        # Fast path: one FULL row per rank, read straight off that rank's
        # device.  A shard qualifies only if it is exactly one leading row
        # and covers every non-leading dim end-to-end — on a multi-axis
        # mesh a leaf also sharded along a non-leading dim produces several
        # *partial* shards per row offset, and keying by offset alone would
        # silently gather partial rows (r3 advisor finding).  Any other
        # layout falls back to global indexing, which is always correct.
        def full_row(s):
            if s.data.shape[0] != 1:
                return False
            return all(
                (sl.start or 0) == 0
                and (sl.stop is None or sl.stop == x.shape[dim])
                for dim, sl in enumerate(s.index[1:], start=1))

        shards = {}
        for s in x.addressable_shards:
            if full_row(s):
                shards[s.index[0].start or 0] = s.data
        if len(shards) == world and sorted(shards) == list(range(world)):
            rows = [shards[r] for r in sorted(shards)]
            # ONE batched device_put for all rows (r4 review: the per-rank
            # loop dispatched world sequential transfers; a single call
            # lets the runtime overlap the D2D copies).
            moved = jax.device_put(rows, [root_dev] * world)
            return jnp.stack([jnp.squeeze(m, 0) for m in moved])
        # Fallback for any other layout (replicated, partial multi-axis
        # shards, unexpected leading split): assemble the global value on
        # the host — always correct, and the root-only contract still
        # holds (host numpy device_puts STRAIGHT to the root device; no
        # other device ever materializes the stack).
        import numpy as np

        return jax.device_put(np.asarray(jax.device_get(x)), root_dev)

    out = jax.tree.map(gather_leaf, tree)
    timings["igather_time"] = time.perf_counter() - start
    return PendingTree(out, timings)


def ibroadcast(tree: Tree, mesh: Mesh, *, axis: str = PS_AXIS,
               root: int = 0) -> PendingTree:
    """Broadcast root's shard to all ranks — ``Ibcast`` of the compressed
    pickle (`/root/reference/mpi_comms.py:127-133`), the AsySG-InCon param
    push.  ``wait()`` is the ``irecv1`` analogue (`mpi_comms.py:120-124`)."""
    def body(t):
        t = jax.tree.map(lambda x: jnp.squeeze(x, 0), t)
        return bcast_tree(t, axis, root=root)

    fn = _sharded_collective(mesh, axis, body, out_replicated=True)
    return _timed_dispatch(fn, tree, name="ibroadcast")


def ialltoall(tree: Tree, mesh: Mesh, *, axis: str = PS_AXIS) -> PendingTree:
    """Each rank scatters its slices to all ranks — ``Ialltoallv``
    (`/root/reference/test_mpi.py:11-25`), static-shape edition."""
    def body(t):
        t = jax.tree.map(lambda x: jnp.squeeze(x, 0), t)
        out = alltoall_tree(t, axis)
        return jax.tree.map(lambda x: x[None], out)

    fn = _sharded_collective(mesh, axis, body, out_replicated=False)
    return _timed_dispatch(fn, tree, name="ialltoall")


def ireduce(tree: Tree, mesh: Mesh, *, axis: str = PS_AXIS) -> PendingTree:
    """Sum each rank's payload into a replicated result (all-reduce)."""

    def body(t):
        return jax.tree.map(lambda x: lax.psum(jnp.squeeze(x, 0), axis), t)

    fn = _sharded_collective(mesh, axis, body, out_replicated=True)
    return _timed_dispatch(fn, tree, name="ireduce")
