"""Round-trip tests of the collectives shim — the reference's test strategy
(`/root/reference/test_comms.py`, `test_mpi.py`, `test_iallgather.py`): build
rank-dependent payloads, push them through a real collective across real
(virtual) devices, and compare against a locally reconstructed expected value
for *all* ranks.  Payloads are deliberately rank-dependent (the ``[rank]*(rank
+1)`` trick of `test_comms.py:10` becomes rank-scaled pytrees; sizes are static
under XLA so variable-*size* payloads become variable-*content*)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.parallel import collectives as C
from pytorch_ps_mpi_tpu.parallel.mesh import batch_sharded, world_size


def rank_payload(mesh, shape=(4,)):
    """Global array whose slice r along dim0 is rank r's payload: r * ones."""
    n = world_size(mesh)
    data = np.stack([np.full(shape, r, np.float32) for r in range(n)])
    return jax.device_put(data, batch_sharded(mesh))


def rank_tree(mesh):
    """Pytree payload — the reference round-trips dicts of tensors
    (`test_comms.py:9-16`)."""
    n = world_size(mesh)
    return {
        "w": rank_payload(mesh, (2, 3)),
        "nested": {"b": rank_payload(mesh, (5,))},
    }


def test_iallgather_roundtrip(mesh8):
    n = world_size(mesh8)
    tree = rank_tree(mesh8)
    pending = C.iallgather(tree, mesh8)
    out = pending.wait()
    # Every rank ends with all ranks' payloads, in rank order.
    for r in range(n):
        np.testing.assert_array_equal(np.asarray(out["w"][r]),
                                      np.full((2, 3), r, np.float32))
        np.testing.assert_array_equal(np.asarray(out["nested"]["b"][r]),
                                      np.full((5,), r, np.float32))
    assert "comm_wait" in pending.timings
    assert pending.timings["msg_bytes"] > 0


def test_igather_matches_local_reconstruction(mesh8):
    """`test_comms.py:9-16` analogue: expected = [payload(r) for r in ranks]."""
    n = world_size(mesh8)
    x = rank_payload(mesh8, (3,))
    out = C.igather(x, mesh8, root=0).wait()
    expected = np.stack([np.full((3,), r, np.float32) for r in range(n)])
    np.testing.assert_array_equal(np.asarray(out), expected)


def test_igather_root_only_lowering(mesh8):
    """True root-only gather (`/root/reference/mpi_comms.py:88,109`): the
    stacked payload materializes on the ROOT device alone — non-root ranks
    pay send-side cost only and never hold the world × payload buffer (the
    memory asymmetry the async-PS topology is designed around)."""
    n = world_size(mesh8)
    for root in (0, 3):
        tree = rank_tree(mesh8)
        pending = C.igather(tree, mesh8, root=root, root_only=True)
        out = pending.wait()
        # Same values as the SPMD all-gather lowering...
        for r in range(n):
            np.testing.assert_array_equal(np.asarray(out["w"][r]),
                                          np.full((2, 3), r, np.float32))
        # ...but every output leaf lives ONLY on the root device.
        root_dev = mesh8.devices[root]
        for leaf in jax.tree.leaves(out):
            assert leaf.sharding.device_set == {root_dev}, (
                f"root_only gather leaked onto {leaf.sharding.device_set}")
        assert "igather_time" in pending.timings


def test_igather_root_only_multiaxis_mesh():
    """Regression (r3 advisor): on a multi-axis mesh, a leaf sharded along a
    NON-leading dim too produces several *partial* shards per row offset;
    keying shards by leading offset alone silently gathered partial rows.
    The fast path must reject partial shards and fall back to global
    indexing — values must match the single-axis lowering exactly."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel.mesh import make_dp_tp_mesh

    mesh = make_dp_tp_mesh(4, 2)  # axes ('ps', 'tp'), 4x2 over 8 devices
    world = 4
    cols = 6
    data = np.stack([np.arange(cols * 2, dtype=np.float32).reshape(2, cols)
                     + 100 * r for r in range(world)])
    # Leading dim over the PS axis AND columns over tp: each row offset now
    # has two partial shards, the advisor's silent-partial-gather shape.
    x = jax.device_put(data, NamedSharding(mesh, P("ps", None, "tp")))
    out = C.igather(x, mesh, axis="ps", root=0, root_only=True).wait()
    np.testing.assert_array_equal(np.asarray(out), data)
    # Root-only contract still holds: output on one device only.
    assert len(jax.tree.leaves(out)[0].sharding.device_set) == 1


def test_ibroadcast_roundtrip(mesh8):
    """`test_comms.py:19-26` analogue: every rank receives root's payload."""
    n = world_size(mesh8)
    x = rank_payload(mesh8, (4,))
    for root in (0, 3):
        out = C.ibroadcast(x, mesh8, root=root).wait()
        # Result is replicated: a single [4] array equal to root's slice.
        np.testing.assert_array_equal(np.asarray(out),
                                      np.full((4,), root, np.float32))


def test_ireduce_sums_across_ranks(mesh8):
    n = world_size(mesh8)
    x = rank_payload(mesh8, (2, 2))
    out = C.ireduce(x, mesh8).wait()
    total = sum(range(n))
    np.testing.assert_array_equal(np.asarray(out),
                                  np.full((2, 2), total, np.float32))


def test_ialltoall_transposes_rank_dim(mesh8):
    """`test_mpi.py:11-25` Ialltoallv analogue: rank r sends slice s to rank s;
    afterwards rank s holds [r-th slice of every rank]."""
    n = world_size(mesh8)
    # Global [n, n] where element (r, s) = r*10 + s: rank r's payload for s.
    data = np.arange(n)[:, None] * 10 + np.arange(n)[None, :]
    x = jax.device_put(data.astype(np.float32), batch_sharded(mesh8))
    out = C.ialltoall(x, mesh8).wait()
    # After all-to-all, global element (s, r) = r*10 + s — the transpose.
    np.testing.assert_array_equal(np.asarray(out),
                                  data.T.astype(np.float32))


def test_in_step_primitives_inside_shard_map(mesh8):
    """The hot-path primitives used by the PS step, exercised directly."""
    from jax.sharding import PartitionSpec as P
    n = world_size(mesh8)
    x = rank_payload(mesh8, (3,))

    def body(t):
        t = jax.tree.map(lambda v: jnp.squeeze(v, 0), t)
        return (
            C.psum_tree(t),
            C.bcast_tree(t, root=2),
            C.ring_shift_tree(t, shift=1, size=n)[None],
        )

    f = jax.jit(jax.shard_map(
        body, mesh=mesh8, in_specs=P("ps"), out_specs=(P(), P(), P("ps")),
        check_vma=False))
    s, b, ring = f(x)
    np.testing.assert_array_equal(np.asarray(s), np.full((3,), sum(range(n)), np.float32))
    np.testing.assert_array_equal(np.asarray(b), np.full((3,), 2, np.float32))
    # ring shift by 1: rank r now holds (r-1) mod n's payload.
    expected = np.stack([np.full((3,), (r - 1) % n, np.float32)
                         for r in range(n)])
    np.testing.assert_array_equal(np.asarray(ring), expected)


def test_reduce_scatter(mesh8):
    from jax.sharding import PartitionSpec as P
    n = world_size(mesh8)
    # Each rank contributes arange(n*2); reduce-scatter leaves each rank with
    # its 2-element shard of the sum.
    data = np.tile(np.arange(n * 2, dtype=np.float32), (n, 1))
    x = jax.device_put(data, batch_sharded(mesh8))

    def body(t):
        return C.reduce_scatter_tree(jnp.squeeze(t, 0))

    f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("ps"),
                              out_specs=P("ps")))
    out = f(x)
    np.testing.assert_array_equal(
        np.asarray(out), np.arange(n * 2, dtype=np.float32) * n)


def test_psum_bucketed_decomposed_matches_allreduce(mesh8):
    """``decompose=True`` lowers each bucket as reduce-scatter+all-gather;
    the result must equal the plain bucketed all-reduce (same elementwise
    cross-rank sum).  Covers the padding path (leaf sizes not divisible by
    world) and mixed dtypes (separate buckets)."""
    from jax.sharding import PartitionSpec as P
    n = world_size(mesh8)
    rng = np.random.RandomState(0)
    # Sizes chosen so flat totals (7, 3*5=15, 10) are NOT multiples of 8.
    tree = {
        "a": jax.device_put(
            rng.randn(n, 7).astype(np.float32), batch_sharded(mesh8)),
        "b": jax.device_put(
            rng.randn(n, 3, 5).astype(np.float32), batch_sharded(mesh8)),
        "c": jax.device_put(
            rng.randn(n, 10).astype(np.float16), batch_sharded(mesh8)),
    }

    def run(decompose, bucket_bytes=1 << 20):
        def body(t):
            t = jax.tree.map(lambda v: jnp.squeeze(v, 0), t)
            return C.psum_tree_bucketed(t, bucket_bytes=bucket_bytes,
                                        decompose=decompose)
        f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("ps"),
                                  out_specs=P(), check_vma=False))
        return jax.device_get(f(tree))

    ref = run(False)
    # Bucketed AND per-leaf (bucket_bytes=None) decomposed lowerings: the
    # flag must not silently no-op in the per-param configuration.
    for dec in (run(True), run(True, bucket_bytes=None)):
        for k in ref:
            assert ref[k].shape == dec[k].shape
            assert ref[k].dtype == dec[k].dtype
            np.testing.assert_allclose(np.asarray(dec[k], np.float64),
                                       np.asarray(ref[k], np.float64),
                                       rtol=1e-3 if k == "c" else 1e-6)


def test_psum_bucketed_decomposed_tuple_axes():
    """Hierarchical data-parallel axes (the hybrid (dcn, ps) shape): the
    decomposed lowering must sum over BOTH axes like the psum it replaces."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel.mesh import make_dp_tp_mesh

    mesh = make_dp_tp_mesh(4, 2)  # axes ('ps', 'tp'); treat both as data
    data = np.arange(8 * 5, dtype=np.float32).reshape(8, 5)
    x = jax.device_put(data, NamedSharding(mesh, P(("ps", "tp"))))

    def body(t):
        t = jnp.squeeze(t, 0)
        return C.psum_tree_bucketed({"g": t}, ("ps", "tp"),
                                    bucket_bytes=1 << 20,
                                    decompose=True)["g"]

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P(("ps", "tp")),
                              out_specs=P(), check_vma=False))
    np.testing.assert_allclose(np.asarray(f(x)), data.sum(0), rtol=1e-6)


def test_bytes_of_nd_correct():
    """The reference's `_bytes_of` self-notes a 2-D bug (`ps.py:26-27`); ours
    must be exact for any rank."""
    from pytorch_ps_mpi_tpu.utils.bytes import bytes_of
    t = {"a": np.zeros((3, 4), np.float32), "b": [np.zeros((2, 2, 2), np.float64)]}
    assert bytes_of(t) == 3 * 4 * 4 + 8 * 8


# ---------------------------------------------------------------------------
# _plan_buckets edge cases + reduce_scatter_flats_bucketed padding
# ---------------------------------------------------------------------------


def _leaf_bytes(x):
    return x.size * x.dtype.itemsize


def test_plan_buckets_empty_tree():
    assert C._plan_buckets([], bucket_bytes=1 << 20) == []


def test_plan_buckets_single_leaf_larger_than_bucket():
    """One oversized leaf gets its OWN bucket (never split, never dropped)."""
    big = np.zeros((1 << 18,), np.float32)  # 1 MiB leaf, 64 KiB buckets
    plan = C._plan_buckets([big], bucket_bytes=64 << 10)
    assert plan == [[0]]
    # Oversized leaf surrounded by small ones: the big leaf still lands in
    # a bucket by itself once the running bucket closes around it.
    small = np.zeros((8,), np.float32)
    plan = C._plan_buckets([small, big, small], bucket_bytes=64 << 10)
    assert sorted(i for b in plan for i in b) == [0, 1, 2]
    [big_bucket] = [b for b in plan if 1 in b]
    assert big_bucket == [1]


def test_plan_buckets_zero_size_leaves():
    """Zero-size leaves cost nothing and must still be assigned exactly once
    (the slice-back in the bucketed collectives depends on every index
    appearing)."""
    leaves = [np.zeros((0,), np.float32), np.zeros((4,), np.float32),
              np.zeros((0,), np.float32)]
    plan = C._plan_buckets(leaves, bucket_bytes=1 << 20)
    assert sorted(i for b in plan for i in b) == [0, 1, 2]
    # All same dtype and tiny: one bucket.
    assert len(plan) == 1


def test_plan_buckets_mixed_dtypes_never_share_a_bucket():
    leaves = [np.zeros((4,), np.float32), np.zeros((4,), np.float16),
              np.zeros((4,), np.float32), np.zeros((4,), np.int32)]
    plan = C._plan_buckets(leaves, bucket_bytes=1 << 20)
    assert sorted(i for b in plan for i in b) == [0, 1, 2, 3]
    for bucket in plan:
        dtypes = {leaves[i].dtype for i in bucket}
        assert len(dtypes) == 1
    # f32 leaves share; f16/int32 are separate buckets.
    assert [0, 2] in plan


def test_plan_buckets_respects_byte_budget_and_order():
    """Greedy packing: deterministic in leaf order, each bucket's total <=
    budget (single-oversized-leaf exception covered above)."""
    rng = np.random.RandomState(0)
    leaves = [np.zeros((rng.randint(1, 2000),), np.float32)
              for _ in range(37)]
    budget = 4000  # bytes: forces many buckets
    plan = C._plan_buckets(leaves, bucket_bytes=budget)
    seen = [i for b in plan for i in b]
    assert sorted(seen) == list(range(37))
    for bucket in plan:
        total = sum(_leaf_bytes(leaves[i]) for i in bucket)
        assert total <= budget or len(bucket) == 1
    # Determinism: same input -> same plan.
    assert plan == C._plan_buckets(leaves, bucket_bytes=budget)


def test_reduce_scatter_flats_bucketed_padding_correct(mesh8):
    """ZeRO bucketed reduce-scatter on padded flats: for leaf sizes NOT
    divisible by world, the (world*chunk,) padded layout's per-rank tile r
    must come back as the cross-rank SUM of every rank's tile r — compare
    against a locally reconstructed expectation for all ranks, including
    the zero pad tail."""
    from jax.sharding import PartitionSpec as P
    world = world_size(mesh8)
    rng = np.random.RandomState(1)
    sizes = {"a": 13, "b": 8 * 5, "c": 1}  # 13 and 1 need padding
    full = {}
    for name, sz in sizes.items():
        chunk = -(-sz // world)
        per_rank = []
        for r in range(world):
            flat = np.zeros((world * chunk,), np.float32)
            flat[:sz] = rng.randn(sz)
            per_rank.append(flat)
        full[name] = np.stack(per_rank)  # [world, world*chunk]

    tree = {n: jax.device_put(v, batch_sharded(mesh8))
            for n, v in full.items()}

    def body(t):
        t = jax.tree.map(lambda v: jnp.squeeze(v, 0), t)
        out = C.reduce_scatter_flats_bucketed(
            t, "ps", world=world, bucket_bytes=1 << 20)
        return jax.tree.map(lambda v: v[None], out)

    f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("ps"),
                              out_specs=P("ps"), check_vma=False))
    got = jax.device_get(f(tree))

    for name, sz in sizes.items():
        chunk = -(-sz // world)
        summed = full[name].sum(axis=0)          # [world*chunk]
        for r in range(world):
            np.testing.assert_allclose(
                np.asarray(got[name][r]),
                summed[r * chunk:(r + 1) * chunk], rtol=1e-5,
                err_msg=f"{name} rank {r}")

    # Per-leaf lowering (bucket_bytes=None) must agree exactly.
    def body_perleaf(t):
        t = jax.tree.map(lambda v: jnp.squeeze(v, 0), t)
        out = C.reduce_scatter_flats_bucketed(
            t, "ps", world=world, bucket_bytes=None)
        return jax.tree.map(lambda v: v[None], out)

    f2 = jax.jit(jax.shard_map(body_perleaf, mesh=mesh8, in_specs=P("ps"),
                               out_specs=P("ps"), check_vma=False))
    got2 = jax.device_get(f2(tree))
    for name in sizes:
        np.testing.assert_allclose(np.asarray(got2[name]),
                                   np.asarray(got[name]), rtol=1e-6)


def test_plan_buckets_groups_by_dtype_and_caps_bytes():
    from pytorch_ps_mpi_tpu.parallel.collectives import _plan_buckets

    import jax.numpy as jnp

    leaves = [jnp.zeros(100, jnp.float32),    # 400 B
              jnp.zeros(50, jnp.int32),       # 200 B
              jnp.zeros(200, jnp.float32),    # 800 B
              jnp.zeros(5000, jnp.float32),   # 20 kB > cap: own bucket
              jnp.zeros(10, jnp.float32)]     # 40 B
    plan = _plan_buckets(leaves, bucket_bytes=1500)
    # Every leaf appears exactly once.
    flat = sorted(i for b in plan for i in b)
    assert flat == [0, 1, 2, 3, 4]
    for b in plan:
        dtypes = {str(leaves[i].dtype) for i in b}
        assert len(dtypes) == 1  # same-dtype buckets only
        if len(b) > 1:  # multi-leaf buckets respect the cap
            assert sum(leaves[i].size * leaves[i].dtype.itemsize
                       for i in b) <= 1500
    # The oversized leaf is alone in its bucket.
    assert [3] in plan
    # Deterministic: same input, same plan.
    assert plan == _plan_buckets(leaves, bucket_bytes=1500)


# ---------------------------------------------------------------------------
# The ring of ppermute hops that takes a bucket's all-reduce on several TPU
# chips (`_allreduce_ring`, `_ring_tree`): platform-blind, so it runs here
# ---------------------------------------------------------------------------


def _ring_and_psum(world, shape, values):
    """``values`` is ``[world, *shape]``, row r rank r's addend; returns the
    ring's sum and `lax.psum`'s, each ``[world, *shape]`` (one row a
    replica)."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    def body(v):
        v = v[0]
        turn = C._ring_turn("ps", world)
        return (C._allreduce_ring(v, turn, "ps", world)[None],
                lax.psum(v, "ps")[None])

    f = jax.jit(jax.shard_map(
        body, mesh=make_ps_mesh(world), in_specs=P("ps"),
        out_specs=(P("ps"), P("ps")), check_vma=False))
    return map(np.asarray, f(values))


# whole chunks only; whole chunks and a tail that `lax.psum` takes; a matrix
# cut along its rows, with and without a tail; too small for one chunk; more
# dimensions; a scalar
RING_SHAPES = [(2 * 4 * 1024 * 3,), (2 * 4 * 1024 + 37,), (128, 24),
               (131, 5), (3,), (16, 2, 3), ()]


@pytest.mark.parametrize("shape", RING_SHAPES, ids=str)
@pytest.mark.parametrize("world", [2, 4])
def test_ring_allreduce_is_the_sum_and_the_same_on_every_replica(
        world, shape):
    """Against `lax.psum`: close to rounding on random f32 (the additions
    come in another order), exactly equal where the addends are small
    integers, and **bitwise equal across the replicas** either way (every
    rank holds the one sum that one rank computed), whether or not the rows
    divide by the chunks."""
    rng = np.random.RandomState(len(shape) + world)
    ring, psum = _ring_and_psum(
        world, shape, (rng.randn(world, *shape) * 100).astype(np.float32))
    for r in range(1, world):
        np.testing.assert_array_equal(ring[r], ring[0])
    np.testing.assert_allclose(ring, psum, rtol=1e-6, atol=1e-4)
    ring, psum = _ring_and_psum(
        world, shape,
        rng.randint(-99, 99, (world,) + shape).astype(np.float32))
    np.testing.assert_array_equal(ring, psum)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=str)
def test_ring_allreduce_in_pieces_keeps_the_dtype_and_the_sum(
        dtype, monkeypatch):
    """A leaf over `_RING_PIECE_BYTES` goes round in several pieces, one
    ring each, and a bf16 leaf is cut in multiples of its own (16-row)
    tiles: the same sum as `lax.psum`, in the leaf's dtype, the same bits on
    every replica."""
    monkeypatch.setattr(C, "_RING_PIECE_BYTES", 64 * 24 * 2)
    rng = np.random.RandomState(7)
    values = jnp.asarray(rng.randint(-9, 9, (4, 300, 24)), dtype)
    ring, psum = _ring_and_psum(4, (300, 24), values)
    assert ring.dtype == psum.dtype == jnp.dtype(dtype)
    np.testing.assert_array_equal(ring, psum)
    for r in range(1, 4):
        np.testing.assert_array_equal(ring[r], ring[0])


def _lowered_ring(shape=(64, 8), world=4):
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    f = jax.jit(jax.shard_map(
        lambda v: C._allreduce_ring(
            v[0], C._ring_turn("ps", world), "ps", world)[None],
        mesh=make_ps_mesh(world), in_specs=P("ps"), out_specs=P("ps"),
        check_vma=False))
    return f.lower(jnp.zeros((world,) + shape, jnp.float32)).as_text()


def test_ring_hops_are_collective_permutes_both_ways_round():
    """What makes it worth having: ``2 * (world - 1)`` hops a half, the two
    halves in opposite senses, and no all-reduce where the rows divide."""
    text = _lowered_ring()
    hops = [ln for ln in text.split("\n") if "collective_permute" in ln]
    assert len(hops) == 12 and "all_reduce" not in text
    senses = {"[[0, 1], [1, 2], [2, 3], [3, 0]]",
              "[[0, 3], [1, 0], [2, 1], [3, 2]]"}
    found = {s for s in senses for ln in hops if s in ln}
    assert found == senses, hops[0]


def _tree_sums(tree, *, ring, bucket_bytes=1 << 14, world=4, lower=False):
    """`psum_tree_bucketed` of ``tree`` (leaves ``[world, ...]``, row r rank
    r's) on ``world`` CPU devices, one replica's result; or the lowered
    text."""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    def body(t):
        t = jax.tree.map(lambda v: jnp.squeeze(v, 0), t)
        return C.psum_tree_bucketed(t, "ps", bucket_bytes=bucket_bytes,
                                    ring=ring)

    f = jax.jit(jax.shard_map(body, mesh=make_ps_mesh(world),
                              in_specs=P("ps"), out_specs=P(),
                              check_vma=False))
    return f.lower(tree).as_text() if lower else jax.device_get(f(tree))


def _ints(rng, shape, dtype=np.float32):
    return jnp.asarray(rng.randint(-9, 9, shape), dtype)


RING_TREES = {
    # large leaves ride in their own shape, small ones packed, each dtype in
    # buckets of its own, rows that do not divide by the chunks
    "mixed": lambda rng: {"big": _ints(rng, (4, 256, 40)),
                          "odd": _ints(rng, (4, 67, 3)),
                          "small": _ints(rng, (4, 7)),
                          "half": _ints(rng, (4, 4096), jnp.bfloat16),
                          "half2d": _ints(rng, (4, 130, 40), jnp.bfloat16)},
    "one_leaf": lambda rng: {"w": _ints(rng, (4, 192, 24))},
    "one_small_leaf": lambda rng: {"b": _ints(rng, (4, 5))},
    # nothing to sum, and a leaf of no elements among others
    "empty": lambda rng: {},
    "empty_leaf": lambda rng: {"none": jnp.zeros((4, 0, 8), jnp.float32),
                               "w": _ints(rng, (4, 128, 8))},
}


@pytest.mark.parametrize("name", sorted(RING_TREES))
def test_psum_bucketed_through_the_ring_matches_the_allreduce(name):
    """`psum_tree_bucketed(ring=True)` against the same call without: the
    same tree of sums, shapes and dtypes (small integers, so the order of
    the additions cannot show)."""
    tree = RING_TREES[name](np.random.RandomState(3))
    ref, got = _tree_sums(tree, ring=False), _tree_sums(tree, ring=True)
    assert jax.tree.structure(ref) == jax.tree.structure(got)
    for k in ref:
        assert ref[k].shape == got[k].shape and ref[k].dtype == got[k].dtype
        np.testing.assert_array_equal(np.asarray(got[k], np.float32),
                                      np.asarray(ref[k], np.float32))


def test_made_order_reads_the_traces_own_counts_and_falls_back():
    """`_made_order`: gradients come out of a trace in the order the
    backward makes them (the parameter used last first), whatever order the
    tree lists them in; values that are no tracers keep the reverse of the
    order they came in."""
    def loss(p, x):
        h = jnp.tanh(x @ p["a"])
        h = jnp.tanh(h @ p["b"])
        return jnp.sum(h @ p["c"])

    seen = {}

    def f(p, x):
        g = jax.grad(loss)(p, x)
        names = sorted(g)
        keys = C._made_order([g[n] for n in names])
        seen["order"] = [n for _, n in sorted(zip(keys, names))]
        return g

    p = {k: jnp.ones((4, 4)) for k in "abc"}
    jax.jit(f).lower(p, jnp.ones((2, 4)))
    assert seen["order"] == ["c", "b", "a"]
    keys = C._made_order([np.zeros(2), np.zeros(3), np.zeros(4)])
    assert sorted(range(3), key=keys.__getitem__) == [2, 1, 0]


def test_ring_tree_chains_the_buckets_and_shares_one_body_a_shape(
        monkeypatch):
    """`_ring_tree`: one barrier between consecutive buckets (the chain that
    makes the scheduler place each bucket's hops beside the backward work
    that follows it); a first bucket over `_RING_FIRST_MAX_BYTES` summed by
    an all-reduce outside the chain; **leaves of one shape share one lowered
    ring** (twelve hops in the text for three buckets of a shape: what keeps
    the set-up cost of a deep model flat); and the same sums."""
    monkeypatch.setattr(C, "_RING_FIRST_MAX_BYTES", 64 * 64 * 4)
    rng = np.random.RandomState(5)
    # the trace makes the leaves in the order of their keys (`_made_order`)
    tree = {"a_huge": _ints(rng, (4, 128, 64)), "b": _ints(rng, (4, 64, 64)),
            "c": _ints(rng, (4, 64, 64)), "d": _ints(rng, (4, 64, 64))}
    got = _tree_sums(tree, ring=True, bucket_bytes=1 << 12)
    for k, v in tree.items():
        np.testing.assert_array_equal(got[k], np.asarray(v).sum(0))
    text = _tree_sums(tree, ring=True, bucket_bytes=1 << 12, lower=True)
    assert text.count("optimization_barrier") == 2      # b -> c -> d
    assert text.count("all_reduce") == 1                # a_huge
    assert text.count("collective_permute") == 12       # one body, 3 calls
    assert text.count("call @_allreduce_ring") == 3
