"""Overlapped bucket-scheduled gradient sync (`parallel/overlap.py` +
``MPI_PS(sync_mode="overlap")``).

Oracle strategy: the overlap engine moves WHERE the cross-rank sum runs
(inside backward, per bucket) but must not change WHAT is computed — every
mode/reducer/feature combination is compared against the post-backward
bucketed path on the same data, plus unit tests for the plan construction,
the auto-tuner, the schedule instrumentation, the refusal surface, and the
no-recompile contract of ``compile_step``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_ps_mpi_tpu import SGD, Adam
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.parallel import overlap as OV
from pytorch_ps_mpi_tpu.parallel.mesh import world_size
from pytorch_ps_mpi_tpu.utils.timing import (clear_overlap_schedules,
                                             overlap_schedules)


def _batch(seed=0, n=64):
    rng = np.random.RandomState(seed)
    return {"x": rng.randn(n, 16).astype(np.float32),
            "y": rng.randint(0, 4, n).astype(np.int32)}


def _train(mesh, steps=3, opt_cls=SGD, **kw):
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    opt = opt_cls(list(params.items()), lr=0.1, mesh=mesh, **kw)
    opt.compile_step(mlp_loss_fn)
    losses = [opt.step(_batch(i))[0] for i in range(steps)]
    return np.asarray(losses), {n: np.asarray(p)
                                for n, p in opt.params.items()}


def _assert_same(a, b, rtol=1e-5, atol=1e-6):
    la, pa = a
    lb, pb = b
    np.testing.assert_allclose(la, lb, rtol=rtol)
    for n in pa:
        np.testing.assert_allclose(pa[n], pb[n], rtol=rtol, atol=atol,
                                   err_msg=n)


# -- end-to-end parity -------------------------------------------------------


@pytest.mark.parametrize("reducer", ["rs_ag", "psum"])
def test_overlap_matches_bucketed_identity(mesh8, reducer):
    """Same losses and final params as the post-backward bucketed psum —
    the sum merely moved inside backward."""
    base = _train(mesh8, momentum=0.9)
    ovl = _train(mesh8, momentum=0.9, sync_mode="overlap",
                 overlap_reducer=reducer)
    _assert_same(base, ovl)


def test_overlap_matches_post_and_small_buckets(mesh8):
    """Bucket granularity is pure scheduling: a tiny bucket budget (every
    leaf its own bucket) and the auto-tuned plan agree with the baseline."""
    base = _train(mesh8)
    _assert_same(base, _train(mesh8, sync_mode="overlap", bucket_mb=1e-5))
    _assert_same(base, _train(mesh8, sync_mode="overlap", bucket_mb=0))
    _assert_same(base, _train(mesh8, sync_mode="post"))


def test_overlap_with_codec_matches_bucketed_codec(mesh8):
    """Lossy/cast codecs ride the per-bucket encode→gather→decode-sum hook;
    results must match the post-backward codec exchange exactly (same
    codes, same sum — only the issue point moved)."""
    for code in ("bf16", "blockq"):
        base = _train(mesh8, code=code)
        ovl = _train(mesh8, code=code, sync_mode="overlap")
        _assert_same(base, ovl, rtol=1e-4, atol=1e-5)


def test_overlap_zero_matches_replicated_overlap(mesh8):
    """ZeRO + overlap: the pre-summed gradients slice into owner chunks;
    updates must equal the replicated-state overlap run (and therefore the
    plain baseline)."""
    base = _train(mesh8, momentum=0.9)
    z = _train(mesh8, momentum=0.9, zero=True, sync_mode="overlap")
    _assert_same(base, z)


def test_overlap_adam_clip_skip_composes(mesh8):
    """Feature stack: Adam + clip_norm + skip_nonfinite on the overlap
    path equals the same stack on the bucketed path."""
    kw = dict(opt_cls=Adam, clip_norm=0.5, skip_nonfinite=True)
    base = _train(mesh8, **kw)
    ovl = _train(mesh8, sync_mode="overlap", **kw)
    _assert_same(base, ovl)


def test_overlap_skip_nonfinite_skips_poisoned_batch(mesh8):
    """A NaN batch under overlap still triggers the world-consensus skip:
    the summed gradient propagates any rank's non-finite value."""
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8,
              skip_nonfinite=True, sync_mode="overlap")
    opt.compile_step(mlp_loss_fn)
    before = {n: np.asarray(p) for n, p in opt.params.items()}
    bad = _batch(0)
    bad["x"][3, :] = np.nan
    _, data = opt.step(bad)
    assert data["nonfinite_skip"] == 1.0
    for n, p in opt.params.items():
        np.testing.assert_array_equal(np.asarray(p), before[n], err_msg=n)


# -- the hook mechanism in isolation ----------------------------------------


def test_wrap_loss_grads_are_cross_rank_summed(mesh8):
    """Inside shard_map, grads of the wrapped loss equal psum(raw grads)."""
    w = world_size(mesh8)
    from collections import OrderedDict
    params = OrderedDict(
        (n, jnp.asarray(v)) for n, v in
        init_mlp(np.random.RandomState(0), sizes=(16, 8, 4)).items())
    plan = OV.plan_overlap(params, 1 << 20, record=False)
    sync_fn = OV.make_bucket_sync_fn(axis="ps", world=w)
    wrapped = OV.wrap_loss(mlp_loss_fn, plan, sync_fn)
    batch = _batch(2, n=8 * w)

    def body(b):
        raw = jax.grad(mlp_loss_fn)(params, b)
        summed_ref = jax.tree.map(
            lambda g: jax.lax.psum(g, "ps"), raw)
        summed_hook = jax.grad(wrapped)(params, b)
        return summed_ref, summed_hook

    f = jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("ps"),
                              out_specs=P(), check_vma=False))
    ref, hook = f({k: jnp.asarray(v) for k, v in batch.items()})
    for n in ref:
        np.testing.assert_allclose(np.asarray(hook[n]), np.asarray(ref[n]),
                                   rtol=1e-5, atol=1e-6, err_msg=n)


# -- plan construction / auto-tuner / instrumentation -----------------------


def test_plan_overlap_buckets_cover_all_params_once():
    from collections import OrderedDict
    params = OrderedDict(
        (f"p{i}", np.zeros((100 * (i + 1),), np.float32)) for i in range(9))
    plan = OV.plan_overlap(params, 1200, record=False)
    names = [n for b in plan.buckets for n in b]
    assert sorted(names) == sorted(params)
    assert plan.n_buckets > 1
    assert plan.total_bytes == sum(v.nbytes for v in params.values())


def test_auto_bucket_bytes_bounds_and_determinism(tmp_path, monkeypatch):
    lo = OV.auto_bucket_bytes(10, world=8)
    hi = OV.auto_bucket_bytes(100 << 30, world=8)
    assert OV.MIN_BUCKET_BYTES <= lo <= OV.MAX_BUCKET_BYTES
    assert hi == OV.MAX_BUCKET_BYTES
    mid = OV.auto_bucket_bytes(256 << 20, world=8)
    assert mid == OV.auto_bucket_bytes(256 << 20, world=8)
    # A function of its arguments: the values it has always returned,
    assert (lo, mid, hi) == (5733000, 16 << 20, 32 << 20)
    assert OV.auto_bucket_bytes(1_600_000_000, world=4) == 32 << 20
    # and the same ones from another working directory, with no file opened.
    import builtins

    def no_open(*a, **k):
        raise AssertionError(f"auto_bucket_bytes opened a file: {a}")
    with monkeypatch.context() as m:
        m.chdir(tmp_path)
        m.setattr(builtins, "open", no_open)
        elsewhere = (OV.auto_bucket_bytes(10, world=8),
                     OV.auto_bucket_bytes(256 << 20, world=8))
    assert elsewhere == (lo, mid)


def test_constructing_overlap_optimizer_records_schedule(mesh8):
    clear_overlap_schedules()
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8,
              sync_mode="overlap", bucket_mb=0)
    recs = overlap_schedules()
    assert len(recs) == 1
    rec = recs[0]
    assert rec["auto_tuned"] is True
    assert rec["n_buckets"] == opt.overlap_plan.n_buckets
    assert rec["reducer"] == "rs_ag"
    assert rec["world"] == world_size(mesh8)


# -- refusal surface ---------------------------------------------------------


def test_overlap_refuses_error_feedback(mesh8):
    params = init_mlp(np.random.RandomState(0), sizes=(16, 8, 4))
    with pytest.raises(ValueError, match="error_feedback"):
        SGD(list(params.items()), lr=0.1, mesh=mesh8, code="topk",
            error_feedback=True, sync_mode="overlap")


def test_overlap_refuses_lossy_codec_with_skip_nonfinite(mesh8):
    params = init_mlp(np.random.RandomState(0), sizes=(16, 8, 4))
    with pytest.raises(ValueError, match="skip_nonfinite"):
        SGD(list(params.items()), lr=0.1, mesh=mesh8, code="blockq",
            skip_nonfinite=True, sync_mode="overlap")


def test_overlap_refuses_accum_steps(mesh8):
    params = init_mlp(np.random.RandomState(0), sizes=(16, 8, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8,
              sync_mode="overlap")
    with pytest.raises(ValueError, match="accum_steps"):
        opt.compile_step(mlp_loss_fn, accum_steps=2)


def test_unknown_sync_mode_and_reducer_rejected(mesh8):
    params = init_mlp(np.random.RandomState(0), sizes=(16, 8, 4))
    with pytest.raises(ValueError, match="sync_mode"):
        SGD(list(params.items()), lr=0.1, mesh=mesh8, sync_mode="magic")
    with pytest.raises(ValueError, match="overlap_reducer"):
        SGD(list(params.items()), lr=0.1, mesh=mesh8,
            overlap_reducer="alltoall")


# -- no-recompile regression -------------------------------------------------


def _compile_counters():
    """Register (once) a process-wide jax.monitoring listener counting
    compilation-cache traffic; returns the live counter dict."""
    if not hasattr(_compile_counters, "counts"):
        counts = {}

        def listener(name, *a, **kw):
            counts[name] = counts.get(name, 0) + 1

        jax.monitoring.register_event_listener(listener)
        _compile_counters.counts = counts
    return _compile_counters.counts


@pytest.mark.parametrize("kw", [dict(), dict(sync_mode="overlap")],
                         ids=["bucketed", "overlap"])
def test_compile_step_twice_hits_jit_cache(mesh8, kw):
    """Rebinding the SAME loss on identical shapes/specs must not trigger a
    fresh XLA compile — the program round-trips through the compilation
    cache (conftest enables the persistent cache).  Guards the
    donate_argnums/step construction against nondeterminism that would
    change the HLO fingerprint between builds."""
    counts = _compile_counters()
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8, **kw)
    opt.compile_step(mlp_loss_fn)
    opt.step(_batch(0))  # traces + compiles (or hits cache from prior runs)
    hits_key = "/jax/compilation_cache/cache_hits"
    miss_key = "/jax/compilation_cache/cache_misses"
    hits_before = counts.get(hits_key, 0)
    misses_before = counts.get(miss_key, 0)
    opt.compile_step(mlp_loss_fn)  # identical shapes/specs
    opt.step(_batch(1))
    assert counts.get(miss_key, 0) == misses_before, (
        "recompiled on identical shapes/specs: "
        f"{counts.get(miss_key, 0) - misses_before} new cache misses")
    # Guard against a vacuous pass (listener silent / key renamed): the
    # rebuild must have produced at least one observed cache HIT.
    assert counts.get(hits_key, 0) > hits_before, (
        "no compilation-cache traffic observed for the rebuilt step — "
        "the cache-miss assertion above proved nothing")


# -- fused sync encode (ISSUE 16: the MFU residual) --------------------------


def test_fused_identity_is_bitwise_equal(mesh8):
    """``fused_encode=True`` with no codec returns the SAME `_sync_identity`
    closure — the identity path is already one fused flat sum per bucket,
    so the knob is definitionally bitwise-equal there."""
    base = _train(mesh8, momentum=0.9, sync_mode="overlap")
    fused = _train(mesh8, momentum=0.9, sync_mode="overlap",
                   fused_encode=True)
    np.testing.assert_array_equal(base[0], fused[0])
    for n in base[1]:
        np.testing.assert_array_equal(base[1][n], fused[1][n], err_msg=n)


def test_fused_blockq_matches_explicit_stage_programs(mesh8):
    """Parity contract of `_sync_blockq_fused`: bitwise-identical to the
    same math run as SEPARATE host-boundary programs — quantize each
    rank's bucket in its own program, stack the codes in rank order (what
    the in-graph all-gather produces), dequant-sum as another program.
    Guards the fused twin against any refactor that changes the block
    partition, the pad, or the reduction order."""
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu.ops import pallas_kernels as pk
    from pytorch_ps_mpi_tpu.ops.codecs import BlockQuantizeCodec

    w = world_size(mesh8)
    codec = BlockQuantizeCodec(impl="ref")
    rng = np.random.RandomState(3)
    shapes = [(40, 7), (111,), (5, 3, 2)]
    base = OrderedDict(
        ("g%d" % i, jnp.asarray(rng.randn(*s).astype(np.float32)))
        for i, s in enumerate(shapes))
    names = list(base)

    def body(scale):
        # Rank-distinct cotangents: leaf * (rank + 1).
        cot = OrderedDict((n, base[n] * scale[0]) for n in names)
        q, scales, _ = OV._blockq_bucket_encode(cot, codec)
        return (OV._sync_blockq_fused(cot, "ps", codec),
                q[None], scales[None])

    ranks = np.arange(1, w + 1, dtype=np.float32)
    fused, fused_q, fused_s = jax.jit(jax.shard_map(
        body, mesh=mesh8, in_specs=P("ps"),
        out_specs=(P(), P("ps"), P("ps")), check_vma=False))(ranks)

    flat_len = sum(int(v.size) for v in base.values())
    rows = codec._rows_for(flat_len)
    qs, ss = [], []
    for rank in range(w):
        flat = jnp.concatenate([(base[n] * float(rank + 1)).reshape(-1)
                                for n in names])
        x2d, _ = pk.pad_to_blocks(flat, rows)
        q, s = pk.block_quantize(x2d, bits=codec.bits, block_rows=rows,
                                 impl="ref")
        qs.append(q)
        ss.append(s)
    # The codes are the contract: bit for bit.
    np.testing.assert_array_equal(np.asarray(fused_q), np.asarray(qs))
    np.testing.assert_array_equal(np.asarray(fused_s), np.asarray(ss))
    out2d = pk.block_dequant_sum(jnp.stack(qs), jnp.stack(ss),
                                 block_rows=rows, impl="ref")
    summed = np.asarray(out2d).reshape(-1)[:flat_len]
    # The f32 dequant-sum may contract multiply-adds (FMA) in one program
    # and not in the other: a few ulp of the largest term, not bitwise.
    atol = 4 * np.finfo(np.float32).eps * float(np.abs(summed).max())
    off = 0
    for n in names:
        sz = int(base[n].size)
        ref = summed[off:off + sz].reshape(base[n].shape)
        np.testing.assert_allclose(np.asarray(fused[n]), ref, rtol=0,
                                   atol=atol, err_msg=n)
        off += sz


def test_fused_interpreter_matches_reference(mesh8):
    """``impl="interpret"`` runs the bucket's kernels under the Pallas
    interpreter, ``impl="ref"`` the jnp reference — the two programs must
    agree (same contract as the async fused encode in
    test_bucket_stream)."""
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu.ops.codecs import BlockQuantizeCodec

    w = world_size(mesh8)
    rng = np.random.RandomState(7)
    base = OrderedDict(
        [("w", jnp.asarray(rng.randn(33, 9).astype(np.float32))),
         ("b", jnp.asarray(rng.randn(129).astype(np.float32)))])

    def run(impl):
        sync = OV.make_bucket_sync_fn(axis="ps", world=w,
                                      codec=BlockQuantizeCodec(impl=impl),
                                      fused_encode=True)

        def body(scale):
            cot = OrderedDict((n, base[n] * scale[0]) for n in base)
            return sync(cot)

        ranks = np.arange(1, w + 1, dtype=np.float32)
        return jax.jit(jax.shard_map(body, mesh=mesh8, in_specs=P("ps"),
                                     out_specs=P(),
                                     check_vma=False))(ranks)

    ref, interp = run("ref"), run("interpret")
    for n in ref:
        big = float(np.abs(np.asarray(ref[n])).max())
        np.testing.assert_allclose(
            np.asarray(ref[n]), np.asarray(interp[n]), rtol=0,
            atol=4 * np.finfo(np.float32).eps * big, err_msg=n)


def test_fused_refuses_non_blockq_codec():
    """A knob that silently fell back to the per-leaf path would claim a
    fusion it never ran — every non-blockq codec refuses loudly."""
    from pytorch_ps_mpi_tpu.ops.codecs import get_codec

    for code in ("bf16", "sign", "topk"):
        with pytest.raises(ValueError, match="fused_encode supports"):
            OV.make_bucket_sync_fn(axis="ps", world=2,
                                   codec=get_codec(code, "cpu"),
                                   fused_encode=True)


def test_fused_encode_requires_overlap_mode(mesh8):
    """Off the overlap path there is no bucket hook to fuse into — the
    ctor refuses instead of leaving the flag silently inert."""
    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    with pytest.raises(ValueError, match="fused_encode requires"):
        SGD(list(params.items()), lr=0.1, mesh=mesh8, code="blockq",
            fused_encode=True)


def test_fused_sync_encodes_counter_counts_steps(mesh8):
    """`fault_stats["fused_sync_encodes"]` counts DISPATCHED steps whose
    program compiled the fused twin in — once per step, not per bucket —
    and stays zero on the unfused path."""
    losses, _ = _train(mesh8, code="blockq", sync_mode="overlap",
                       fused_encode=True)
    assert np.all(np.isfinite(losses))

    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8, code="blockq",
              sync_mode="overlap", fused_encode=True)
    opt.compile_step(mlp_loss_fn)
    for i in range(3):
        opt.step(_batch(i))
    assert opt.fault_stats["fused_sync_encodes"] == 3

    unfused = SGD(list(params.items()), lr=0.1, mesh=mesh8, code="blockq",
                  sync_mode="overlap")
    unfused.compile_step(mlp_loss_fn)
    unfused.step(_batch(0))
    assert unfused.fault_stats["fused_sync_encodes"] == 0
