"""Bucket-streamed async gradients (ISSUE 15, protocol v11).

Oracles mirror the tentpole's contracts:

* the degenerate single-bucket stream — and any multi-bucket plan —
  trains BITWISE identically to the whole-tree path (assembly restores
  canonical param order, the decode/apply math never changes);
* the fused per-bucket grad+encode step equals the host-boundary
  encode (and, for the Pallas-backed blockq codec, the interpreter-mode
  kernel equals the jnp reference) — compression error is a codec
  property, never a scheduling one;
* flow control meters GRADIENTS, not frames: one `begin_data_parts`
  credit covers the stream, a closed gate parks the whole gradient as
  one entry (flushed in order, shed oldest-first as a unit, sentinel-
  checked against the parked copies);
* partial assemblies (a bucket shed / lost mid-gradient) retire
  COUNTED — never half-applied — and interleaved streams from many
  ranks assemble rank-distinct;
* the aggregator's per-bucket pre-reduce forwards ONE assembled AGGR
  gradient per fill (`agg_frames` counts gradients, not frames) and
  the per-bucket statistics compose bitwise to the whole-tree reduce;
* steady state never retraces (one jitted step covers every bucket),
  every new counter renders, and the CLI refuses the knobs anywhere
  they would be silently inert.
"""

import socket
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu.async_ps import dataset_batch_fn, make_worker_step
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import (AsyncPSWorker,
                                                AsyncSGDServer)
from pytorch_ps_mpi_tpu.ops.codecs import get_codec
from pytorch_ps_mpi_tpu.parallel import overlap as OV
from pytorch_ps_mpi_tpu.parallel.overlap import (make_async_bucket_step,
                                                 merge_buckets,
                                                 plan_overlap, split_tree)
from pytorch_ps_mpi_tpu.transport import Session, recv_frame
from pytorch_ps_mpi_tpu.utils.timing import format_fault_stats

SIZES = (32, 64, 8)


def _teacher():
    rng = np.random.RandomState(7)
    x = rng.randn(256, SIZES[0]).astype(np.float32)
    w = rng.randn(SIZES[0], SIZES[-1]).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _params(seed=0):
    return init_mlp(np.random.RandomState(seed), sizes=SIZES)


def _batch(seed=1):
    x, y = _teacher()
    return {"x": x[:64], "y": y[:64]}


def _server(quota=1, seed=0, **kw):
    srv = AsyncSGDServer(list(_params(seed).items()), lr=0.05,
                         momentum=0.5, quota=quota, **kw)
    srv.compile_step(mlp_loss_fn)
    return srv


def _serve(srv, steps, out, **kw):
    def go():
        try:
            out["hist"] = srv.serve(steps=steps, idle_timeout=60.0, **kw)
        except BaseException as exc:  # noqa: BLE001 - asserted by tests
            out["error"] = exc

    t = threading.Thread(target=go, daemon=True, name="bucket-serve")
    t.start()
    return t


def _host_tree(tree):
    return jax.tree.map(np.asarray, jax.device_get(tree))


# ---------------------------------------------------------------------------
# plan / split / merge
# ---------------------------------------------------------------------------

def test_split_merge_roundtrip_covers_every_param_once():
    params = _params()
    plan = plan_overlap(params, 4096)
    assert plan.n_buckets > 1
    subs = split_tree(params, plan)
    names = [n for sub in subs for n in sub]
    assert sorted(names) == sorted(params)
    merged = merge_buckets(subs, list(params))
    assert list(merged) == list(params)
    assert all(merged[n] is params[n] for n in params)


def test_plan_overlap_buckets_cover_all_params_once():
    from collections import OrderedDict
    params = OrderedDict(
        (f"p{i}", np.zeros((100 * (i + 1),), np.float32)) for i in range(9))
    plan = plan_overlap(params, 1200)
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


def test_solo_plan_gives_large_leaves_their_own_bucket():
    from pytorch_ps_mpi_tpu.parallel.collectives import _plan_buckets

    leaves = [np.zeros(64 << 10, np.float32),   # 256 KB: solo
              np.zeros(256, np.float32), np.zeros(256, np.float32)]
    plan = _plan_buckets(leaves, bucket_bytes=4 << 20,
                         solo_bytes=256 << 10)
    assert [0] in plan                       # the big leaf stands alone
    assert sorted(sum(plan, [])) == [0, 1, 2]
    # solo_bytes=0 keeps the legacy pack-everything plan.
    legacy = _plan_buckets(leaves, bucket_bytes=4 << 20)
    assert legacy == [[0, 1, 2]]


def test_solo_psum_bitwise_matches_packed_psum(mesh8):
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel import collectives as C
    from pytorch_ps_mpi_tpu.parallel.mesh import replicated

    grads = {n: jax.device_put(jnp.asarray(v), replicated(mesh8))
             for n, v in _params().items()}
    run = lambda solo: jax.jit(jax.shard_map(
        lambda g: C.psum_tree_bucketed(g, "ps", bucket_bytes=4096,
                                       solo_bytes=solo),
        mesh=mesh8, in_specs=P(), out_specs=P(), check_vma=False))(grads)
    solo, packed = run(None), run(0)
    for n in grads:
        assert np.array_equal(np.asarray(solo[n]), np.asarray(packed[n]))


# ---------------------------------------------------------------------------
# the bucketed step: fused == host encode == whole-tree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["identity", "blockq"])
def test_fused_encode_matches_host_encode(codec):
    params = _params()
    code = get_codec(codec, "cpu")
    plan = plan_overlap(params, 4096)
    fused = make_async_bucket_step(mlp_loss_fn, code, plan, fused=True)
    host = make_async_bucket_step(mlp_loss_fn, code, plan, fused=False)
    batch = _batch()
    lf, bf = fused(params, batch)
    lh, bh = host(params, batch)
    assert np.array_equal(np.asarray(lf), np.asarray(lh))
    assert len(bf) == len(bh) == plan.n_buckets
    for sf, sh in zip(bf, bh):
        assert list(sf) == list(sh)
        for n in sf:
            fl = jax.tree_util.tree_leaves(sf[n])
            hl = jax.tree_util.tree_leaves(sh[n])
            for a, b in zip(fl, hl):
                assert np.array_equal(np.asarray(a), np.asarray(b))


def test_single_bucket_step_equals_whole_tree_step():
    params = _params()
    code = get_codec(None, "cpu")
    plan = plan_overlap(params, 1 << 30)
    assert plan.n_buckets == 1
    bucketed = make_async_bucket_step(mlp_loss_fn, code, plan, fused=True)
    whole = make_worker_step(mlp_loss_fn, code)
    batch = _batch()
    lb, buckets = bucketed(params, batch)
    lw, codes = whole(params, batch)
    assert np.array_equal(np.asarray(lb), np.asarray(lw))
    (sub,) = buckets
    assert list(sub) == list(codes)
    for n in codes:
        assert np.array_equal(np.asarray(sub[n]), np.asarray(codes[n]))


def test_pallas_blockq_interpreter_encode_matches_reference():
    """The fused-encode kernel half under the Pallas interpreter equals
    the jnp reference — the encode analogue of the cast_sum parity the
    decode half already carries."""
    from pytorch_ps_mpi_tpu.ops import pallas_kernels as pk

    rng = np.random.RandomState(0)
    x2d, _ = pk.pad_to_blocks(jnp.asarray(
        rng.randn(3000).astype(np.float32)), 8)
    qi, si = pk.block_quantize_tpu(x2d, bits=8, block_rows=8,
                                   interpret=True)
    qr, sr = pk.block_quantize_ref(x2d, bits=8, block_rows=8)
    assert np.array_equal(np.asarray(qi), np.asarray(qr))
    assert np.allclose(np.asarray(si), np.asarray(sr), rtol=1e-6)


def test_bucketed_step_steady_state_never_retraces():
    params = _params()
    plan = plan_overlap(params, 4096)
    fn = make_async_bucket_step(mlp_loss_fn, get_codec(None, "cpu"), plan,
                                fused=True)
    if not hasattr(fn, "_cache_size"):
        pytest.skip("jit cache introspection unavailable")
    for i in range(3):
        jax.block_until_ready(fn(params, _batch(i))[0])
    assert fn._cache_size() == 1


# ---------------------------------------------------------------------------
# e2e: deterministic drives, bitwise parity with the whole-tree path
# ---------------------------------------------------------------------------

def _drive(bucket_bytes, steps=3):
    """Deterministic lock-step drive: push one gradient, wait for the
    version to advance, repeat — removes the async race so two runs see
    the identical gradient sequence and final params compare bitwise."""
    srv = _server(quota=1)
    out: dict = {}
    t = _serve(srv, steps, out)
    kw = {} if bucket_bytes is None else dict(bucket_bytes=bucket_bytes)
    w = AsyncPSWorker("127.0.0.1", srv.address[1], **kw)
    version, params = w.pull()
    plan = (plan_overlap(params, bucket_bytes)
            if bucket_bytes is not None else None)
    fn = (make_async_bucket_step(mlp_loss_fn, w.code, plan, fused=True)
          if plan is not None else make_worker_step(mlp_loss_fn, w.code))
    batch = _batch()
    done = False
    while not done:
        if plan is not None:
            loss, buckets = fn(params, batch)
            host = [_host_tree(sub) for sub in buckets]
            w.push_buckets(iter(host), plan.n_buckets, version,
                           float(loss))
        else:
            loss, codes = fn(params, batch)
            w.push(_host_tree(codes), version, float(loss))
        while True:
            pulled = w.pull(force=True)
            if pulled is None:
                done = True
                break
            v2, p2 = pulled
            if v2 > version:
                version, params = v2, p2
                break
    w.close()
    t.join(60)
    assert "error" not in out, out
    return out["hist"], params


def test_multi_bucket_stream_trains_bitwise_like_whole_tree():
    hist_w, params_w = _drive(None)
    hist_b, params_b = _drive(4096)
    assert hist_b["losses"] == hist_w["losses"]
    for n in params_w:
        assert np.array_equal(params_w[n], params_b[n])
    fs = hist_b["fault_stats"]
    assert fs["buckets_filled"] > 0
    assert fs["bucket_partial_timeouts"] == 0


def test_one_bucket_stream_is_the_whole_tree_path_bitwise():
    hist_w, params_w = _drive(None)
    hist_1, params_1 = _drive(1 << 30)  # degenerate single-bucket plan
    assert hist_1["losses"] == hist_w["losses"]
    for n in params_w:
        assert np.array_equal(params_w[n], params_1[n])
    # A single-bucket plan rides the (0, 1) header — the literal
    # whole-tree frame, so assembly (and its counters) never engages.
    assert hist_1["fault_stats"]["buckets_filled"] == 0


def test_partial_bucket_times_out_without_double_apply():
    """A gradient whose last bucket never arrives must retire COUNTED
    when the rank's next stream completes — and contribute nothing (the
    served update consumes exactly the complete gradient once)."""
    srv = _server(quota=1)
    out: dict = {}
    t = _serve(srv, 1, out)
    w = AsyncPSWorker("127.0.0.1", srv.address[1], bucket_bytes=4096)
    version, params = w.pull()
    plan = plan_overlap(params, 4096)
    fn = make_async_bucket_step(mlp_loss_fn, w.code, plan, fused=True)
    loss, buckets = fn(params, _batch())
    host = [_host_tree(sub) for sub in buckets]
    # Withhold the final bucket of seq 0 (the generator just runs dry).
    w.push_buckets(iter(host[:-1]), plan.n_buckets, version, float(loss))
    # Seq 1 streams completely: its assembly completes, retires seq 0's
    # partial, and satisfies the fill.
    w.push_buckets(iter(host), plan.n_buckets, version, float(loss))
    t.join(60)
    w.close()
    assert "error" not in out, out
    hist = out["hist"]
    fs = hist["fault_stats"]
    assert hist["grads_consumed"] == 1
    assert fs["bucket_partial_timeouts"] >= 1
    assert fs["buckets_filled"] == plan.n_buckets


def test_interleaved_rank_streams_fill_rank_distinct():
    """Bucket frames interleaved across two ranks assemble per (rank,
    seq): one fill consumes one gradient from EACH rank, never a
    chimera."""
    srv = _server(quota=2)
    out: dict = {}
    t = _serve(srv, 1, out)
    ws = [AsyncPSWorker("127.0.0.1", srv.address[1], bucket_bytes=4096)
          for _ in range(2)]
    pulls = [w.pull() for w in ws]
    plan = plan_overlap(pulls[0][1], 4096)
    fn = make_async_bucket_step(mlp_loss_fn, ws[0].code, plan, fused=True)
    hosts = []
    for i, w in enumerate(ws):
        loss, buckets = fn(pulls[i][1], _batch(i))
        hosts.append((float(loss), [_host_tree(s) for s in buckets]))
    # Interleave at the FRAME level: each worker's stream yields one
    # bucket, then blocks on an event until the OTHER worker's same-
    # index bucket went out — so the server's arrival order is strictly
    # w0.b0, w1.b0, w0.b1, w1.b1, ... across the two sockets.
    turn = threading.Semaphore(1)
    other = threading.Semaphore(0)

    def stream(host, mine, theirs):
        for sub in host:
            mine.acquire()
            yield sub
            theirs.release()

    ts = []
    for i, w in enumerate(ws):
        loss, host = hosts[i]
        mine, theirs = (turn, other) if i == 0 else (other, turn)

        def go(w=w, host=host, loss=loss, i=i, mine=mine, theirs=theirs):
            w.push_buckets(stream(host, mine, theirs),
                           plan.n_buckets, pulls[i][0], loss)

        th = threading.Thread(target=go, daemon=True)
        th.start()
        ts.append(th)
    for th in ts:
        th.join(30)
    t.join(60)
    for w in ws:
        w.close()
    assert "error" not in out, out
    hist = out["hist"]
    assert sorted(hist["contributors"][0]) == [0, 1]
    assert hist["fault_stats"]["buckets_filled"] == 2 * plan.n_buckets


def test_duplicate_bucket_frame_drops_without_decode():
    srv = _server(quota=1)
    out: dict = {}
    t = _serve(srv, 2, out)
    w = AsyncPSWorker("127.0.0.1", srv.address[1], bucket_bytes=4096)
    version, params = w.pull()
    plan = plan_overlap(params, 4096)
    fn = make_async_bucket_step(mlp_loss_fn, w.code, plan, fused=True)
    loss, buckets = fn(params, _batch())
    host = [_host_tree(sub) for sub in buckets]
    w.push_buckets(iter(host), plan.n_buckets, version, float(loss))
    # Replay the SAME stream under the same seq: every frame is a
    # (seq, bucket) duplicate.
    w._push_seq -= 1
    w.push_buckets(iter(host), plan.n_buckets, version, float(loss))
    # A fresh seq completes the second update.
    w.push_buckets(iter(host), plan.n_buckets, version, float(loss))
    t.join(60)
    w.close()
    assert "error" not in out, out
    fs = out["hist"]["fault_stats"]
    assert fs["duplicate_dropped"] == plan.n_buckets
    assert fs["buckets_filled"] == 2 * plan.n_buckets


# ---------------------------------------------------------------------------
# the multipart credit gate
# ---------------------------------------------------------------------------

def _session_pair(**kw):
    a, b = socket.socketpair()
    a.settimeout(10.0)
    b.settimeout(10.0)
    return Session(a, **kw), a, b


def test_multipart_charges_one_credit_per_gradient():
    s, a, b = _session_pair()
    s.replenish(1)
    assert s.begin_data_parts()            # consumes THE credit
    s.send_data_part([b"GRAD", b"x" * 8])
    s.send_data_part([b"GRAD", b"y" * 8])  # continuation: no gate
    assert s.credits() == 0
    assert recv_frame(b) == b"GRAD" + b"x" * 8
    assert recv_frame(b) == b"GRAD" + b"y" * 8
    # Gate now closed: the next gradient stalls as a unit.
    assert not s.begin_data_parts()
    assert s.stats["credits_stalled"] == 1
    a.close()
    b.close()


def test_parked_multipart_flushes_in_order_and_sheds_as_a_unit():
    s, a, b = _session_pair(max_pending=1, sentinel=True)
    s.replenish(0)
    assert not s.begin_data_parts()
    s.park_data_parts([[b"GRAD", b"old0"], [b"GRAD", b"old1"]])
    assert not s.begin_data_parts()
    s.park_data_parts([[b"GRAD", b"new0"], [b"GRAD", b"new1"]])
    # max_pending=1: the OLDEST gradient (both its frames) shed.
    assert s.stats["shed_data_frames"] == 1
    assert s.pending_count() == 1
    s.replenish(2)
    assert recv_frame(b) == b"GRAD" + b"new0"
    assert recv_frame(b) == b"GRAD" + b"new1"
    assert s.stats["sentinel_checks"] == 1  # one entry, one check
    assert s.stats["sentinel_trips"] == 0
    a.close()
    b.close()


def test_parked_multipart_is_copy_on_park():
    """The caller may reuse every buffer it handed in the moment
    park_data_parts returns: the flush must send the parked copies."""
    s, a, b = _session_pair(sentinel=True)
    s.replenish(0)
    payload = bytearray(b"bucket-bytes")
    assert not s.begin_data_parts()
    s.park_data_parts([[b"GRAD", payload]])
    payload[:6] = b"mutate"            # legal: caller kept ownership
    s.replenish(1)
    assert recv_frame(b) == b"GRAD" + b"bucket-bytes"
    assert s.stats["sentinel_trips"] == 0
    a.close()
    b.close()


# ---------------------------------------------------------------------------
# aggregator: per-bucket pre-reduce, one assembled forward per fill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("aggregate", ["mean", "trimmed_mean"])
def test_aggregator_bucketed_forward_counts_gradients(aggregate):
    from pytorch_ps_mpi_tpu.shard import LocalAggregator

    steps = 4
    quorum = dict(quorum=3, fill_deadline=0.2) \
        if aggregate == "trimmed_mean" else {}
    root = _server(quota=1)
    out: dict = {}
    rt = _serve(root, steps, out)
    agg = LocalAggregator(
        list(_params().items()), group=0,
        upstream=[("127.0.0.1", root.address[1])], group_size=3,
        bucket_bytes=4096, aggregate=aggregate, **quorum)
    agg.compile_reduce()
    if aggregate == "mean":
        assert agg._reduce_bucket_fn is not None  # streamable policy
    ah: dict = {}

    def serve_group():
        try:
            ah["hist"] = agg.serve_group(idle_timeout=60.0)
        except BaseException as exc:  # noqa: BLE001
            ah["error"] = exc

    at = threading.Thread(target=serve_group, daemon=True)
    at.start()
    x, y = _teacher()
    results: dict = {}
    ts = []
    for i in range(3):
        def go(i=i):
            w = AsyncPSWorker("127.0.0.1", agg.address[1])
            results[i] = w.run(mlp_loss_fn,
                               dataset_batch_fn(x, y, 64, seed=i))
        th = threading.Thread(target=go, daemon=True)
        th.start()
        ts.append(th)
    rt.join(120)
    at.join(60)
    for th in ts:
        th.join(30)
    assert "error" not in out, out
    assert "error" not in ah, ah
    hist = out["hist"]
    fs = hist["fault_stats"]
    assert len(hist["losses"]) == steps
    assert all(np.isfinite(hist["losses"]))
    # One ASSEMBLED forward per fill: agg_frames counts gradients,
    # never the bucket frames they streamed as.
    assert fs["agg_frames"] == hist["grads_consumed"]
    assert fs["buckets_filled"] >= fs["agg_frames"] * 2
    assert fs["bucket_partial_timeouts"] == 0


def test_aggregator_per_bucket_reduce_matches_whole_tree():
    """The coordinate-wise per-bucket programs compose bitwise to the
    whole-tree reduce: split(stacked) -> reduce each -> merge equals
    reduce(stacked)."""
    from pytorch_ps_mpi_tpu.shard import LocalAggregator

    root = _server(quota=1)
    out: dict = {}
    rt = _serve(root, 1, out)
    agg = LocalAggregator(
        list(_params().items()), group=0,
        upstream=[("127.0.0.1", root.address[1])], group_size=2,
        bucket_bytes=4096)
    agg.compile_reduce()
    assert agg._reduce_bucket_fn is not None
    code = agg.code
    rng = np.random.RandomState(3)
    stacks = {n: np.stack([rng.randn(*np.shape(v)).astype(np.float32)
                           for _ in range(2)])
              for n, v in _params().items()}
    w = jnp.asarray(np.asarray([1.0, 0.5], np.float32))
    whole = agg._reduce_fn(stacks, w, jnp.float32(float("nan")))[0]
    subs = split_tree(stacks, agg._bucket_plan)
    merged = merge_buckets(
        [agg._reduce_bucket_fn(sub, w) for sub in subs], list(stacks))
    for n in whole:
        wl = jax.tree_util.tree_leaves(whole[n])
        ml = jax.tree_util.tree_leaves(merged[n])
        for a, b in zip(wl, ml):
            assert np.array_equal(np.asarray(a), np.asarray(b))
    # Unblock the serving root and tear down.
    worker = AsyncPSWorker("127.0.0.1", root.address[1])
    worker.run(mlp_loss_fn,
               dataset_batch_fn(*_teacher(), 64, seed=0), max_iters=4)
    rt.join(60)
    agg.close()


def test_aggregator_bucketing_refuses_sharded_root():
    from pytorch_ps_mpi_tpu.shard import LocalAggregator

    with pytest.raises(ValueError, match="SINGLE root"):
        LocalAggregator(list(_params().items()), group=0,
                        upstream=[("h", 1), ("h", 2)], group_size=2,
                        bucket_bytes=4096)


# ---------------------------------------------------------------------------
# counters, validation, refusals
# ---------------------------------------------------------------------------

def test_new_counters_render_and_key_parity():
    srv = _server()
    base = srv._base_fault_snapshot()
    for key in ("buckets_sent", "buckets_filled",
                "bucket_partial_timeouts", "fused_encodes"):
        assert key in base
        assert format_fault_stats({key: 3}) == f"{key}=3"
    srv.close()


def test_worker_ctor_refusals():
    with pytest.raises(ValueError, match="bucket_bytes"):
        AsyncPSWorker("h", 1, bucket_bytes=-1)
    with pytest.raises(ValueError, match="fused_encode"):
        AsyncPSWorker("h", 1, fused_encode=True)


def test_cli_refusal_matrix():
    from pytorch_ps_mpi_tpu import train

    base = ["--model", "mlp", "--steps", "1"]
    with pytest.raises(SystemExit, match="MULTIHOST worker"):
        train.main(base + ["--async-bucket-bytes", "0"])
    with pytest.raises(SystemExit, match="MULTIHOST worker"):
        train.main(base + ["--serve", "0", "--async-bucket-bytes", "0"])
    with pytest.raises(SystemExit, match="MULTIHOST worker"):
        train.main(base + ["--async-ps", "--async-bucket-bytes", "0"])
    with pytest.raises(SystemExit, match="needs --async-bucket-bytes"):
        train.main(base + ["--connect", "h:1", "--fused-encode"])
    with pytest.raises(SystemExit, match="must be >= 0"):
        train.main(base + ["--connect", "h:1",
                           "--async-bucket-bytes", "-3"])
    with pytest.raises(SystemExit, match="failover worker"):
        train.main(base + ["--connect", "h:1", "--fallback", "h:2",
                           "--async-bucket-bytes", "0"])
    with pytest.raises(SystemExit, match="shard router"):
        train.main(base + ["--connect", "h:1,h:2",
                           "--async-bucket-bytes", "0"])


def test_mismatched_bucket_plan_is_quarantined():
    """A bucket stream whose union is not the served tree must cost its
    connection (quarantined), never half-apply."""
    srv = _server(quota=1)
    out: dict = {}
    t = _serve(srv, 1, out)
    w = AsyncPSWorker("127.0.0.1", srv.address[1], bucket_bytes=4096)
    version, params = w.pull()
    plan = plan_overlap(params, 4096)
    fn = make_async_bucket_step(mlp_loss_fn, w.code, plan, fused=True)
    loss, buckets = fn(params, _batch())
    host = [_host_tree(sub) for sub in buckets]
    # Ship bucket 0's SUB-TREE twice under ids (0, 1): each frame is
    # structurally valid, the assembly completes, but the union is not
    # the served tree -> quarantined, conn dropped — never half-applied.
    assert plan.n_buckets == 2
    w.push_buckets(iter([host[0], host[0]]), plan.n_buckets, version,
                   float(loss))
    # A healthy worker completes the run.
    w2 = AsyncPSWorker("127.0.0.1", srv.address[1], bucket_bytes=4096)
    v2, p2 = w2.pull()
    loss2, buckets2 = fn(p2, _batch())
    w2.push_buckets(iter([_host_tree(s) for s in buckets2]),
                    plan.n_buckets, v2, float(loss2))
    t.join(60)
    w.close()
    w2.close()
    assert "error" not in out, out
    fs = out["hist"]["fault_stats"]
    assert fs["quarantined_frames"] >= 1
    assert fs["buckets_filled"] == plan.n_buckets


# ---------------------------------------------------------------------------
# drift coverage: the real modules stay tamper-evident
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_bucket_stream_chaos_endurance():
    """Real processes end to end: a --serve PS with quorum under --chaos
    straggler, two --connect workers streaming bucketed fused-encode
    gradients — the run completes with the streaming mode engaged and
    the straggler absorbed (completion is what is checked; loss parity
    under the straggler is not)."""
    import subprocess
    import sys as _sys

    from test_multihost_async import ChildProc, _reap_all

    from pytorch_ps_mpi_tpu.utils.faults import FaultPlan

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    chaos = FaultPlan(slow_rank=1,
                      slow_delay_s=0.1).to_json().replace("'", "\\'")
    base = ("'--model','mlp','--steps','16','--quota','2',"
            "'--batch-size','32','--n-examples','128'")

    server = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0',{base},'--quorum','1',"
         f"'--fill-deadline','0.2'])"])
    line = server.stdout.readline()
    assert line.startswith("serving on port "), line
    port = line.strip().rsplit(" ", 1)[1]

    workers = [ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--connect','127.0.0.1:{port}',{base},"
         f"'--async-bucket-bytes','4096','--fused-encode',"
         f"'--chaos','{chaos}'])"])
        for _ in range(2)]

    outs = _reap_all([server] + workers, timeout=300)
    (s_out, s_err) = outs[0]
    assert server.returncode == 0, f"server failed:\n{s_out}\n{s_err}"
    assert "done: 16 updates" in s_err, s_err
    for w, (w_out, w_err) in zip(workers, outs[1:]):
        assert w.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"
        assert "bucket streaming on (fused encode)" in w_err, w_err
        assert "gradients pushed" in w_err


def test_drift_checker_catches_bucket_field_tamper(tmp_path):
    """Strip the _BKT pack from the REAL `push` head: PSL304 must
    convict the v11 GRAD arity at the segmented send site."""
    import sys
    from pathlib import Path

    REPO = Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(REPO))
    from tools.pslint.core import load_corpus, run_checkers

    src = (REPO / "pytorch_ps_mpi_tpu" / "multihost_async.py").read_text()
    needle = 'head = (b"GRAD" + _BKT.pack(0, 1) + _U64.pack(seq)'
    assert src.count(needle) == 1  # the whole-tree push head
    tampered = src.replace(
        needle, 'head = (b"GRAD" + _U64.pack(seq)')
    path = tmp_path / "multihost_tampered.py"
    path.write_text(tampered)
    findings = run_checkers(load_corpus([path]))
    hits = [f for f in findings if f.checker == "PSL304"
            and "b'GRAD'" in f.message and "_BKT" in f.message]
    assert hits, findings
