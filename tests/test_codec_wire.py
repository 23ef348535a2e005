"""Compressed parameter wire (ISSUE 16, protocol v12): host-side wire
codecs, delta framing, and the codec-id byte end to end.

Oracles mirror the contract the compressed wire claims:

* the codecs are HOST-side (pure numpy — nothing dispatches jax from a
  conn thread), transform only f32 leaves, and round-trip with the
  documented precision: bf16 is the top 16 bits with round-to-nearest-
  even (specials preserved), int8 is per-block symmetric quantization;
* delta frames patch the reader's base tree BITWISE-identically to a
  full decode, fall back to a full snapshot when the diff is not worth
  it, and the server counts every hit/miss;
* each served version is encoded ONCE regardless of codec (the PR 13
  fanout cache now holds compressed segments), frames self-describe
  via the codec-id byte (readers need no configuration), and the
  optimizer state stays f32 server-side — only the wire is lossy;
* forced-full rules: `load_state_dict` clears the delta ring (a
  restored server never diffs across a restore), and a redialling
  subscriber presents `_UNVERSIONED` so failover always pays one full
  snapshot, never a corrupt patch — with zero version rewinds;
* replication carries the codec byte too: a standby stashes the blob
  and codec, and promotion decodes BEFORE `apply_optimizer`.
"""

import threading
import time

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.async_ps import dataset_batch_fn
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import (AsyncPSWorker,
                                                AsyncSGDServer)
from pytorch_ps_mpi_tpu.ops import codecs
from pytorch_ps_mpi_tpu.serve import Subscriber
from pytorch_ps_mpi_tpu.utils.timing import format_fault_stats


def _teacher(seed=7):
    rng = np.random.RandomState(seed)
    x = rng.randn(256, 16).astype(np.float32)
    w = rng.randn(16, 4).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)
    return x, y


def _server(quota=1, seed=0, **kw):
    params = init_mlp(np.random.RandomState(seed), sizes=(16, 32, 4))
    srv = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.5,
                         quota=quota, **kw)
    srv.compile_step(mlp_loss_fn)
    return srv


def _serve_bg(srv, steps, **kw):
    out = {}

    def body():
        try:
            out["hist"] = srv.serve(steps=steps, idle_timeout=60, **kw)
        except BaseException as exc:  # surfaced by the caller
            out["error"] = exc

    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t, out


def _tree(seed=0, shape=(64, 32)):
    rng = np.random.RandomState(seed)
    return {"w": rng.randn(*shape).astype(np.float32) * 3.0,
            "b": rng.randn(shape[1]).astype(np.float32),
            "step": np.int64(7)}


# ---------------------------------------------------------------------------
# the host-side codecs: precision, ratio, pass-through, idempotence
# ---------------------------------------------------------------------------

def test_wire_codec_id_table_and_refusal():
    assert codecs.WIRE_CODEC_IDS == {"identity": 0, "bf16": 1, "int8": 2}
    for name, cid in codecs.WIRE_CODEC_IDS.items():
        assert codecs.WIRE_CODEC_NAMES[cid] == name
        assert codecs.wire_codec_id(name) == cid
    with pytest.raises(ValueError, match="wire codec"):
        codecs.wire_codec_id("zstd")


def test_identity_encode_is_the_same_object():
    # The zero-copy contract: identity must NOT rebuild the tree — the
    # PARM fanout cache aliases the served leaves through it.
    tree = _tree()
    assert codecs.encode_wire_tree("identity", tree) is tree
    assert codecs.decode_wire_tree(0, tree) is tree


def test_bf16_halves_bytes_and_bounds_error():
    tree = _tree(shape=(128, 64))
    enc = codecs.encode_wire_tree("bf16", tree)
    raw = codecs.tree_raw_nbytes(tree)
    wire = codecs.tree_raw_nbytes(enc)
    # f32 leaves halve; the int64 leaf rides along unchanged.
    assert wire < 0.55 * raw
    dec = codecs.decode_wire_tree("bf16", enc)
    assert dec["step"] == tree["step"]
    # bf16 keeps 8 mantissa bits: relative error < 2^-8 away from zero.
    err = np.abs(dec["w"] - tree["w"]) / np.maximum(np.abs(tree["w"]),
                                                    1e-6)
    assert float(err.max()) < 2 ** -8
    # Exactly-representable values round-trip bitwise.
    exact = {"x": np.array([0.0, 1.0, -2.5, 0.15625], np.float32)}
    rt = codecs.decode_wire_tree(
        "bf16", codecs.encode_wire_tree("bf16", exact))
    np.testing.assert_array_equal(rt["x"], exact["x"])


def test_bf16_preserves_specials_and_is_idempotent():
    spec = {"x": np.array([np.inf, -np.inf, np.nan, 0.0, -0.0],
                          np.float32)}
    dec = codecs.decode_wire_tree(
        "bf16", codecs.encode_wire_tree("bf16", spec))
    assert np.isposinf(dec["x"][0]) and np.isneginf(dec["x"][1])
    assert np.isnan(dec["x"][2])
    np.testing.assert_array_equal(np.signbit(dec["x"]),
                                  np.signbit(spec["x"]))
    # Decoded values are exactly representable: a second trip through
    # the wire is bitwise stable (the lossy step happens exactly once).
    tree = _tree()
    once = codecs.decode_wire_tree(
        "bf16", codecs.encode_wire_tree("bf16", tree))
    twice = codecs.decode_wire_tree(
        "bf16", codecs.encode_wire_tree("bf16", once))
    for k in ("w", "b"):
        np.testing.assert_array_equal(once[k], twice[k])


def test_int8_quarters_bytes_and_bounds_error():
    tree = _tree(shape=(256, 64))
    enc = codecs.encode_wire_tree("int8", tree)
    raw = codecs.tree_raw_nbytes(tree)
    wire = codecs.tree_raw_nbytes(enc)
    assert wire < 0.35 * raw
    dec = codecs.decode_wire_tree("int8", enc)
    # Symmetric per-block quantization: error bounded by scale/2 =
    # blockmax/254 — assert against the coarse whole-tensor bound.
    bound = float(np.abs(tree["w"]).max()) / 254 + 1e-7
    assert float(np.abs(dec["w"] - tree["w"]).max()) <= bound
    # Small leaves must not INFLATE (the adaptive block size): a
    # 4-element bias still comes out smaller than f32.
    small = {"b": np.arange(4, dtype=np.float32)}
    assert (codecs.tree_raw_nbytes(
        codecs.encode_wire_tree("int8", small))
        <= codecs.tree_raw_nbytes(small))


def test_non_f32_leaves_pass_through_unchanged():
    tree = {"i": np.arange(6, dtype=np.int32),
            "h": np.arange(6, dtype=np.float16)}
    for name in ("bf16", "int8"):
        enc = codecs.encode_wire_tree(name, tree)
        assert enc["i"] is tree["i"] and enc["h"] is tree["h"]
        dec = codecs.decode_wire_tree(name, enc)
        np.testing.assert_array_equal(dec["i"], tree["i"])


# ---------------------------------------------------------------------------
# delta framing: bitwise patches, worth-it fallback
# ---------------------------------------------------------------------------

def test_delta_patch_is_bitwise_and_sublinear():
    base = _tree(shape=(128, 64))
    cur = {k: np.array(v, copy=True) for k, v in base.items()}
    # ~10% of one leaf changes — the bytes must track the CHANGE.
    rng = np.random.RandomState(1)
    idx = rng.choice(cur["w"].size, cur["w"].size // 10, replace=False)
    cur["w"].ravel()[idx] += 1.0
    delta, nbytes = codecs.diff_wire_delta(base, cur)
    patched = codecs.apply_wire_delta(base, delta)
    for k in ("w", "b"):
        np.testing.assert_array_equal(patched[k], cur[k])
    assert patched["step"] == cur["step"]
    assert nbytes < 0.35 * codecs.tree_raw_nbytes(cur)


def test_delta_full_fallback_on_shape_change():
    base = _tree()
    cur = dict(base)
    cur["w"] = np.zeros((3, 3), np.float32)  # repartitioned leaf
    delta, _ = codecs.diff_wire_delta(base, cur)
    patched = codecs.apply_wire_delta(base, delta)
    np.testing.assert_array_equal(patched["w"], cur["w"])


def test_delta_composes_with_wire_codec():
    # The server diffs POST-DECODE trees: what the reader holds after a
    # lossy full snapshot is exactly the base the next delta patches.
    base = codecs.decode_wire_tree(
        "bf16", codecs.encode_wire_tree("bf16", _tree(seed=2)))
    cur_raw = _tree(seed=3)
    cur = codecs.decode_wire_tree(
        "bf16", codecs.encode_wire_tree("bf16", cur_raw))
    delta, _ = codecs.diff_wire_delta(base, cur)
    patched = codecs.apply_wire_delta(base, delta)
    for k in ("w", "b"):
        np.testing.assert_array_equal(patched[k], cur[k])


# ---------------------------------------------------------------------------
# the wire end to end: PULL, SUBS, delta ring, forced-full rules
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ["bf16", "int8"])
def test_worker_trains_through_compressed_parm(codec):
    """A v12 worker needs NO codec configuration: the PARM frame byte
    names the transform, the pull decodes, training completes, and the
    byte sentinel stays armed across every compressed frame."""
    srv = _server(quota=1, wire_codec=codec)
    try:
        t, out = _serve_bg(srv, steps=8)
        x, y = _teacher()
        w = AsyncPSWorker("127.0.0.1", srv.address[1])
        w.run(mlp_loss_fn, dataset_batch_fn(x, y, 32))
        t.join(timeout=60)
        assert "error" not in out, out.get("error")
        fs = out["hist"]["fault_stats"]
        assert fs["parm_encodes"] >= 1
        assert fs["parm_bytes_raw"] > 0
        # Compressed wire: strictly below raw even with segment/meta
        # overhead on this tiny MLP (a 0.5x ratio needs payloads large
        # enough to amortize that overhead, which this one is not).
        assert fs["parm_bytes_wire"] < fs["parm_bytes_raw"]
        # The byte sentinel never tripped on a compressed frame (checks
        # may be 0 here: only a credit stall forces the parked-flush
        # path the sentinel instruments).
        assert fs["sentinel_trips"] == 0
        for n, p in srv.params.items():
            assert np.isfinite(np.asarray(p)).all(), n
        # Server-side state stayed f32: the wire is the only lossy hop.
        assert all(np.asarray(p).dtype == np.float32
                   for p in srv.params.values())
    finally:
        srv.close()


def test_identity_wire_bytes_equal_raw():
    srv = _server(quota=1)
    try:
        t, out = _serve_bg(srv, steps=4)
        x, y = _teacher()
        AsyncPSWorker("127.0.0.1", srv.address[1]).run(
            mlp_loss_fn, dataset_batch_fn(x, y, 32))
        t.join(timeout=60)
        assert "error" not in out
        fs = out["hist"]["fault_stats"]
        # Identity: wire bytes may exceed raw slightly (meta + segment
        # heads) but never compress — the counters expose the honest
        # baseline the benchmark divides by.
        assert fs["parm_bytes_wire"] >= fs["parm_bytes_raw"] > 0
    finally:
        srv.close()


def _publish(srv, n_changed=8):
    """Advance the served snapshot deterministically (the serve loop's
    rebind-never-mutate contract, driven by hand), touching only a few
    entries of the first leaf — a delta-shaped update (a 100%-changed
    tree rightly loses the worth-it comparison and ships full)."""
    served = {n: np.array(p, copy=True) for n, p in srv._served.items()}
    leaf = served[next(iter(served))]
    leaf.ravel()[:n_changed] += np.float32(0.25)
    srv._served = served
    srv._served_version += 1


@pytest.mark.parametrize("codec", ["identity", "bf16"])
def test_subscriber_delta_hits_patch_bitwise(codec):
    """SUBS polls inside the ring window get sparse deltas; the patched
    tree is BITWISE what a full decode of the served version yields."""
    srv = _server(quota=1, wire_codec=codec, delta_parm=True)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        srv._standby = False
        sub = Subscriber("127.0.0.1", srv.address[1])
        v0, params0 = sub.snapshot()
        for i in range(3):
            _publish(srv)
            version, params, changed = sub.poll()
            assert changed and version == v0 + i + 1
            assert srv.fault_stats["delta_hits"] == i + 1
        assert srv.fault_stats["delta_misses"] == 0
        # The reader's patched tree == an independent full decode of
        # what the server would put on the wire for this version.
        expect = codecs.decode_wire_tree(
            codec, codecs.encode_wire_tree(codec, srv._served))
        for n in expect:
            np.testing.assert_array_equal(params[n], expect[n])
        assert sub.fault_stats["version_rewinds"] == 0
        sub.close()
    finally:
        srv.close()


def test_delta_ring_miss_serves_full_snapshot():
    """A reader whose base version aged out of the ring gets a FULL
    frame (counted as a miss) — never a patch against a base the
    server no longer holds."""
    from pytorch_ps_mpi_tpu.multihost_async import _DELTA_RING

    srv = _server(quota=1, wire_codec="bf16", delta_parm=True)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        srv._standby = False
        stale = Subscriber("127.0.0.1", srv.address[1])
        fresh = Subscriber("127.0.0.1", srv.address[1])
        stale.snapshot()
        fresh.snapshot()
        # The fresh reader polls EVERY version, so each one is encoded
        # and enters the ring; the stale reader sits at version 0 until
        # the ring (depth _DELTA_RING) has evicted it.
        for _ in range(_DELTA_RING + 2):
            _publish(srv)
            version, params, changed = fresh.poll()
            assert changed
        with srv._parm_lock:
            assert 0 not in srv._delta_ring  # the stale base is gone
        hits_before = srv.fault_stats["delta_hits"]
        version, params, changed = stale.poll()
        assert changed and version == _DELTA_RING + 2
        assert srv.fault_stats["delta_misses"] >= 1
        assert srv.fault_stats["delta_hits"] == hits_before
        expect = codecs.decode_wire_tree(
            "bf16", codecs.encode_wire_tree("bf16", srv._served))
        for n in expect:
            np.testing.assert_array_equal(params[n], expect[n])
        # Back inside the window: the stale reader's NEXT poll hits.
        _publish(srv)
        version, params, changed = stale.poll()
        assert changed
        assert srv.fault_stats["delta_hits"] == hits_before + 1
        assert stale.fault_stats["version_rewinds"] == 0
        stale.close()
        fresh.close()
    finally:
        srv.close()


def test_load_state_dict_clears_the_delta_ring():
    """The server-side forced-full rule: a restore invalidates every
    ring base — the next conditional read is a full snapshot, never a
    diff across the restore boundary."""
    srv = _server(quota=1, wire_codec="bf16", delta_parm=True)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        srv._standby = False
        sub = Subscriber("127.0.0.1", srv.address[1])
        sub.snapshot()
        _publish(srv)
        sub.poll()
        assert srv.fault_stats["delta_hits"] == 1
        srv.load_state_dict(srv.state_dict())  # in-place "restore"
        assert srv._delta_ring == {} and srv._delta_cache == {}
        _publish(srv)
        version, params, changed = sub.poll()
        assert changed
        # The restore boundary forced a miss (full frame), and the
        # reader never rewound.
        assert srv.fault_stats["delta_misses"] >= 1
        assert sub.fault_stats["version_rewinds"] == 0
        sub.close()
    finally:
        srv.close()


def test_redial_presents_unversioned_and_pays_one_full_read():
    """The reader-side forced-full rule: after a redial the subscriber
    presents `_UNVERSIONED` — the server cannot (and must not) serve a
    delta against a base it cannot see."""
    srv = _server(quota=1, wire_codec="bf16", delta_parm=True)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        srv._standby = False
        sub = Subscriber("127.0.0.1", srv.address[1],
                         reconnect_retries=10, backoff_max=0.2)
        sub.snapshot()
        _publish(srv)
        sub.poll()
        hits_before = srv.fault_stats["delta_hits"]
        # Sever the link; the next poll redials and full-reads.
        sub._session.sock.close()
        _publish(srv)
        changed = False
        for _ in range(50):
            try:
                version, params, changed = sub.poll()
            except OSError:
                time.sleep(0.02)
                continue
            if changed:
                break
            time.sleep(0.02)
        assert changed
        # The recovery read was a FULL snapshot: `_UNVERSIONED` never
        # reaches the delta path at all (no hit — and no miss either:
        # misses count ring lookups, not unconditional reads).
        assert srv.fault_stats["delta_hits"] == hits_before
        assert srv.fault_stats["delta_misses"] == 0
        # ...and the link never rewound.
        assert sub.fault_stats["version_rewinds"] == 0
        expect = codecs.decode_wire_tree(
            "bf16", codecs.encode_wire_tree("bf16", srv._served))
        for n in expect:
            np.testing.assert_array_equal(params[n], expect[n])
        sub.close()
    finally:
        srv.close()


def test_delta_encode_is_cached_across_subscribers():
    """Two readers at the same base version cost ONE diff encode — the
    (have, version) delta cache is the read-path fanout cache."""
    srv = _server(quota=1, wire_codec="bf16", delta_parm=True)
    try:
        threading.Thread(target=srv._accept_loop, daemon=True).start()
        srv._standby = False
        subs = [Subscriber("127.0.0.1", srv.address[1])
                for _ in range(3)]
        for s in subs:
            s.snapshot()
        _publish(srv)
        for s in subs:
            version, params, changed = s.poll()
            assert changed
        assert srv.fault_stats["delta_hits"] == 3
        with srv._parm_lock:
            assert len(srv._delta_cache) == 1  # one diff, three sends
        for s in subs:
            s.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# replication: the codec byte rides REPL, promotion decodes first
# ---------------------------------------------------------------------------

def test_standby_promotion_decodes_compressed_replica():
    from pytorch_ps_mpi_tpu.shard import PSFleet
    from pytorch_ps_mpi_tpu.shard import ShardRouter

    params = init_mlp(np.random.RandomState(0), sizes=(16, 32, 4))
    fleet = PSFleet(list(params.items()), num_shards=2, quota=1,
                    optim="sgd", lr=0.05, momentum=0.5, replicas=1,
                    wire_codec="bf16")
    results = {}
    try:
        fleet.compile_step(mlp_loss_fn)
        x, y = _teacher()

        def go():
            try:
                r = ShardRouter(fleet.addresses)
                r.run(mlp_loss_fn, dataset_batch_fn(x, y, 64, seed=3))
                results["ok"] = True
            except BaseException as exc:
                results["error"] = exc

        t = threading.Thread(target=go, daemon=True)
        t.start()
        hist = fleet.serve(steps=6, idle_timeout=60.0)
        t.join(timeout=60)
        assert "error" not in results, results.get("error")
        fs = hist["fault_stats"]
        assert fs["repl_received"] == fs["repl_sent"] > 0
        # The standby stashed the codec id alongside the blob, and a
        # hand-driven promotion decodes the arrays back to f32 before
        # apply_optimizer — within bf16 tolerance of the primary.
        sb = fleet.standbys[0]
        assert sb._repl_codec == codecs.wire_codec_id("bf16")
        step = sb.promote_from_replica()
        assert step == sb.replica_step()
        primary = fleet.servers[0]
        for n, p in sb.params.items():
            ref = np.asarray(primary.params[n])
            got = np.asarray(p)
            assert got.dtype == np.float32
            tol = np.maximum(np.abs(ref), 1e-6) * 2 ** -7
            assert np.all(np.abs(got - ref) <= tol), n
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# observability + refusals
# ---------------------------------------------------------------------------

def test_v12_counters_render_and_stats_stay_keyed():
    srv = _server(quota=1, wire_codec="bf16", delta_parm=True)
    try:
        for key in ("parm_bytes_raw", "parm_bytes_wire", "delta_hits",
                    "delta_misses", "fused_sync_encodes"):
            assert key in srv.fault_stats, key
        srv.fault_stats["parm_bytes_raw"] = 2704
        srv.fault_stats["parm_bytes_wire"] = 1420
        srv.fault_stats["delta_hits"] = 3
        rendered = format_fault_stats(srv.fault_stats)
        assert "parm_bytes_wire=1420" in rendered
        assert "delta_hits=3" in rendered
    finally:
        srv.close()


def test_server_refuses_unknown_wire_codec():
    with pytest.raises(ValueError, match="wire codec"):
        _server(quota=1, wire_codec="zstd")


def test_cli_refuses_wire_codec_off_serve_roles():
    from pytorch_ps_mpi_tpu import train

    for extra in ([], ["--connect", "127.0.0.1:1"],
                  ["--subscribe", "127.0.0.1:1"]):
        with pytest.raises(SystemExit, match="wire-codec"):
            train.main(["--model", "mlp", "--steps", "1",
                        "--wire-codec", "bf16", *extra])
        with pytest.raises(SystemExit, match="delta-parm"):
            train.main(["--model", "mlp", "--steps", "1",
                        "--delta-parm", *extra])


# ---------------------------------------------------------------------------
# endurance: the real CLI roles over a compressed wire, with failover
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_cli_bf16_wire_failover_endurance():
    """Real processes end to end: a 2-shard bf16-wire fleet with
    --delta-parm and a mid-run shard kill, a subscriber polling through
    the failover (forced-full recovery, ZERO version rewinds), and a
    worker riding its reconnect backoff — everyone exits 0."""
    import subprocess
    import sys as _sys

    from test_multihost_async import ChildProc, _reap_all

    from pytorch_ps_mpi_tpu.utils.faults import FaultPlan

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    chaos = FaultPlan(kill_shard_at={1: 6}).to_json().replace("'", "\\'")
    base = ("'--model','mlp','--steps','16','--quota','1',"
            "'--batch-size','32','--n-examples','128'")

    server = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0','--shards','2',{base},"
         f"'--wire-codec','bf16','--delta-parm','--read-window','64',"
         f"'--checkpoint-every','1','--save','/tmp/_codec_wire_ckpt.psz',"
         f"'--chaos','{chaos}'])"])
    line = server.stdout.readline()
    assert line.startswith("serving on ports "), line
    ports = line.strip().split("ports ", 1)[1].split()
    assert len(ports) == 2
    connect = ",".join(f"127.0.0.1:{p}" for p in ports)

    worker = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--connect','{connect}',{base},"
         "'--reconnect-retries','100'])"])
    subscriber = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--subscribe','{connect}','--shards','2','--model','mlp',"
         "'--steps','600','--reconnect-retries','100'])"])

    outs = _reap_all([server, worker, subscriber], timeout=420)
    (s_out, s_err) = outs[0]
    assert server.returncode == 0, f"server failed:\n{s_out}\n{s_err}"
    assert "shard_restores=1" in s_err or "restored shard 1" in s_err, s_err
    (w_out, w_err) = outs[1]
    assert worker.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"
    assert "gradients pushed" in w_err
    (r_out, r_err) = outs[2]
    assert subscriber.returncode == 0, \
        f"subscriber failed:\n{r_out}\n{r_err}"
    assert r_out.startswith("subscribed at version"), r_out
    assert "subscriber done:" in r_err, r_err
    # format_fault_stats renders only non-clean counters: a rewind
    # would surface as version_rewinds=N in the stderr stats line.
    assert "version_rewinds" not in r_err, r_err
