"""Round-trip and fuzz tests for the native (C++) serialization pipeline.

Strategy mirrors the reference's test suite oracle — construct payloads,
push them through the protocol, compare against the original
(`/root/reference/test_comms.py:10-16`) — applied to the in-repo native
byte pipeline instead of MPI framing.
"""

import os

import numpy as np
import pytest

from pytorch_ps_mpi_tpu.native import lib
from pytorch_ps_mpi_tpu.native.serializer import (compress, decompress, dumps,
                                                  loads)


def roundtrip(data, **kw):
    frame = compress(data, **kw)
    raw = np.asarray(data).tobytes() if isinstance(data, np.ndarray) else bytes(data)
    out = decompress(frame)
    assert out.tobytes() == raw
    return frame


def test_lib_builds_and_loads():
    L = lib()
    assert L.ps_max_compressed(1000) >= 1000


def test_library_name_follows_the_source_bytes(tmp_path, monkeypatch):
    """The .so is named after a hash of the sources and the compile
    command, so a binary built from other sources (``_lib/`` is git-ignored
    and travels with a copied tree) can never be the one loaded: one
    changed source byte is a new name."""
    import shutil

    from pytorch_ps_mpi_tpu import native

    loaded = os.path.basename(native._build())
    assert loaded == f"libps_native-{native.build_id()}.so"
    srcs = []
    for src in native._SRCS:
        dst = tmp_path / os.path.basename(src)
        shutil.copy(src, dst)
        srcs.append(str(dst))
    monkeypatch.setattr(native, "_SRCS", srcs)
    assert native.build_id() == loaded[len("libps_native-"):-len(".so")]
    with open(srcs[0], "ab") as f:
        f.write(b"\n")
    assert os.path.basename(native.lib_path()) != loaded
    monkeypatch.setattr(native, "_CXX", [*native._CXX, "-DNDEBUG"])
    assert os.path.basename(native.lib_path()) != loaded


def test_missing_compiler_is_a_typed_error(tmp_path, monkeypatch):
    from pytorch_ps_mpi_tpu import native
    from pytorch_ps_mpi_tpu.errors import NativeToolchainError

    monkeypatch.setattr(native, "_LIBDIR", str(tmp_path / "_lib"))
    monkeypatch.setattr(native, "_CXX", ["no-such-compiler-xyz"])
    with pytest.raises(NativeToolchainError, match="g\\+\\+"):
        native._build()


def test_empty_and_tiny():
    roundtrip(b"")
    roundtrip(b"a")
    roundtrip(b"abc")


def test_highly_compressible():
    data = b"abcd" * 10_000
    frame = roundtrip(data)
    assert len(frame) < len(data) // 20  # LZ must crush periodic data


def test_incompressible_falls_back_to_store():
    rng = np.random.RandomState(0)
    data = rng.bytes(100_000)
    frame = roundtrip(data)
    # Store fallback: at most header overhead above the original.
    assert len(frame) <= len(data) + 32


def test_float_array_shuffle_helps():
    # Smoothly varying floats: high bytes are near-constant; shuffle exposes
    # the runs to LZ.
    x = np.linspace(0.0, 1.0, 50_000).astype(np.float32)
    framed = compress(x, level=1)
    stored = compress(x, level=0)
    assert len(framed) < len(stored) * 0.6
    out = decompress(framed).view(np.float32)
    np.testing.assert_array_equal(out, x)


def test_level0_is_store():
    x = np.arange(1000, dtype=np.int32)
    frame = compress(x, level=0)
    assert len(frame) == x.nbytes + 26  # header (incl. crc32) is 26 bytes
    np.testing.assert_array_equal(decompress(frame).view(np.int32), x)


@pytest.mark.parametrize("seed", range(5))
def test_fuzz_roundtrip(seed):
    rng = np.random.RandomState(seed)
    for _ in range(20):
        kind = rng.randint(3)
        n = int(rng.randint(0, 5000))
        if kind == 0:
            data = rng.bytes(n)
        elif kind == 1:  # runs + noise: exercises match emission paths
            data = (rng.bytes(7) * (n // 7 + 1))[:n]
        else:  # long runs: exercises extended-length encoding
            data = bytes([rng.randint(256)]) * n
        roundtrip(data)


def test_fuzz_float_arrays():
    rng = np.random.RandomState(42)
    for dtype in (np.float32, np.float64, np.int16, np.int8):
        for shape in [(0,), (1,), (17,), (128, 3), (33, 5, 7)]:
            x = (rng.randn(*shape) * 100).astype(dtype)
            frame = compress(x)
            out = decompress(frame).view(dtype).reshape(shape)
            np.testing.assert_array_equal(out, x)


def test_corrupt_frames_raise():
    x = np.arange(100, dtype=np.float32)
    frame = bytearray(compress(x))
    with pytest.raises(ValueError):
        decompress(b"XXXX" + bytes(frame[4:]))
    with pytest.raises(ValueError):
        decompress(frame[: len(frame) // 2])  # truncated
    with pytest.raises(ValueError):
        decompress(b"")  # shorter than the header itself


def test_corrupt_store_frame_cannot_oob():
    """A store-mode shuffled frame whose payload is shorter than the claimed
    original size must raise, never hand a short buffer to the native
    unshuffle (out-of-bounds read)."""
    import struct
    import zlib

    from pytorch_ps_mpi_tpu.native.serializer import _BUF_HDR_V1, _BUF_MAGIC

    orig = 1 << 20
    head = _BUF_HDR_V1.pack(_BUF_MAGIC, 2, 4, orig, 8)
    evil = (head + struct.pack("<I", zlib.crc32(b"12345678",
                                                zlib.crc32(head)))
            + b"12345678")
    with pytest.raises(ValueError, match="corrupt store frame"):
        decompress(evil)


def test_crc_catches_payload_and_header_bitflips():
    """Any single bitflip — payload OR header (flags/itemsize/sizes, whose
    corruption would mis-decode with a payload-only crc) — must raise (the
    r1 advisor found ~40% of payload bitflips silently decoded pre-crc)."""
    x = np.linspace(0.0, 1.0, 10_000).astype(np.float32)
    for level in (0, 1):
        frame = bytearray(compress(x, level=level))
        positions = list(range(26)) + list(
            range(26, len(frame), max(1, (len(frame) - 26) // 64)))
        for pos in positions:
            corrupted = bytearray(frame)
            corrupted[pos] ^= 0x10
            with pytest.raises(ValueError):
                decompress(bytes(corrupted))


def test_legacy_psz1_frames_still_load():
    """Pre-crc checkpoints (PSZ1 header, no crc field) must stay readable."""
    from pytorch_ps_mpi_tpu.native.serializer import (_BUF_HDR_V1,
                                                      _BUF_MAGIC_V1)

    x = np.arange(100, dtype=np.float32)
    payload = x.tobytes()
    legacy = _BUF_HDR_V1.pack(_BUF_MAGIC_V1, 0, 4, len(payload),
                              len(payload)) + payload
    np.testing.assert_array_equal(decompress(legacy).view(np.float32), x)


def test_restricted_unpickler_blocks_gadgets():
    """Tree metadata naming non-allowlisted globals must be refused — the
    pickle-RCE hazard of torch.load-style loaders.  Covers the classic
    os.system gadget AND the bypasses a module-root filter misses:
    builtins.eval, and numpy object-dtype scalar (whose reconstruction
    nests an *unrestricted* pickle.loads)."""
    import os
    import pickle

    from pytorch_ps_mpi_tpu.native.serializer import _TREE_HDR, _TREE_MAGIC

    def gadget(fn, args):
        class Gadget:
            def __reduce__(self):
                return (fn, args)
        return Gadget()

    scalar = np.core.multiarray.scalar  # numpy<2 path; np2 aliases it
    cases = [
        gadget(os.system, ("true",)),
        gadget(eval, ("__import__('os').system('true')",)),
        gadget(scalar, (np.dtype("O"), pickle.dumps(42))),
    ]
    import zlib

    for evil in cases:
        evil_meta = pickle.dumps({"shapes": [], "dtypes": [],
                                  "treedef": None, "gadget": evil})
        blob = _TREE_HDR.pack(_TREE_MAGIC, len(evil_meta),
                              zlib.crc32(evil_meta)) + evil_meta
        with pytest.raises(pickle.UnpicklingError, match="not in the allow"):
            loads(blob)


def test_tree_meta_bitflip_detected():
    """Corruption inside the pickled tree metadata (step counters, lr, the
    treedef itself) must fail loudly, same as payload corruption."""
    tree = {"w": np.arange(8, dtype=np.float32)}
    blob = bytearray(dumps(tree, meta={"step": 4096, "lr": 0.1}))
    hdr = 16  # PST2 tree header: magic + meta_len(u64) + crc(u32)
    for pos in range(hdr, hdr + 40):  # flips inside the meta pickle
        corrupted = bytearray(blob)
        corrupted[pos] ^= 0x08
        with pytest.raises(Exception):
            loads(bytes(corrupted))


def test_dumps_rejects_meta_its_own_loads_would_refuse():
    """Write-time validation: meta that the restricted loader cannot re-read
    (e.g. numpy scalars/arrays) must fail at save time, not produce an
    unrecoverable checkpoint discovered at restore time."""
    with pytest.raises(ValueError, match="plain-Python"):
        dumps({"w": np.zeros(3, np.float32)}, meta={"lr": np.float32(0.1)})
    with pytest.raises(ValueError, match="plain-Python"):
        dumps({"w": np.zeros(3, np.float32)},
              meta={"rng": np.arange(4)})
    # Plain-data meta still round-trips.
    _, user = loads(dumps({"w": np.zeros(3, np.float32)},
                          meta={"lr": 0.1, "betas": (0.9, 0.999)}),
                    with_meta=True)
    assert user == {"lr": 0.1, "betas": (0.9, 0.999)}


NT = __import__("collections").namedtuple("NT", ["a", "b"])


def test_namedtuple_tree_needs_and_honors_trusted():
    """Trees with namedtuple nodes (optax-style states): refused by default
    at SAVE time with an actionable message, round-trip with trusted=True
    on both ends.  (NT is module-level so plain pickle can resolve it.)"""
    tree = {"s": NT(np.arange(3, dtype=np.float32), np.zeros(2, np.float32))}
    with pytest.raises(ValueError, match="trusted=True"):
        dumps(tree)
    blob = dumps(tree, trusted=True)
    with pytest.raises(Exception):  # restricted reader refuses the class
        loads(blob)
    back = loads(blob, trusted=True)
    np.testing.assert_array_equal(back["s"].a, tree["s"].a)


def test_ps_crc32_matches_zlib():
    """The native crc must be bit-identical to zlib.crc32 (frames written by
    either side verify on the other), including chained updates."""
    import zlib

    rng = np.random.RandomState(3)
    L = lib()
    for n in (0, 1, 7, 8, 63, 1024, 100_000):
        buf = np.frombuffer(rng.bytes(n), np.uint8) if n else \
            np.empty(0, np.uint8)
        assert L.ps_crc32(0, buf.ctypes.data, n) == zlib.crc32(buf)
        start = zlib.crc32(b"prefix")
        assert (L.ps_crc32(start, buf.ctypes.data, n)
                == zlib.crc32(buf, start))


@pytest.mark.parametrize("level", [0, 1])
def test_batch_encode_matches_per_leaf_compress(level):
    """`dumps` (batched native ps_tree_encode) must produce byte-identical
    frames to the per-leaf `compress` path it replaced."""
    rng = np.random.RandomState(4)
    leaves = {
        "a": np.linspace(0, 1, 5000).astype(np.float32),
        "b": rng.randn(17).astype(np.float64),
        "c": np.arange(33, dtype=np.int16),
        "d": np.zeros(0, np.float32),
        "e": np.int8(3),
    }
    blob = dumps(leaves, level=level)
    import jax

    arrs = [np.asarray(x) for x in jax.tree_util.tree_leaves(leaves)]
    expected = b"".join(compress(a, level=level) for a in arrs)
    assert blob.endswith(expected)


def test_tree_decode_threaded_path():
    """Exercise the std::thread fan-out inside ps_tree_decode/encode
    explicitly (a 1-core host never engages it via the auto heuristic)."""
    import ctypes

    from pytorch_ps_mpi_tpu.native.serializer import (_TREE_HDR,
                                                      _decode_frames,
                                                      _encode_frames)

    rng = np.random.RandomState(5)
    arrs = [np.linspace(0, i + 1, 100_000).astype(np.float32)
            for i in range(6)] + [rng.randn(50_000).astype(np.float64)]
    frames = bytes(_encode_frames(arrs, 1))
    view = memoryview(frames)
    shapes = [a.shape for a in arrs]
    dtypes = [a.dtype.str for a in arrs]

    import pytorch_ps_mpi_tpu.native.serializer as S
    orig = S._native_threads
    S._native_threads = lambda total, n: 4
    try:
        leaves = _decode_frames(view, 0, shapes, dtypes)
    finally:
        S._native_threads = orig
    for got, want in zip(leaves, arrs):
        np.testing.assert_array_equal(got, want)

    # Corruption surfaces from worker threads too.
    bad = bytearray(frames)
    bad[len(frames) // 2] ^= 0x40
    S._native_threads = lambda total, n: 4
    try:
        with pytest.raises(ValueError):
            _decode_frames(memoryview(bytes(bad)), 0, shapes, dtypes)
    finally:
        S._native_threads = orig


def test_legacy_psz1_frames_inside_tree_still_load():
    """A tree whose buffer frames are legacy PSZ1 (no per-frame crc) must
    load through the batched native decoder."""
    import pickle
    import zlib

    import jax

    from pytorch_ps_mpi_tpu.native.serializer import (_BUF_HDR_V1,
                                                      _BUF_MAGIC_V1,
                                                      _TREE_HDR, _TREE_MAGIC)

    tree = {"w": np.arange(20, dtype=np.float32),
            "b": np.arange(6, dtype=np.int64)}
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrs = [np.asarray(x) for x in leaves]
    meta = {"treedef": treedef, "shapes": [a.shape for a in arrs],
            "dtypes": [a.dtype.str for a in arrs], "user": None}
    meta_blob = pickle.dumps(meta)
    frames = b"".join(
        _BUF_HDR_V1.pack(_BUF_MAGIC_V1, 0, a.itemsize, a.nbytes, a.nbytes)
        + a.tobytes() for a in arrs)
    blob = _TREE_HDR.pack(_TREE_MAGIC, len(meta_blob),
                          zlib.crc32(meta_blob)) + meta_blob + frames
    back = loads(blob)
    np.testing.assert_array_equal(back["w"], tree["w"])
    np.testing.assert_array_equal(back["b"], tree["b"])


def test_tree_leaf_size_mismatch_detected():
    """A frame whose original size disagrees with the tree metadata must
    fail loudly (the C decoder validates orig against the meta-derived
    expected size instead of mis-viewing the arena)."""
    tree = {"w": np.arange(8, dtype=np.float32)}
    blob = bytearray(dumps(tree))
    # Patch the frame's orig field (u64 at frame_start+6) to lie.
    import pickle
    from pytorch_ps_mpi_tpu.native.serializer import _TREE_HDR

    meta_len = _TREE_HDR.unpack_from(blob, 0)[1]
    frame_at = _TREE_HDR.size + meta_len
    with pytest.raises(ValueError):
        bad = bytearray(blob)
        bad[frame_at + 6] ^= 0xFF
        loads(bytes(bad))


def test_tree_roundtrip():
    from collections import OrderedDict

    rng = np.random.RandomState(1)
    tree = {
        "params": OrderedDict(
            w=rng.randn(64, 32).astype(np.float32),
            b=np.zeros(32, np.float32)),
        "state": {"step": np.int32(7),
                  "nested": [rng.randn(8).astype(np.float64),
                             np.arange(5, dtype=np.int64)]},
    }
    blob = dumps(tree)
    back = loads(blob)
    assert set(back) == {"params", "state"}
    np.testing.assert_array_equal(back["params"]["w"], tree["params"]["w"])
    np.testing.assert_array_equal(back["state"]["nested"][0],
                                  tree["state"]["nested"][0])
    assert back["state"]["step"] == 7


def test_tree_roundtrip_jax_leaves():
    import jax.numpy as jnp

    tree = {"w": jnp.arange(12.0).reshape(3, 4), "b": jnp.zeros(4)}
    back = loads(dumps(tree))
    np.testing.assert_array_equal(back["w"], np.arange(12.0).reshape(3, 4))


def test_dumps_compresses_checkpoint_like_payload():
    rng = np.random.RandomState(2)
    # Momentum buffers near zero + weights: realistic checkpoint bytes.
    tree = {"w": (rng.randn(256, 256) * 0.01).astype(np.float32),
            "m": np.zeros((256, 256), np.float32)}
    blob = dumps(tree, level=1)
    raw = 2 * 256 * 256 * 4
    assert len(blob) < raw * 0.75  # zeros plane must compress away


# ---------------------------------------------------------------------------
# encode_segments — the scatter-gather form of dumps (ISSUE 13, wire v9)
# ---------------------------------------------------------------------------

def _segments_tree(seed=0):
    rng = np.random.RandomState(seed)
    from collections import OrderedDict
    return OrderedDict([
        ("w", rng.randn(37, 21).astype(np.float32)),
        ("b", rng.randn(21).astype(np.float64)),
        ("empty", np.zeros((0,), np.float32)),
        ("scalar", np.float32(2.5)),
        ("noncontig", np.asarray(rng.randn(6, 4), np.float32).T),
    ])


@pytest.mark.parametrize("level", [0, 1])
def test_encode_segments_joins_to_dumps_bytes(level):
    """The invariant the whole segmented wire rests on:
    ``meta_blob + b"".join(segments)`` is byte-identical to the blob
    `dumps` writes — receivers are agnostic to how the frame was
    gathered, and `loads` round-trips the concatenation."""
    from pytorch_ps_mpi_tpu.native.serializer import encode_segments

    tree = _segments_tree()
    blob = dumps(tree, level=level)
    meta_blob, segs = encode_segments(tree, level=level)
    joined = bytes(meta_blob) + b"".join(bytes(s) for s in segs)
    assert joined == blob
    back = loads(joined)
    for k in tree:
        np.testing.assert_array_equal(np.asarray(back[k]),
                                      np.asarray(tree[k]))


@pytest.mark.parametrize("level", [0, 1])
def test_encode_segments_wire_crc_single_pass(level):
    """`SegmentList.wire_crc`/`wire_len` (derived via `crc32_combine`
    without a second pass over the leaves) must equal the crc/length of
    the concatenated payload — what the transport frame header needs."""
    import zlib

    from pytorch_ps_mpi_tpu.native.serializer import encode_segments

    meta_blob, segs = encode_segments(_segments_tree(1), level=level)
    joined = bytes(meta_blob) + b"".join(bytes(s) for s in segs)
    assert segs.wire_len == len(joined)
    assert segs.wire_crc == zlib.crc32(joined)


def test_encode_segments_level0_leaf_views_are_zero_copy():
    """Level-0 leaf payload segments alias the caller's array buffers
    (no bytes moved at encode time) — the scatter-gather contract; a
    caller-side mutation is visible through the view (which is exactly
    why `Session.send_data_segments` copies on park)."""
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu.native.serializer import encode_segments

    leaf = np.arange(64, dtype=np.float32)
    _meta, segs = encode_segments(OrderedDict([("w", leaf)]), level=0)
    payload = segs[1]  # [header, payload-view]
    assert isinstance(payload, memoryview)
    leaf[0] = 123.0
    assert bytes(payload[:4]) == np.float32(123.0).tobytes()


def test_crc32_combine_matches_zlib_concat():
    import os
    import zlib

    from pytorch_ps_mpi_tpu.utils.crc import crc32_combine, fast_crc32

    for la, lb in ((0, 5), (5, 0), (1, 1), (1000, 33), (33, 100_000)):
        a, b = os.urandom(la), os.urandom(lb)
        assert crc32_combine(zlib.crc32(a), zlib.crc32(b), lb) \
            == zlib.crc32(a + b)
    # fast_crc32 is zlib-compatible across the native-dispatch
    # threshold (small -> zlib, large -> PCLMUL kernel), seeded too.
    for n in (10, 4095, 4096, 70_000):
        buf = os.urandom(n)
        assert fast_crc32(buf) == zlib.crc32(buf)
        assert fast_crc32(buf, 777) == zlib.crc32(buf, 777)
        assert fast_crc32(memoryview(buf)) == zlib.crc32(buf)


def test_meta_blob_cache_returns_identical_framing():
    """The structure-keyed meta cache must be invisible: repeated dumps
    of same-structure trees with DIFFERENT values share the meta blob
    byte-for-byte while the payloads differ."""
    from collections import OrderedDict

    t1 = OrderedDict([("w", np.arange(6, dtype=np.float32))])
    t2 = OrderedDict([("w", np.arange(6, 12, dtype=np.float32))])
    b1, b2 = dumps(t1, level=0), dumps(t2, level=0)
    assert b1 != b2
    np.testing.assert_array_equal(loads(b2)["w"], t2["w"])
    # Different structure misses the cache and still round-trips.
    t3 = OrderedDict([("w", np.arange(7, dtype=np.float32))])
    np.testing.assert_array_equal(loads(dumps(t3, level=0))["w"],
                                  t3["w"])
