"""Kernel-vs-reference parity for the codec compute layer.

On the CPU test mesh the Mosaic lowering can't run, so these tests name the
*reference* math (``impl="ref"``, which the TPU kernels mirror op-for-op)
or the Pallas interpreter (``impl="interpret"``), and pin the layout
contract (padding, packing, block framing) that every implementation
shares.  Kernel == reference on the chip is ``chip_smoke.py``'s
``kernel_parity`` phase; the dispatch rules themselves are tested at the
end of this file.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import pallas_kernels as pk
from pytorch_ps_mpi_tpu.ops.codecs import BlockQuantizeCodec, SignCodec


def test_pad_to_blocks_roundtrip():
    flat = jnp.arange(1000, dtype=jnp.float32)
    x2d, n_blocks = pk.pad_to_blocks(flat, block_rows=8)
    assert x2d.shape == (8, pk.LANE)
    assert n_blocks == 1
    np.testing.assert_array_equal(np.asarray(x2d).reshape(-1)[:1000], flat)
    assert np.all(np.asarray(x2d).reshape(-1)[1000:] == 0)


def test_block_quantize_roundtrip_error_bound():
    rng = np.random.RandomState(0)
    x = rng.randn(4 * 8 * pk.LANE).astype(np.float32)
    x2d, _ = pk.pad_to_blocks(jnp.asarray(x), block_rows=8)
    q, scales = pk.block_quantize(x2d, bits=8, block_rows=8, impl="ref")
    assert q.dtype == jnp.int8
    assert scales.shape == (4, 1)
    deq = (np.asarray(q, np.float32).reshape(4, -1)
           * np.asarray(scales)).reshape(-1)[:x.size]
    # Quantization error bounded by scale/2 per element.
    per_block_scale = np.repeat(np.asarray(scales)[:, 0], 8 * pk.LANE)[:x.size]
    assert np.all(np.abs(deq - x) <= per_block_scale * 0.5 + 1e-7)


def test_block_quantize_per_block_scales_differ():
    # Two blocks with very different magnitude -> different scales (the
    # whole point of block quantization vs per-tensor).
    a = np.full(8 * pk.LANE, 100.0, np.float32)
    b = np.full(8 * pk.LANE, 0.01, np.float32)
    x2d = jnp.asarray(np.concatenate([a, b])).reshape(16, pk.LANE)
    _, scales = pk.block_quantize(x2d, bits=8, block_rows=8, impl="ref")
    s = np.asarray(scales)[:, 0]
    assert s[0] > 100 * s[1]


def test_block_dequant_sum_matches_manual():
    rng = np.random.RandomState(1)
    world, n_blocks, br = 3, 2, 8
    rows = n_blocks * br
    qs, ss = [], []
    for w in range(world):
        x2d = jnp.asarray(rng.randn(rows, pk.LANE).astype(np.float32))
        q, s = pk.block_quantize(x2d, bits=8, block_rows=br, impl="ref")
        qs.append(q)
        ss.append(s)
    q = jnp.stack(qs)
    s = jnp.stack(ss)
    out = pk.block_dequant_sum(q, s, block_rows=br, impl="ref")
    manual = sum(
        np.asarray(qs[w], np.float32).reshape(n_blocks, -1)
        * np.asarray(ss[w]) for w in range(world)).reshape(rows, pk.LANE)
    # atol floor: XLA may fuse the dequant multiply-add (fma, no
    # intermediate rounding), so near-zero entries differ from the
    # numpy manual sum by ~f32 ulps — a relative bound alone flags them.
    np.testing.assert_allclose(np.asarray(out), manual, rtol=1e-5,
                               atol=1e-5)


def test_sign_pack_unpack_roundtrip():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(128).astype(np.float32))
    packed = pk.pack_signs(x)
    assert packed.dtype == jnp.uint8
    assert packed.shape == (16,)
    signs = pk.unpack_signs(packed, 128)
    np.testing.assert_array_equal(np.asarray(signs),
                                  np.where(np.asarray(x) >= 0, 1.0, -1.0))


def test_sign_codec_packed_wire():
    rng = np.random.RandomState(3)
    g = jnp.asarray(rng.randn(10, 7).astype(np.float32))  # 70 elems, pads to 72
    codec = SignCodec()
    code = codec.encode(g)
    assert code["sign"].shape == (9,)  # 72 / 8 bytes
    out = codec.decode(code, shape=(10, 7), dtype=jnp.float32)
    scale = float(jnp.mean(jnp.abs(g)))
    np.testing.assert_allclose(
        np.asarray(out), np.where(np.asarray(g) >= 0, scale, -scale),
        rtol=1e-6)
    assert codec.wire_bytes((10, 7), jnp.float32) == 9 + 4


@pytest.mark.parametrize("bits", [8, 16])
def test_blockq_codec_decode_sum(bits):
    rng = np.random.RandomState(4)
    shape = (33, 17)
    codec = BlockQuantizeCodec(bits=bits, block_rows=8, impl="ref")
    grads = [jnp.asarray(rng.randn(*shape).astype(np.float32))
             for _ in range(4)]
    codes = [codec.encode(g) for g in grads]
    stacked = {k: jnp.stack([c[k] for c in codes]) for k in codes[0]}
    out = codec.decode_sum(stacked, shape=shape, dtype=jnp.float32)
    manual = sum(codec.decode(c, shape=shape, dtype=jnp.float32)
                 for c in codes)
    np.testing.assert_allclose(np.asarray(out), np.asarray(manual),
                               rtol=1e-5, atol=1e-6)


def test_blockq_in_ps_step(mesh8):
    """End-to-end: the blockq codec drives a full SPMD PS step."""
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu import SGD

    rng = np.random.RandomState(5)
    params = OrderedDict(
        w=jnp.asarray(rng.randn(20, 4).astype(np.float32)),
        b=jnp.zeros((4,), jnp.float32))

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    opt = SGD(list(params.items()), lr=0.05, mesh=mesh8,
              code=BlockQuantizeCodec(8, block_rows=8, impl="ref"))
    opt.compile_step(loss_fn)
    batch = {"x": rng.randn(16, 20).astype(np.float32),
             "y": rng.randn(16, 4).astype(np.float32)}
    losses = [opt.step(batch)[0] for _ in range(5)]
    assert losses[-1] < losses[0]


# ---------------------------------------------------------------------------
# fused cast decode-sum (CastCodec's bf16-wire -> f32-accumulate kernel)
# ---------------------------------------------------------------------------


def _stack_codes(codec, grads):
    return jnp.stack([codec.encode(g) for g in grads])


@pytest.mark.parametrize("n", [5, 128, 1000, 8 * pk.LANE, 3 * 512 * pk.LANE])
def test_cast_sum_pallas_interpreter_matches_ref(n):
    """The Pallas kernel itself, run under the CPU interpreter
    (``interpret=True``), must match the jnp reference bit-for-bit in f32
    — the numerical-parity gate for the fused decode-sum."""
    rng = np.random.RandomState(0)
    world = 4
    rows = pk.rows_for_flat(n)
    per_block = rows * pk.LANE
    n_blocks = max(1, -(-n // per_block))
    flat = jnp.asarray(rng.randn(world, n).astype(np.float32)
                       ).astype(jnp.bfloat16)
    padded = jnp.zeros((world, n_blocks * per_block),
                       flat.dtype).at[:, :n].set(flat)
    x3 = padded.reshape(world, n_blocks * rows, pk.LANE)
    kernel = pk.cast_sum_tpu(x3, block_rows=rows, interpret=True)
    ref = pk.cast_sum_ref(x3, block_rows=rows)
    assert kernel.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(kernel), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("shape", [(1000,), (7, 33), (128,), (3, 128, 5),
                                   ()])
def test_cast_codec_fused_decode_sum_matches_generic(shape):
    """CastCodec.decode_sum (the fused path) vs the generic vmap-decode-
    then-sum it replaces: same sum within fp32 tolerance, any rank/shape,
    including the padding tail."""
    from pytorch_ps_mpi_tpu.ops.codecs import CastCodec, Codec

    rng = np.random.RandomState(1)
    world = 5
    codec = CastCodec(impl="ref")
    grads = [jnp.asarray(np.asarray(3 * rng.randn(*shape), np.float32))
             for _ in range(world)]
    codes = _stack_codes(codec, grads)
    fused = codec.decode_sum(codes, shape=shape, dtype=jnp.float32)
    generic = Codec.decode_sum(codec, codes, shape=shape,
                               dtype=jnp.float32)
    assert fused.shape == tuple(shape)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(generic),
                               rtol=1e-6, atol=1e-6)


def test_cast_codec_accumulates_in_f32_not_wire_dtype():
    """The reduction must run in f32 even when the wire is bf16: summing
    many small same-sign values in bf16 would lose them to rounding; the
    fused kernel's f32 accumulator must not."""
    from pytorch_ps_mpi_tpu.ops.codecs import CastCodec

    codec = CastCodec(impl="ref")
    world, n = 64, 256
    # 64 ranks each contribute 1.0 + tiny; a bf16 accumulator would round
    # the tiny parts away long before rank 64.
    vals = np.full((world, n), 1.0 + 2 ** -7, np.float32)
    codes = jnp.asarray(vals).astype(jnp.bfloat16)
    out = codec.decode_sum(codes, shape=(n,), dtype=jnp.float32)
    expect = world * np.asarray(
        jnp.asarray(vals[0]).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-6)


def test_cast_codec_in_ps_step(mesh8):
    """End-to-end: the bf16 codec's fused decode-sum drives a full SPMD PS
    step and matches the identity-codec step within bf16 wire error."""
    from collections import OrderedDict

    from pytorch_ps_mpi_tpu import SGD

    rng = np.random.RandomState(7)
    params = OrderedDict(
        w=jnp.asarray(rng.randn(20, 4).astype(np.float32)),
        b=jnp.zeros((4,), jnp.float32))

    def loss_fn(p, batch):
        pred = batch["x"] @ p["w"] + p["b"]
        return jnp.mean((pred - batch["y"]) ** 2)

    batch = {"x": rng.randn(64, 20).astype(np.float32),
             "y": rng.randn(64, 4).astype(np.float32)}

    def run(code):
        opt = SGD([(k, v) for k, v in params.items()], lr=0.05, mesh=mesh8,
                  code=code)
        opt.compile_step(loss_fn)
        for _ in range(3):
            loss, _ = opt.step(batch)
        return loss, {n: np.asarray(p) for n, p in opt.params.items()}

    loss_id, p_id = run(None)
    loss_bf, p_bf = run("bf16")
    assert np.isfinite(loss_bf)
    np.testing.assert_allclose(loss_bf, loss_id, rtol=5e-2)
    for n in p_id:
        np.testing.assert_allclose(p_bf[n], p_id[n], rtol=5e-2, atol=5e-3)


# ---------------------------------------------------------------------------
# kernel dispatch: chosen from the devices' platform, never silently
# ---------------------------------------------------------------------------


def test_impl_follows_the_platform_and_refuses_unknown_ones():
    from pytorch_ps_mpi_tpu.errors import KernelPlatformError

    assert pk.impl_for_platform("tpu") == "mosaic"
    assert pk.impl_for_platform("cpu") == "ref"
    assert pk.impl_for_platform("cpu", cpu="interpret") == "interpret"
    with pytest.raises(KernelPlatformError):
        pk.impl_for_platform("gpu")


def test_mosaic_codec_on_cpu_mesh_is_a_typed_error(mesh2):
    """The default codec construction means the TPU kernel; handing it to
    an optimizer built on CPU devices raises instead of quietly running
    the reference."""
    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.errors import KernelPlatformError
    from pytorch_ps_mpi_tpu.ops.codecs import CastCodec, get_codec

    named = [("w", np.zeros((4, 4), np.float32))]
    for codec in (BlockQuantizeCodec(), CastCodec()):
        assert codec.impl == "mosaic"
        with pytest.raises(KernelPlatformError, match="mosaic"):
            SGD(named, lr=0.1, mesh=mesh2, code=codec)
        with pytest.raises(KernelPlatformError):
            get_codec(codec, "cpu")
    # By name, the CPU mesh gets the reference; a TPU build gets Mosaic.
    assert SGD(named, lr=0.1, mesh=mesh2, code="blockq").code.impl == "ref"
    assert get_codec("blockq", "tpu").impl == "mosaic"
    assert get_codec("bf16", "tpu").impl == "mosaic"


def test_interpreter_and_reference_only_on_request(monkeypatch):
    """``impl`` alone decides which implementation runs: the default
    reaches the Mosaic lowering (which the CPU backend refuses, loudly),
    ``"interpret"`` the kernel under the interpreter, ``"ref"`` the jnp
    function — no other path reaches them."""
    x2d = jnp.asarray(np.random.RandomState(0)
                      .randn(8, pk.LANE).astype(np.float32))
    with pytest.raises(ValueError, match="interpret mode"):
        pk.block_quantize(x2d, bits=8, block_rows=8)
    calls = []
    monkeypatch.setattr(pk, "block_quantize_ref",
                        lambda *a, **k: calls.append("ref"))
    monkeypatch.setattr(pk, "block_quantize_tpu",
                        lambda *a, interpret, **k: calls.append(
                            "interpret" if interpret else "mosaic"))
    for impl in ("interpret", "ref", "mosaic"):
        pk.block_quantize(x2d, bits=8, block_rows=8, impl=impl)
    assert calls == ["interpret", "ref", "mosaic"]
    with pytest.raises(ValueError, match="impl must be"):
        pk.block_quantize(x2d, bits=8, block_rows=8, impl="dense")


def test_flash_attention_default_is_the_mosaic_kernel():
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention

    q = jnp.zeros((1, 128, 1, 8), jnp.float32)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, q, q, causal=True)
    out = flash_attention(q, q, q, causal=True, impl="interpret")
    assert out.shape == q.shape
    with pytest.raises(ValueError, match="impl must be"):
        flash_attention(q, q, q, impl="ref")
