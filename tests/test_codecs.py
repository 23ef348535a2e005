"""Codec unit tests — the L2a plug-point (`/root/reference/ps.py:65-66,
165-166`): encode/decode round-trips, decode_sum == sum-of-decodes (the
reference's decode-loop + ``sum(grads)``, `ps.py:165-176`), wire-byte
accounting."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops.codecs import (
    IdentityCodec, QuantizeCodec, SignCodec, TopKCodec, get_codec)


RNG = np.random.RandomState(0)
GRAD = jnp.asarray(RNG.randn(6, 5).astype(np.float32))


def test_identity_roundtrip():
    c = IdentityCodec()
    code = c.encode(GRAD)
    out = c.decode(code, shape=GRAD.shape, dtype=GRAD.dtype)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(GRAD))
    assert c.wire_bytes(GRAD.shape, GRAD.dtype) == 30 * 4


def test_topk_keeps_largest():
    c = TopKCodec(k=5)
    code = c.encode(GRAD)
    out = np.asarray(c.decode(code, shape=GRAD.shape, dtype=GRAD.dtype))
    dense = np.asarray(GRAD)
    # Exactly k nonzeros, and they are the k largest-|.| entries, unchanged.
    assert (out != 0).sum() == 5
    flat = np.abs(dense).ravel()
    topk_idx = np.argsort(-flat)[:5]
    for i in topk_idx:
        assert out.ravel()[i] == dense.ravel()[i]


def test_topk_fraction_static_k():
    c = TopKCodec(fraction=0.1)
    assert c._k_for(30) == 3
    assert c._k_for(5) == 1  # floor at 1
    code = c.encode(GRAD)
    assert code["values"].shape == (3,)
    assert code["indices"].dtype == jnp.int32


def test_quantize_roundtrip_error_bounded():
    c = QuantizeCodec(bits=8)
    code = c.encode(GRAD)
    assert code["q"].dtype == jnp.int8
    out = np.asarray(c.decode(code, shape=GRAD.shape, dtype=jnp.float32))
    dense = np.asarray(GRAD)
    scale = np.abs(dense).max() / 127.0
    assert np.abs(out - dense).max() <= scale / 2 + 1e-7
    assert c.wire_bytes(GRAD.shape, GRAD.dtype) == 30 + 4


def test_sign_codec():
    c = SignCodec()
    code = c.encode(GRAD)
    out = np.asarray(c.decode(code, shape=GRAD.shape, dtype=jnp.float32))
    dense = np.asarray(GRAD)
    np.testing.assert_array_equal(np.sign(out), np.where(dense >= 0, 1.0, -1.0))
    assert np.allclose(np.abs(out), np.abs(dense).mean(), rtol=1e-6)


@pytest.mark.parametrize("codec", [
    IdentityCodec(), TopKCodec(k=4), QuantizeCodec(8), SignCodec()])
def test_decode_sum_equals_sum_of_decodes(codec):
    """The hot-path fusion must be exactly the reference semantics:
    decode each rank's code independently, then sum (`ps.py:165-176`)."""
    n_ranks = 4
    grads = [jnp.asarray(RNG.randn(3, 4).astype(np.float32))
             for _ in range(n_ranks)]
    codes = [codec.encode(g) for g in grads]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *codes)
    fused = np.asarray(codec.decode_sum(stacked, shape=(3, 4),
                                        dtype=jnp.float32))
    manual = sum(
        np.asarray(codec.decode(c, shape=(3, 4), dtype=jnp.float32))
        for c in codes)
    np.testing.assert_allclose(fused, manual, rtol=1e-6, atol=1e-7)


def test_get_codec_resolution():
    assert isinstance(get_codec(None, "cpu"), IdentityCodec)
    assert isinstance(get_codec("topk", "cpu"), TopKCodec)
    c = QuantizeCodec(16)
    assert get_codec(c, "cpu") is c
    with pytest.raises(ValueError):
        get_codec("lz4", "cpu")  # banned in the reference too (`mpi_comms.py:22-24`)


def test_scale_code_is_linear_for_all_codecs():
    """The property the async PS's staleness weighting actually uses:
    ``decode_sum(vmap(scale_code)(codes, w)) == Σᵢ wᵢ·decode(codeᵢ)`` —
    exercised through decode_sum itself (TopK and blockq override it with
    independent scatter/kernel implementations), per codec."""
    import jax
    import jax.numpy as jnp
    from pytorch_ps_mpi_tpu.ops.codecs import get_codec

    rng = np.random.RandomState(0)
    gs = [jnp.asarray(rng.randn(24, 16).astype(np.float32))
          for _ in range(3)]
    w = jnp.asarray([0.25, 1.0, 0.5], jnp.float32)
    for name in ("identity", "bf16", "topk", "topk_approx", "quantize",
                 "sign", "blockq"):
        codec = get_codec(name, "cpu")
        codes = [codec.encode(g) for g in gs]
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *codes)
        got = np.asarray(codec.decode_sum(
            jax.vmap(codec.scale_code)(stacked, w),
            shape=gs[0].shape, dtype=jnp.float32))
        want = sum(float(wi) * np.asarray(
            codec.decode(c, shape=gs[0].shape, dtype=jnp.float32))
            for wi, c in zip(w, codes))
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-3,
                                   err_msg=name)
