"""Error-feedback compression (EF-SGD) and the bf16 cast codec.

Oracles: the residual algebra checked against a hand-computed two-rank
trace; convergence under aggressive top-k where the plain codec stalls;
skip-consensus rollback of the residual; world-size-independent
checkpointing of the aggregate residual."""

from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
from pytorch_ps_mpi_tpu.ops.codecs import (CastCodec, IdentityCodec,
                                           TopKCodec, get_codec)
from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh


def _mlp_opt(world, *, seed=0, **kw):
    rng = np.random.RandomState(seed)
    params = init_mlp(rng, sizes=(12, 16, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=make_ps_mesh(world), **kw)
    opt.compile_step(mlp_loss_fn)
    return opt


def _batches(world, n, seed=1):
    rng = np.random.RandomState(seed)
    return [{"x": rng.randn(4 * world, 12).astype(np.float32),
             "y": rng.randint(0, 4, 4 * world).astype(np.int32)}
            for _ in range(n)]


# -- bf16 cast codec ---------------------------------------------------------


def test_cast_codec_roundtrip_and_bytes():
    codec = get_codec("bf16", "cpu")
    g = jnp.asarray(np.random.RandomState(0).randn(33, 7).astype(np.float32))
    code = codec.encode(g)
    assert code.dtype == jnp.bfloat16
    dec = codec.decode(code, shape=g.shape, dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(g),
                               rtol=1e-2, atol=1e-2)
    assert codec.wire_bytes(g.shape, g.dtype) == g.size * 2


def test_cast_codec_trains():
    opt = _mlp_opt(4, code="bf16")
    losses = [opt.step(b)[0] for b in _batches(4, 30)]
    assert losses[-1] < losses[0] * 0.7, losses[::6]


# -- EF residual algebra -----------------------------------------------------


def test_ef_residual_matches_manual_trace():
    """After one step: e_r == (g_r) - decode(encode(g_r)); after two:
    e_r == (g_r2 + e_r1) - decode(encode(g_r2 + e_r1))."""
    world = 2
    codec = TopKCodec(k=2)
    opt = _mlp_opt(world, code=codec, error_feedback=True)

    def rank_grads(batch):
        """Per-rank gradients, computed independently of the PS step."""
        host_params = OrderedDict(
            (n, jnp.asarray(np.asarray(p)))
            for n, p in opt.named_parameters())
        out = []
        for r in range(world):
            shard = {k: v[r * 4:(r + 1) * 4] for k, v in batch.items()}
            out.append(jax.grad(mlp_loss_fn)(host_params, shard))
        return out

    e = {n: [np.zeros_like(np.asarray(p)) for _ in range(world)]
         for n, p in opt.named_parameters()}
    for batch in _batches(world, 2, seed=3):
        grads = rank_grads(batch)  # uses CURRENT params, pre-step
        opt.step(batch)
        for n in e:
            for r in range(world):
                d = np.asarray(grads[r][n]) + e[n][r]
                dj = jnp.asarray(d)
                dec = np.asarray(codec.decode(codec.encode(dj),
                                              shape=d.shape, dtype=dj.dtype))
                e[n][r] = d - dec
        for n in e:
            got = np.asarray(opt.ef_state[n])
            want = np.stack(e[n])
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                       err_msg=n)


def _regression_setup(world, *, code, seed=0, **kw):
    """Deterministic ill-conditioned least squares through the real PS
    step: the setting where top-1 compression provably biases (greedy
    coordinate descent stalls off-axis) and EF provably recovers the
    dense rate (Karimireddy et al.)."""
    rng = np.random.RandomState(seed)
    d = 20
    q, _ = np.linalg.qr(rng.randn(d, d))
    x = rng.randn(8 * world, d) @ (q * np.logspace(0, -1, d)) @ q.T
    w_true = rng.randn(d)
    batch = {"x": x.astype(np.float32),
             "y": (x @ w_true).astype(np.float32)}

    def loss_fn(params, b):
        pred = b["x"] @ params["w"]
        return jnp.mean((pred - b["y"]) ** 2)

    opt = SGD([("w", np.zeros(d, np.float32))], lr=0.02,
              mesh=make_ps_mesh(world), code=code, **kw)
    opt.compile_step(loss_fn)
    return opt, batch


def test_ef_beats_plain_aggressive_topk():
    """Full-batch top-1 compression: plain stalls at its bias floor, EF
    tracks the dense trajectory through the residual stream."""
    plain, batch = _regression_setup(2, code=TopKCodec(k=1))
    ef, _ = _regression_setup(2, code=TopKCodec(k=1), error_feedback=True)
    dense, _ = _regression_setup(2, code=None)
    for _ in range(300):
        lp, _m = plain.step(batch)
        le, _m = ef.step(batch)
        ld, _m = dense.step(batch)
    assert le < lp * 0.3, (le, lp)           # EF far below the bias floor
    assert le < ld * 5 + 1e-3, (le, ld)      # ...and near the dense run


def test_ef_composes_with_approx_topk():
    """EF + the approx_max_k selection path: the residual stream absorbs
    whatever the approximate selection drops, so training still converges
    (on CPU approx falls back to exact selection — this pins the
    integration, the TPU-primitive speed is the bench's to measure)."""
    opt = _mlp_opt(4, code=TopKCodec(k=2, approx=True), error_feedback=True)
    losses = [opt.step(b)[0] for b in _batches(4, 30)]
    assert losses[-1] < losses[0] * 0.7, losses[::6]


def test_ef_requires_lossy_codec():
    with pytest.raises(ValueError, match="lossy codec"):
        _mlp_opt(2, error_feedback=True)
    with pytest.raises(ValueError, match="lossy codec"):
        _mlp_opt(2, code=IdentityCodec(), error_feedback=True)


def test_ef_skip_nonfinite_rolls_back_residual():
    opt = _mlp_opt(2, code=TopKCodec(k=2), error_feedback=True,
                   skip_nonfinite=True)
    good = _batches(2, 1, seed=7)[0]
    opt.step(good)
    ef_before = {n: np.asarray(v).copy() for n, v in opt.ef_state.items()}
    bad = dict(good)
    bad["x"] = good["x"].copy()
    bad["x"][0, 0] = np.nan
    _, data = opt.step(bad)
    assert data["nonfinite_skip"] == 1.0
    for n, v in opt.ef_state.items():
        np.testing.assert_array_equal(np.asarray(v), ef_before[n], err_msg=n)


def test_ef_zero_composes():
    """EF + ZeRO-sharded state: the decoded sum feeds the chunked update
    and the residual stream still recovers the dense trajectory."""
    opt, batch = _regression_setup(4, code=TopKCodec(k=1),
                                   error_feedback=True, zero=True)
    losses = [opt.step(batch)[0] for _ in range(300)]
    assert losses[-1] < losses[0] * 0.05, losses[::60]


def test_ef_checkpoint_world_size_change():
    """state_dict stores the per-rank residual; loading on a different
    world size collapses to the cross-rank sum and splits evenly — the
    aggregate un-applied error is preserved exactly."""
    opt4 = _mlp_opt(4, code=TopKCodec(k=2), error_feedback=True)
    for b in _batches(4, 3, seed=11):
        opt4.step(b)
    sd = opt4.state_dict()
    agg4 = {n: np.asarray(v).sum(axis=0) for n, v in opt4.ef_state.items()}
    for n, v in (sd["ef"] or {}).items():
        assert np.asarray(v).shape[0] == 4  # per-rank, not pre-summed
        np.testing.assert_allclose(np.asarray(v).sum(axis=0), agg4[n],
                                   rtol=1e-6, err_msg=n)

    opt2 = _mlp_opt(2, code=TopKCodec(k=2), error_feedback=True)
    opt2.load_state_dict(sd)
    for n, v in opt2.ef_state.items():
        np.testing.assert_allclose(np.asarray(v).sum(axis=0), agg4[n],
                                   rtol=1e-5, atol=1e-7, err_msg=n)
        assert np.asarray(v).shape[0] == 2


def test_ef_resume_same_world_is_bitwise():
    """Interrupted-vs-uninterrupted EF trajectory equality (r3 VERDICT #6):
    with the per-rank residual restored exactly, save/load mid-run changes
    NOTHING — params, optimizer state, and the residual itself continue
    bitwise-identically to the uninterrupted run."""
    batches = _batches(4, 8, seed=13)
    straight = _mlp_opt(4, code=TopKCodec(k=2), error_feedback=True)
    for b in batches:
        straight.step(b)

    resumed = _mlp_opt(4, code=TopKCodec(k=2), error_feedback=True)
    for b in batches[:4]:
        resumed.step(b)
    sd = resumed.state_dict()
    fresh = _mlp_opt(4, code=TopKCodec(k=2), error_feedback=True)
    fresh.load_state_dict(sd)
    for b in batches[4:]:
        fresh.step(b)

    for n in straight.params:
        np.testing.assert_array_equal(
            np.asarray(straight.params[n]), np.asarray(fresh.params[n]),
            err_msg=f"params[{n}] diverged across save/resume")
    for n in straight.ef_state:
        np.testing.assert_array_equal(
            np.asarray(straight.ef_state[n]),
            np.asarray(fresh.ef_state[n]),
            err_msg=f"ef[{n}] diverged across save/resume")
    for n, st in straight.state.items():
        for k, v in st.items():
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(fresh.state[n][k]),
                err_msg=f"state[{n}][{k}] diverged across save/resume")


def test_cast_codec_cli_name_roundtrip():
    assert isinstance(get_codec("bf16", "cpu"), CastCodec)


def test_ef_and_ema_compose():
    """Both carried-extras at once: per-rank-sharded residual + replicated
    EMA in the same jitted step."""
    opt, batch = _regression_setup(2, code=TopKCodec(k=1),
                                   error_feedback=True, ema_decay=0.9)
    for _ in range(50):
        loss, _ = opt.step(batch)
    assert np.isfinite(loss)
    assert opt.ef_state is not None and opt.ema_params is not None
    assert opt.ef_state["w"].shape[0] == 2
    sd = opt.state_dict()
    assert sd["ef"] is not None and sd["ema"] is not None
