"""Gradient accumulation (compile_step(accum_steps=K)) and non-finite-skip
(MPI_PS(skip_nonfinite=True)).

Accumulation oracle: for mean losses, the average of K microbatch gradients
equals the full-shard gradient, so an accumulated step must match the
plain step to float tolerance — including momentum across steps, codecs,
and ZeRO sharding.  Skip oracle: a poisoned batch (NaN gradients on any
rank) must leave params/state/aux untouched and report the skip; training
resumes cleanly on the next good batch.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu import SGD, Adam
from pytorch_ps_mpi_tpu.ps import MPI_PS


def make_problem(seed=0):
    rng = np.random.RandomState(seed)
    named = [("w", (rng.randn(6, 4) * 0.3).astype(np.float32)),
             ("b", np.zeros(4, np.float32))]
    x = rng.randn(64, 6).astype(np.float32)
    w = rng.randn(6, 4).astype(np.float32)
    y = (x @ w).astype(np.float32)
    return named, {"x": x, "y": y}


def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] + params["b"] - batch["y"]) ** 2)


# ---------------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("accum", [2, 4])
@pytest.mark.parametrize("zero", [False, True])
def test_accum_matches_plain_step(mesh8, accum, zero):
    named, batch = make_problem()
    ref = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8, zero=zero)
    ref.compile_step(loss_fn)
    acc = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8, zero=zero)
    acc.compile_step(loss_fn, accum_steps=accum)

    for step in range(5):
        loss_r, _ = ref.step(batch)
        loss_a, _ = acc.step(batch)
        np.testing.assert_allclose(loss_a, loss_r, rtol=1e-5, atol=1e-6)
        for n in ref.params:
            np.testing.assert_allclose(
                np.asarray(acc.params[n]), np.asarray(ref.params[n]),
                rtol=1e-5, atol=1e-6, err_msg=f"{n} @ step {step}")


def test_accum_with_codec(mesh8):
    """Codec encode runs once on the accumulated gradient (not per
    microbatch), so lossy compression error matches the plain step's."""
    named, batch = make_problem(seed=1)
    ref = SGD(named, lr=0.05, mesh=mesh8, code="quantize")
    ref.compile_step(loss_fn)
    acc = SGD(named, lr=0.05, mesh=mesh8, code="quantize")
    acc.compile_step(loss_fn, accum_steps=4)
    for _ in range(3):
        ref.step(batch)
        acc.step(batch)
    for n in ref.params:
        np.testing.assert_allclose(np.asarray(acc.params[n]),
                                   np.asarray(ref.params[n]),
                                   rtol=1e-5, atol=1e-6, err_msg=n)


def test_accum_with_bn_aux(mesh8):
    """BN models: aux threads sequentially through the microbatch scan —
    semantics differ from one big batch (as in any framework), but stats
    must move and training must stay finite."""
    from pytorch_ps_mpi_tpu.models import (build_model, make_classifier_loss,
                                           resnet18)

    model = resnet18(num_classes=10, small_inputs=True)
    params, aux = build_model(model, (1, 8, 8, 3))
    lf, has_aux = make_classifier_loss(model, has_aux=bool(aux))
    rng = np.random.RandomState(2)
    batch = {"x": rng.randn(32, 8, 8, 3).astype(np.float32),
             "y": rng.randint(0, 10, 32).astype(np.int32)}

    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8)
    opt.compile_step(lf, has_aux=True, aux=aux, accum_steps=2)
    aux0 = [np.asarray(v).copy() for v in jax.tree.leaves(opt.aux)]
    losses = [opt.step(batch)[0] for _ in range(3)]
    assert np.isfinite(losses).all()
    moved = any(not np.allclose(a0, np.asarray(v))
                for a0, v in zip(aux0, jax.tree.leaves(opt.aux)))
    assert moved


def test_accum_indivisible_batch_rejected(mesh8):
    named, batch = make_problem()
    opt = SGD(named, lr=0.05, mesh=mesh8)
    opt.compile_step(loss_fn, accum_steps=3)  # 64/8 = 8 per rank, 8 % 3 != 0
    with pytest.raises(ValueError, match="microbatch"):
        opt.step(batch)
    with pytest.raises(ValueError, match="accum_steps"):
        opt.compile_step(loss_fn, accum_steps=0)


# ---------------------------------------------------------------------------
# non-finite skip
# ---------------------------------------------------------------------------


def scaled_loss(params, batch):
    base = jnp.mean((batch["x"] @ params["w"] + params["b"]
                     - batch["y"]) ** 2)
    return base * batch["scale"][0]


@pytest.mark.parametrize("zero", [False, True])
@pytest.mark.parametrize("code", [None, "blockq"])
def test_poisoned_batch_skips_update(mesh8, zero, code):
    named, batch = make_problem(seed=3)
    opt = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8, zero=zero,
              code=code, skip_nonfinite=True)
    opt.compile_step(scaled_loss)

    good = dict(batch, scale=np.ones(8, np.float32))
    # Poison ONE rank's shard: consensus must still skip everywhere.
    poison_scale = np.ones(8, np.float32)
    poison_scale[3] = np.nan
    poisoned = dict(batch, scale=poison_scale)

    opt.step(good)
    p_before = {n: np.asarray(p).copy() for n, p in opt.params.items()}
    s_before = jax.tree.map(lambda x: np.asarray(x).copy(), opt.state)

    loss, data = opt.step(poisoned)
    assert data["nonfinite_skip"] == 1.0
    for n in p_before:
        np.testing.assert_array_equal(np.asarray(opt.params[n]),
                                      p_before[n], err_msg=n)
    for a, b in zip(jax.tree.leaves(s_before),
                    jax.tree.leaves(opt.state)):
        np.testing.assert_array_equal(np.asarray(b), a)

    # Training resumes cleanly after the skip.
    loss2, data2 = opt.step(good)
    assert data2["nonfinite_skip"] == 0.0
    assert np.isfinite(loss2)
    assert any(not np.array_equal(np.asarray(opt.params[n]), p_before[n])
               for n in p_before)


def test_skip_matches_unskipped_on_clean_data(mesh8):
    """With only finite gradients the flag must never fire and the
    trajectory must be identical to skip_nonfinite=False."""
    named, batch = make_problem(seed=4)
    a = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8)
    a.compile_step(loss_fn)
    b = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8, skip_nonfinite=True)
    b.compile_step(loss_fn)
    for _ in range(5):
        la, _ = a.step(batch)
        lb, data = b.step(batch)
        assert data["nonfinite_skip"] == 0.0
        np.testing.assert_allclose(lb, la, rtol=1e-7, atol=0)
    for n in a.params:
        np.testing.assert_array_equal(np.asarray(b.params[n]),
                                      np.asarray(a.params[n]))


def test_nonblocking_step_keeps_timings_floats(mesh8):
    """block=False must not leak device arrays into the timings dicts
    (print_summary / JSON serialization expect host floats)."""
    named, batch = make_problem(seed=5)
    opt = SGD(named, lr=0.05, mesh=mesh8, skip_nonfinite=True)
    opt.compile_step(loss_fn)
    opt.step(batch, block=False)
    loss, data = opt.step(batch)  # blocking: flag reported
    assert data["nonfinite_skip"] == 0.0
    for d in opt.timings:
        for k, v in d.items():
            assert isinstance(v, float), (k, type(v))


def test_remat_matches_plain():
    """jax.checkpoint rematerialization must not change the math: losses
    and final params match the plain step to float noise."""
    import numpy as np
    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    rng = np.random.RandomState(0)
    params = init_mlp(rng, sizes=(12, 16, 4))
    mesh = make_ps_mesh(4)

    opts = []
    for remat in (False, True):
        opt = SGD(list(params.items()), lr=0.1, momentum=0.9, mesh=mesh)
        opt.compile_step(mlp_loss_fn, remat=remat)
        opts.append(opt)

    for step in range(5):
        b = {"x": rng.randn(8, 12).astype(np.float32),
             "y": rng.randint(0, 4, 8).astype(np.int32)}
        l0, _ = opts[0].step(b)
        l1, _ = opts[1].step(b)
        assert abs(l0 - l1) < 1e-6, (step, l0, l1)
    for n in opts[0].params:
        np.testing.assert_allclose(
            np.asarray(opts[0].params[n]), np.asarray(opts[1].params[n]),
            rtol=1e-6, atol=1e-7, err_msg=n)


def test_ema_matches_manual_recurrence():
    """ema_t = d*ema_{t-1} + (1-d)*params_t, folded from the recorded param
    trajectory — the in-step EMA must match exactly."""
    import numpy as np
    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    d = 0.9
    rng = np.random.RandomState(0)
    params = init_mlp(rng, sizes=(12, 16, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=make_ps_mesh(4),
              ema_decay=d)
    opt.compile_step(mlp_loss_fn)

    manual = {n: np.asarray(p).copy() for n, p in params.items()}
    for step in range(6):
        b = {"x": rng.randn(8, 12).astype(np.float32),
             "y": rng.randint(0, 4, 8).astype(np.int32)}
        opt.step(b)
        for n in manual:
            manual[n] = d * manual[n] + (1 - d) * np.asarray(opt.params[n])
    for n in manual:
        np.testing.assert_allclose(np.asarray(opt.ema_params[n]), manual[n],
                                   rtol=1e-6, atol=1e-7, err_msg=n)


def test_ema_checkpoint_roundtrip():
    import numpy as np
    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    rng = np.random.RandomState(1)
    params = init_mlp(rng, sizes=(12, 16, 4))

    def fresh():
        opt = SGD(list(params.items()), lr=0.1, mesh=make_ps_mesh(2),
                  ema_decay=0.95)
        opt.compile_step(mlp_loss_fn)
        return opt

    a = fresh()
    for _ in range(4):
        a.step({"x": rng.randn(8, 12).astype(np.float32),
                "y": rng.randint(0, 4, 8).astype(np.int32)})
    b = fresh()
    b.load_state_dict(a.state_dict())
    for n, v in a.ema_params.items():
        np.testing.assert_array_equal(np.asarray(v),
                                      np.asarray(b.ema_params[n]), err_msg=n)


def test_ema_skip_rolls_back():
    import numpy as np
    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    rng = np.random.RandomState(2)
    params = init_mlp(rng, sizes=(12, 16, 4))
    opt = SGD(list(params.items()), lr=0.1, mesh=make_ps_mesh(2),
              ema_decay=0.9, skip_nonfinite=True)
    opt.compile_step(mlp_loss_fn)
    good = {"x": rng.randn(8, 12).astype(np.float32),
            "y": rng.randint(0, 4, 8).astype(np.int32)}
    opt.step(good)
    before = {n: np.asarray(v).copy() for n, v in opt.ema_params.items()}
    bad = {"x": good["x"].copy(), "y": good["y"]}
    bad["x"][0, 0] = np.nan
    _, data = opt.step(bad)
    assert data["nonfinite_skip"] == 1.0
    for n, v in opt.ema_params.items():
        np.testing.assert_array_equal(np.asarray(v), before[n], err_msg=n)
