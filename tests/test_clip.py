"""Global-norm gradient clipping (MPI_PS(clip_norm=C)).

Oracles: a manual NumPy reconstruction of clip(sum-of-shard-grads) → SGD,
replicated-vs-ZeRO equality (chunked sq-sums psum to the same global
norm), profile-mode phase parity, and the no-op regime (clip far above
the norm) matching unclipped training exactly."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.ps import MPI_PS


def make_problem(seed=0):
    rng = np.random.RandomState(seed)
    named = [("w", (rng.randn(6, 4) * 0.5).astype(np.float32)),
             ("b", np.zeros(4, np.float32))]
    x = rng.randn(64, 6).astype(np.float32)
    y = (x @ rng.randn(6, 4) * 3.0).astype(np.float32)  # big targets → big grads
    return named, {"x": x, "y": y}


def loss_fn(params, batch):
    return jnp.mean((batch["x"] @ params["w"] + params["b"] - batch["y"]) ** 2)


def manual_clipped_step(named, batch, lr, clip, world=8):
    """NumPy oracle: sum of per-shard grads, global-norm clip, plain SGD."""
    params = {n: p.copy() for n, p in named}
    per = batch["x"].shape[0] // world
    gsum = {n: np.zeros_like(p) for n, p in params.items()}
    for r in range(world):
        shard = {k: v[r * per:(r + 1) * per] for k, v in batch.items()}
        g = jax.grad(loss_fn)(params, shard)
        for n in gsum:
            gsum[n] += np.asarray(g[n])
    norm = np.sqrt(sum(np.sum(np.square(g)) for g in gsum.values()))
    scale = min(1.0, clip / (norm + 1e-6))
    return {n: params[n] - lr * scale * gsum[n] for n in params}, norm


@pytest.mark.parametrize("zero", [False, True])
def test_clip_matches_manual_oracle(mesh8, zero):
    named, batch = make_problem()
    clip = 1.5
    opt = SGD(named, lr=0.05, mesh=mesh8, zero=zero, clip_norm=clip)
    opt.compile_step(loss_fn)
    opt.step(batch)

    want, norm = manual_clipped_step(named, batch, lr=0.05, clip=clip)
    assert norm > clip  # the clip actually engaged
    for n in want:
        np.testing.assert_allclose(np.asarray(opt.params[n]), want[n],
                                   rtol=2e-5, atol=1e-6, err_msg=n)


def test_zero_clip_matches_replicated_clip(mesh8):
    named, batch = make_problem(seed=1)
    a = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8, clip_norm=2.0)
    a.compile_step(loss_fn)
    b = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8, clip_norm=2.0,
            zero=True)
    b.compile_step(loss_fn)
    for _ in range(4):
        a.step(batch)
        b.step(batch)
    for n in a.params:
        np.testing.assert_allclose(np.asarray(b.params[n]),
                                   np.asarray(a.params[n]),
                                   rtol=2e-6, atol=1e-7, err_msg=n)


def test_huge_clip_is_noop(mesh8):
    named, batch = make_problem(seed=2)
    a = SGD(named, lr=0.05, mesh=mesh8)
    a.compile_step(loss_fn)
    b = SGD(named, lr=0.05, mesh=mesh8, clip_norm=1e9)
    b.compile_step(loss_fn)
    for _ in range(3):
        a.step(batch)
        b.step(batch)
    for n in a.params:
        np.testing.assert_allclose(np.asarray(b.params[n]),
                                   np.asarray(a.params[n]),
                                   rtol=1e-6, atol=1e-7, err_msg=n)


def test_invalid_clip_rejected(mesh8):
    named, _ = make_problem()
    for bad in (0.0, -1.0, float("nan")):
        with pytest.raises(ValueError, match="positive"):
            MPI_PS(named, mesh=mesh8, clip_norm=bad)


def test_cli_clip_rejected_on_async_paths():
    from pytorch_ps_mpi_tpu import train

    with pytest.raises(SystemExit, match="sync PS only"):
        train.main(["--model", "mlp", "--clip-norm", "1.0", "--async-ps",
                    "--steps", "1"])
