"""ZeRO-style sharded optimizer state (`MPI_PS(zero=True)`).

Oracle: replicated-state training on the same mesh/data — zero mode runs
the identical update math on per-rank chunks (reduce-scatter in, all-gather
out), so parameters must match the replicated run to float tolerance at
every step, for SGD and Adam, even/uneven param sizes, identity and codec
paths.  State memory must actually shard (leading world dim), and
checkpoints must interchange with replicated mode (world-size-independent
full buffers on disk).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from pytorch_ps_mpi_tpu import Adam, SGD
from pytorch_ps_mpi_tpu.ps import MPI_PS


def make_problem(seed=0, sizes=((12, 7), (7,), (5, 3), (10,))):
    """Param sizes chosen to exercise padding: 84, 7, 15, 10 elements on an
    8-rank mesh all need zero-pad to a multiple of 8."""
    rng = np.random.RandomState(seed)
    named = [(f"p{i}", (rng.randn(*s) * 0.3).astype(np.float32))
             for i, s in enumerate(sizes)]
    x = rng.randn(64, 12).astype(np.float32)
    w = rng.randn(12, 7).astype(np.float32)
    y = (x @ w).astype(np.float32)
    return named, {"x": x, "y": y}


def loss_fn(params, batch):
    h = batch["x"] @ params["p0"] + params["p1"]
    pred = jax.nn.relu(h)
    reg = sum(jnp.sum(params[n] ** 2) for n in ("p2", "p3"))
    return jnp.mean((pred - batch["y"]) ** 2) + 1e-3 * reg


@pytest.mark.parametrize("opt_cls,hyper", [
    (SGD, dict(lr=0.05, momentum=0.9, weight_decay=1e-4)),
    (SGD, dict(lr=0.05, momentum=0.9, nesterov=True)),
    (Adam, dict(lr=2e-3, amsgrad=True)),
])
def test_zero_matches_replicated(mesh8, opt_cls, hyper):
    named, batch = make_problem()
    ref = opt_cls(named, mesh=mesh8, **hyper)
    ref.compile_step(loss_fn)
    zopt = opt_cls(named, mesh=mesh8, zero=True, **hyper)
    zopt.compile_step(loss_fn)

    for step in range(6):
        loss_r, _ = ref.step(batch)
        loss_z, _ = zopt.step(batch)
        np.testing.assert_allclose(loss_z, loss_r, rtol=1e-6, atol=1e-7)
        for n in ref.params:
            np.testing.assert_allclose(
                np.asarray(zopt.params[n]), np.asarray(ref.params[n]),
                rtol=2e-6, atol=1e-7, err_msg=f"{n} @ step {step}")


def test_zero_with_codec_matches_replicated_codec(mesh8):
    named, batch = make_problem(seed=1)
    ref = SGD(named, mesh=mesh8, lr=0.05, momentum=0.9, code="quantize")
    ref.compile_step(loss_fn)
    zopt = SGD(named, mesh=mesh8, lr=0.05, momentum=0.9, code="quantize",
               zero=True)
    zopt.compile_step(loss_fn)
    for _ in range(4):
        ref.step(batch)
        zopt.step(batch)
    for n in ref.params:
        np.testing.assert_allclose(
            np.asarray(zopt.params[n]), np.asarray(ref.params[n]),
            rtol=2e-6, atol=1e-7, err_msg=n)


@pytest.mark.parametrize("code", [None, "quantize"])
def test_zero_state_is_actually_sharded(mesh8, code):
    """Both ways into the chunks: the identity path's reduce-scatter and a
    codec's decode-sum followed by the owner's slice."""
    named, batch = make_problem(seed=2)
    zopt = Adam(named, mesh=mesh8, lr=1e-3, zero=True, code=code)
    zopt.compile_step(loss_fn)
    zopt.step(batch)
    for n, p in zopt.params.items():
        sz = int(np.prod(p.shape))
        chunk = -(-sz // 8)
        st = zopt.state[n]
        for key in ("exp_avg", "exp_avg_sq"):
            leaf = st[key]
            assert leaf.shape == (8, chunk), (n, key, leaf.shape)
            # Each rank's addressable shard is one (1, chunk) row — the
            # world_size memory saving is real, not a replicated reshape.
            shard_shapes = {s.data.shape for s in leaf.addressable_shards}
            assert shard_shapes == {(1, chunk)}, shard_shapes
        assert st["step"].shape == ()  # scalar stays replicated


def test_zero_checkpoint_interchanges_with_replicated(tmp_path, mesh8):
    from pytorch_ps_mpi_tpu.utils import checkpoint

    named, batch = make_problem(seed=3)
    zopt = SGD(named, mesh=mesh8, lr=0.05, momentum=0.9, zero=True)
    zopt.compile_step(loss_fn)
    for _ in range(3):
        zopt.step(batch)
    checkpoint.save_optimizer(tmp_path / "z.psz", zopt, step=3)

    # zero -> replicated
    rep = SGD(named, mesh=mesh8, lr=0.05, momentum=0.9)
    rep.compile_step(loss_fn)
    checkpoint.load_optimizer(tmp_path / "z.psz", rep)
    for n in zopt.params:
        np.testing.assert_array_equal(np.asarray(rep.params[n]),
                                      np.asarray(zopt.params[n]))
        np.testing.assert_array_equal(
            np.asarray(rep.state[n]["momentum_buffer"]),
            zopt._dechunk_state(zopt.state)[n]["momentum_buffer"])

    # replicated -> zero, then both trajectories stay identical
    z2 = SGD(named, mesh=mesh8, lr=0.05, momentum=0.9, zero=True)
    z2.compile_step(loss_fn)
    checkpoint.save_optimizer(tmp_path / "r.psz", rep, step=3)
    checkpoint.load_optimizer(tmp_path / "r.psz", z2)
    loss_a, _ = rep.step(batch)
    loss_b, _ = z2.step(batch)
    np.testing.assert_allclose(loss_b, loss_a, rtol=1e-6, atol=1e-7)
    for n in rep.params:
        np.testing.assert_allclose(np.asarray(z2.params[n]),
                                   np.asarray(rep.params[n]),
                                   rtol=2e-6, atol=1e-7, err_msg=n)


def test_zero_on_dp_sp_mesh():
    """ZeRO shards over the data axes while extra (sp) axes stay replicated:
    training matches the replicated-state run on the same 2-D mesh."""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.parallel.mesh import make_dp_sp_mesh
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)

    mesh = make_dp_sp_mesh(dp=4, sp=2)
    dense = TransformerLM(vocab_size=17, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_len=64)
    params = build_lm(dense, seq_len=8)
    lf = make_lm_loss(dense)
    toks = np.random.RandomState(5).randint(0, 17, size=(8, 9))

    ref = SGD(list(params.items()), lr=0.05, mesh=mesh,
              batch_spec=P("ps", "sp"))
    ref.compile_step(lf)
    zopt = SGD(list(params.items()), lr=0.05, mesh=mesh, zero=True,
               batch_spec=P("ps", "sp"))
    zopt.compile_step(lf)
    for _ in range(4):
        loss_r, _ = ref.step(lm_batch(toks))
        loss_z, _ = zopt.step(lm_batch(toks))
        np.testing.assert_allclose(loss_z, loss_r, rtol=1e-5, atol=1e-6)
    for n in ref.params:
        np.testing.assert_allclose(np.asarray(zopt.params[n]),
                                   np.asarray(ref.params[n]),
                                   rtol=1e-5, atol=1e-6, err_msg=n)
