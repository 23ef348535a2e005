"""Checkpoint/resume: the oracle is bitwise-identical continuation —
a run that checkpoints and restores must match an uninterrupted run exactly
(the analogue of the reference's round-trip-equality test strategy, SURVEY §4,
applied to persistence instead of collectives)."""

from collections import OrderedDict

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import Adam, SGD, checkpoint
from pytorch_ps_mpi_tpu.async_ps import AsyncSGD


def _problem(seed=0):
    rng = np.random.RandomState(seed)
    params = OrderedDict(
        w=rng.randn(12, 4).astype(np.float32) * 0.1,
        b=np.zeros(4, np.float32))
    X = rng.randn(32, 12).astype(np.float32)
    Y = X @ rng.randn(12, 4).astype(np.float32)

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] + p["b"] - batch["y"]) ** 2)

    return params, {"x": X, "y": Y}, loss_fn


@pytest.mark.parametrize("cls,hyper", [
    (SGD, dict(lr=0.05, momentum=0.9)),
    (Adam, dict(lr=0.01, amsgrad=True)),
])
def test_resume_is_bitwise_identical(tmp_path, mesh8, cls, hyper):
    params, batch, loss_fn = _problem()
    path = tmp_path / "ckpt.psz"

    # Uninterrupted: 6 steps.
    ref = cls(list(params.items()), mesh=mesh8, **hyper)
    ref.compile_step(loss_fn)
    for _ in range(6):
        ref.step(batch)

    # Interrupted: 3 steps, checkpoint, fresh optimizer, restore, 3 more.
    a = cls(list(params.items()), mesh=mesh8, **hyper)
    a.compile_step(loss_fn)
    for _ in range(3):
        a.step(batch)
    checkpoint.save_optimizer(path, a, step=3, extra={"note": "mid-run"})

    b = cls(list(params.items()), mesh=mesh8, **hyper)
    b.compile_step(loss_fn)
    info = checkpoint.load_optimizer(path, b)
    assert info["step"] == 3
    assert info["extra"] == {"note": "mid-run"}
    for _ in range(3):
        b.step(batch)

    for n in ref.params:
        np.testing.assert_array_equal(np.asarray(ref.params[n]),
                                      np.asarray(b.params[n]), err_msg=n)
    # Optimizer state must match too (momentum buffers / Adam moments).
    import jax

    flat_ref = jax.tree_util.tree_leaves(ref.state)
    flat_b = jax.tree_util.tree_leaves(b.state)
    for x, y in zip(flat_ref, flat_b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_steps_completed_tracks_applied_updates(mesh8):
    """``steps_completed`` advances with each applied update — the counter
    an interrupt-triggered checkpoint records so the saved step count always
    matches the params it snapshots (r4 advisor: the loop counter lags one
    step when Ctrl-C lands inside step()'s blocking wait)."""
    params, batch, loss_fn = _problem()
    opt = SGD(list(params.items()), mesh=mesh8, lr=0.05, momentum=0.9)
    opt.compile_step(loss_fn)
    assert opt.steps_completed == 0
    for i in range(4):
        opt.step(batch)
        assert opt.steps_completed == i + 1


def test_save_optimizer_accepts_jax_array_leaves(tmp_path, mesh8):
    """The payload/metadata partition must route jax.Array leaves into the
    array payload (normalized to numpy), not the pickled metadata — which
    the restricted unpickler would refuse at load (r4 advisor)."""
    import jax

    params, batch, loss_fn = _problem()
    opt = SGD(list(params.items()), mesh=mesh8, lr=0.05, momentum=0.9)
    opt.compile_step(loss_fn)
    opt.step(batch)

    real_sd = opt.state_dict()

    class JaxLeafOpt:
        """state_dict with live jax.Array leaves (a future optimizer that
        skips the device_get/np.asarray conversion)."""

        def state_dict(self):
            sd = dict(real_sd)
            sd["params"] = {n: jnp.asarray(v)
                            for n, v in sd["params"].items()}
            assert any(isinstance(v, jax.Array)
                       and not isinstance(v, np.ndarray)
                       for v in sd["params"].values())
            return sd

    path = tmp_path / "jaxleaf.psz"
    checkpoint.save_optimizer(path, JaxLeafOpt(), step=1)
    arrays, meta = checkpoint.load(path, with_meta=True)
    assert "params" in arrays  # routed as payload, not metadata
    for n, v in real_sd["params"].items():
        np.testing.assert_array_equal(np.asarray(arrays["params"][n]),
                                      np.asarray(v), err_msg=n)


def test_state_dict_roundtrip_without_disk(mesh8):
    params, batch, loss_fn = _problem(1)
    opt = SGD(list(params.items()), lr=0.1, momentum=0.9, mesh=mesh8)
    opt.compile_step(loss_fn)
    opt.step(batch)
    sd = opt.state_dict()
    assert sd["optim"] == "sgd"
    assert set(sd["params"]) == {"w", "b"}
    # The snapshot is decoupled from the live optimizer both ways: leaves
    # are host COPIES (not views into donated device buffers), so mutating
    # the snapshot cannot corrupt the optimizer, and stepping the optimizer
    # (which recycles donated buffers) cannot mutate the snapshot.
    w_before = sd["params"]["w"].copy()
    sd["params"]["w"][:] = 0
    assert float(jnp.abs(opt.params["w"]).sum()) > 0
    sd2 = opt.state_dict()
    opt.step(batch)
    opt.step(batch)
    np.testing.assert_array_equal(sd2["params"]["w"], w_before)


def test_optim_mismatch_rejected(tmp_path, mesh8):
    params, batch, loss_fn = _problem(2)
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8)
    opt.compile_step(loss_fn)
    opt.step(batch)
    checkpoint.save_optimizer(tmp_path / "c.psz", opt)
    other = Adam(list(params.items()), mesh=mesh8)
    with pytest.raises(ValueError, match="optim"):
        checkpoint.load_optimizer(tmp_path / "c.psz", other)


def test_param_name_mismatch_rejected(tmp_path, mesh8):
    params, batch, loss_fn = _problem(3)
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8)
    checkpoint.save_optimizer(tmp_path / "c.psz", opt)
    renamed = OrderedDict(("x_" + n, p) for n, p in params.items())
    other = SGD(list(renamed.items()), lr=0.1, mesh=mesh8)
    with pytest.raises(ValueError, match="name mismatch"):
        checkpoint.load_optimizer(tmp_path / "c.psz", other)


def test_restored_hyper_takes_effect(tmp_path, mesh8):
    """lr is a trace-time constant; load_state_dict must rebuild the step."""
    params, batch, loss_fn = _problem(4)
    hot = SGD(list(params.items()), lr=0.5, mesh=mesh8)
    checkpoint.save_optimizer(tmp_path / "c.psz", hot)

    cold = SGD(list(params.items()), lr=1e-9, mesh=mesh8)
    cold.compile_step(loss_fn)
    before = np.asarray(cold.params["w"]).copy()
    checkpoint.load_optimizer(tmp_path / "c.psz", cold)
    cold.step(batch)
    delta = np.abs(np.asarray(cold.params["w"]) - before).max()
    assert delta > 1e-4  # lr=0.5 moved the weights; lr=1e-9 would not have


def test_async_ps_checkpoint_roundtrip(tmp_path):
    params, batch, loss_fn = _problem(5)
    opt = AsyncSGD(list(params.items()), lr=0.05, momentum=0.9, quota=1)
    opt.compile_step(loss_fn)
    hist = opt.run(lambda rank, it: batch, steps=3)
    assert len(hist["losses"]) == 3
    checkpoint.save_optimizer(tmp_path / "a.psz", opt, step=3)

    fresh = AsyncSGD(list(params.items()), lr=0.05, momentum=0.9, quota=1)
    fresh.compile_step(loss_fn)
    info = checkpoint.load_optimizer(tmp_path / "a.psz", fresh)
    assert info["step"] == 3
    for n in opt.params:
        np.testing.assert_array_equal(np.asarray(opt.params[n]),
                                      np.asarray(fresh.params[n]))


def test_corrupt_checkpoint_raises_typed_error(tmp_path, mesh8):
    """Truncated and bit-flipped checkpoint files must raise the one typed
    `CheckpointError` — never a garbage unpickle, a partial tree, or a
    random struct/pickle internal error the caller can't catch cleanly."""
    from pytorch_ps_mpi_tpu.utils.checkpoint import CheckpointError

    params, batch, loss_fn = _problem(6)
    opt = SGD(list(params.items()), lr=0.1, momentum=0.9, mesh=mesh8)
    opt.compile_step(loss_fn)
    opt.step(batch)
    path = tmp_path / "c.psz"
    checkpoint.save_optimizer(path, opt, step=1)
    blob = path.read_bytes()

    # Truncation at every region: inside the magic, the metadata, the
    # payload frames, and one byte short of complete.
    for cut in (2, 9, len(blob) // 3, len(blob) // 2, len(blob) - 1):
        bad = tmp_path / f"trunc{cut}.psz"
        bad.write_bytes(blob[:cut])
        with pytest.raises(CheckpointError):
            checkpoint.load(bad)
        with pytest.raises(CheckpointError):
            checkpoint.load_optimizer(bad, opt)

    # Bit flips: header, metadata pickle, and payload regions are all
    # covered by a magic check or a crc32, so every flip fails loudly.
    for off in (1, 6, 20, len(blob) // 2, len(blob) - 8):
        flipped = bytearray(blob)
        flipped[off] ^= 0x10
        bad = tmp_path / f"flip{off}.psz"
        bad.write_bytes(bytes(flipped))
        with pytest.raises(CheckpointError):
            checkpoint.load(bad)

    # CheckpointError subclasses ValueError: existing catch sites hold.
    assert issubclass(CheckpointError, ValueError)
    # A valid pytree checkpoint that is NOT an optimizer checkpoint is a
    # typed refusal too, not a KeyError.
    plain = tmp_path / "plain.psz"
    checkpoint.save(plain, {"w": np.ones(3, np.float32)})
    with pytest.raises(CheckpointError, match="not an optimizer"):
        checkpoint.load_optimizer(plain, opt)


def test_save_is_atomic_under_crash_mid_write(tmp_path, monkeypatch):
    """A crash between the tmp-file write and the rename must leave the
    previous checkpoint intact and no tmp litter behind (the tmp+rename
    contract `save` documents)."""
    import os as _os

    from pytorch_ps_mpi_tpu.utils import checkpoint as ckpt_mod

    path = tmp_path / "atomic.psz"
    ckpt_mod.save(path, {"w": np.arange(6, dtype=np.float32)})
    before = path.read_bytes()

    def crash_replace(src, dst):
        raise OSError("simulated crash during rename")

    monkeypatch.setattr(ckpt_mod.os, "replace", crash_replace)
    with pytest.raises(OSError, match="simulated crash"):
        ckpt_mod.save(path, {"w": np.zeros(6, np.float32)})
    monkeypatch.undo()

    assert path.read_bytes() == before  # old checkpoint untouched
    assert [f for f in _os.listdir(tmp_path)
            if f.endswith(".tmp")] == []  # tmp cleaned up
    tree = ckpt_mod.load(path)
    np.testing.assert_array_equal(tree["w"],
                                  np.arange(6, dtype=np.float32))


def test_resume_bitwise_with_zero_ef_ema_combo(tmp_path, mesh8):
    """The full feature stack at once — ZeRO-sharded state + error-feedback
    residual + EMA weights — must also continue bitwise across save/load
    on the same world size (each extra carries its own state tree through
    `state_dict`; a regression in any one of them breaks equality here)."""
    from pytorch_ps_mpi_tpu.ops.codecs import TopKCodec

    params, batch, loss_fn = _problem(seed=5)
    path = tmp_path / "combo.psz"
    mk = lambda: SGD(list(params.items()), mesh=mesh8, lr=0.05,
                     momentum=0.9, zero=True, ema_decay=0.9,
                     code=TopKCodec(k=3), error_feedback=True)

    ref = mk()
    ref.compile_step(loss_fn)
    for _ in range(6):
        ref.step(batch)

    a = mk()
    a.compile_step(loss_fn)
    for _ in range(3):
        a.step(batch)
    checkpoint.save_optimizer(path, a, step=3)

    b = mk()
    b.compile_step(loss_fn)
    assert checkpoint.load_optimizer(path, b)["step"] == 3
    for _ in range(3):
        b.step(batch)

    import jax

    for tag, t_ref, t_b in (
            ("params", ref.params, b.params),
            ("state", ref.state, b.state),
            ("ef", ref.ef_state, b.ef_state),
            ("ema", ref.ema_params, b.ema_params)):
        for x, y in zip(jax.tree_util.tree_leaves(t_ref),
                        jax.tree_util.tree_leaves(t_b)):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y),
                err_msg=f"{tag} diverged across zero+ef+ema resume")
