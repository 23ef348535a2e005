"""The lowering of `MPI_PS`'s bucketed gradient sum, chosen from what it sees
of its mesh (`MPI_PS._exchange_ring`): a ring of collective-permute hops on
several TPU chips, XLA's all-reduce, and the program the parent made,
everywhere else.  The ring itself is platform-blind, so a toy step is driven
through it here on four CPU devices; the chip's side is `gpt2m-sync-dp4`
(PERF.md §5 (4))."""

import hashlib
import inspect
import re

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import Adam, MPI_PS
from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
from pytorch_ps_mpi_tpu.utils.timing import (STEP_SCOPES, in_scope,
                                             program_scopes)

# Eight layers of one shape and a head: enough same-shaped leaves for the
# shared ring bodies to show, rows that divide by the ring's chunks (64).
LAYERS, WIDTH, OUT = 8, 128, 192


def make_problem(seed=0):
    rng = np.random.RandomState(seed)
    named = [(f"w{i}", (rng.randn(WIDTH, WIDTH) * 0.05).astype(np.float32))
             for i in range(LAYERS)]
    named += [(f"b{i}", np.zeros(WIDTH, np.float32)) for i in range(LAYERS)]
    named += [("head", (rng.randn(WIDTH, OUT) * 0.05).astype(np.float32))]
    return named, {"x": rng.randn(32, WIDTH).astype(np.float32),
                   "y": rng.randn(32, OUT).astype(np.float32)}


def loss_fn(params, batch):
    h = batch["x"]
    for i in range(LAYERS):
        h = jnp.tanh(h @ params[f"w{i}"] + params[f"b{i}"])
    return jnp.mean((h @ params["head"] - batch["y"]) ** 2)


def built(mesh, **kw):
    named, batch = make_problem()
    # buckets of 16 KiB: the 64 KiB matrices ride alone, the biases packed
    opt = Adam(named, lr=0.01, mesh=mesh, bucket_mb=1 / 64, **kw)
    opt.compile_step(loss_fn)
    return opt, batch


def lowered_text(opt, batch):
    """The StableHLO of the step for this batch, as `MPI_PS._step_program`
    lowers it."""
    args = (opt.params, opt.state, opt.aux, opt._shard_batch(batch))
    return opt._step_fn.lower(*args).as_text()


@pytest.fixture
def ring_taken(monkeypatch):
    """The ring whatever the platform: what `_exchange_ring` answers on
    several TPU chips."""
    monkeypatch.setattr(MPI_PS, "_exchange_ring", lambda self: True)


def test_the_cpu_mesh_and_one_device_keep_the_allreduce():
    """The selection as `MPI_PS` makes it from its own mesh: the CPU meshes
    of tier-1 keep today's lowering, and `__init__` has no argument for the
    other."""
    for n in (1, 4):
        opt, _ = built(make_ps_mesh(n))
        assert opt._exchange_ring() is False
    assert not any("ring" in p for p in
                   inspect.signature(MPI_PS.__init__).parameters)


class _Chip:
    platform = "tpu"


@pytest.mark.parametrize("kw, taken", [
    ({}, True), ({"zero": True}, False),
    ({"decompose_allreduce": True}, False), ({"sync_mode": "overlap"}, False),
    ({"sync_mode": "post"}, False), ({"code": "bf16"}, False)], ids=str)
def test_the_ring_is_the_default_exchange_on_several_tpu_chips_only(
        kw, taken):
    """`_exchange_ring` on the inputs of its condition, without a chip: the
    devices of a four-device mesh made to say ``tpu``."""
    opt, _ = built(make_ps_mesh(4), **kw)

    class Mesh:
        devices = np.array([_Chip() for _ in range(4)])

    opt.mesh = Mesh()
    assert opt._exchange_ring() is taken
    opt.world_size = 1
    assert opt._exchange_ring() is False


# sha256 of the lowered toy step at the parent commit (17297057), where
# `psum_tree_bucketed` had no ring: one device, and four CPU devices.
PARENT_TEXT = {
    1: "caeb97e2a92392d0a8ce33bc8a95b4dd"
       "11a35f3193cd2759fe6f802d9843d852",
    4: "961dd4ef130202833cc0295d7d194914"
       "e9fc8ba285ee05da2269f8b46074d923",
}


@pytest.mark.parametrize("devices", [1, 4])
def test_the_default_program_text_is_the_parents(devices):
    """One chip and the CPU keep the program they had, to the character:
    the lowered text of the toy step hashes as it did at the parent commit.
    (A change to the step that is meant to move every program moves these
    two hashes: compute them again with this file's `lowered_text`.)"""
    opt, batch = built(make_ps_mesh(devices))
    text = lowered_text(opt, batch)
    assert "collective_permute" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_TEXT[devices]


def test_a_step_through_the_ring_is_the_step_through_the_allreduce(
        ring_taken, monkeypatch):
    """Three steps of the toy problem on four devices through the ring
    against the same through `lax.psum`: the same losses and parameters to
    rounding (f32 sums in another order), and the replicas bitwise equal
    (`check_consensus`)."""
    ring, batch = built(make_ps_mesh(4))
    ring_losses = [ring.step(batch)[0] for _ in range(3)]
    assert "collective_permute" in lowered_text(ring, batch)
    assert ring.check_consensus()["ok"]
    monkeypatch.undo()
    psum, _ = built(make_ps_mesh(4))
    psum_losses = [psum.step(batch)[0] for _ in range(3)]
    np.testing.assert_allclose(ring_losses, psum_losses, rtol=1e-5)
    for n in psum.params:
        np.testing.assert_allclose(np.asarray(ring.params[n]),
                                   np.asarray(psum.params[n]),
                                   rtol=1e-3, atol=1e-5)


# An instruction of the compiled text whose opcode is a cross-rank
# collective (synchronous, or the start of an asynchronous pair).
COLLECTIVE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? (all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(", re.M)


def test_every_hop_is_under_the_exchange_scope(ring_taken):
    """`sync_exchange_ms_step` reads the operations under ``ps.exchange``
    and nothing else, so every collective of the compiled step, the ring's
    hops among them, carries that scope, and so does every instruction that
    came out of a ring's body (the slices, adds and writes round the
    hops)."""
    opt, batch = built(make_ps_mesh(4))
    opt.step(batch)
    (program,) = opt._step_programs.values()
    scopes = program_scopes("MPI_PS.step")
    found = COLLECTIVE.findall(program.as_text())
    assert sum(kind == "collective-permute" for _, kind in found) >= 12
    for name, kind in found:
        assert in_scope(scopes[name], STEP_SCOPES["exchange"]), (
            name, kind, scopes[name])
    ring = [op for op in scopes.values() if "_allreduce_ring" in op]
    assert len(ring) > 24
    assert all(in_scope(op, STEP_SCOPES["exchange"]) for op in ring)


def test_the_ring_keeps_the_program_small(ring_taken, monkeypatch):
    """The guard against the set-up cost coming back unnoticed: PR 38's ring
    was spelt out a leaf, 26,000 lines of StableHLO more than the all-reduce
    form's 22,000 at GPT-2 medium, and 7.8 s of every set-up went into
    tracing and lowering them.  With one shared body a shape the toy step's
    lowered text through the ring is within 1.6 times the all-reduce
    form's, and a model twice as deep adds calls, not bodies."""
    ring, batch = built(make_ps_mesh(4))
    with_ring = lowered_text(ring, batch)
    monkeypatch.undo()
    psum, _ = built(make_ps_mesh(4))
    without = lowered_text(psum, batch)
    lines = lambda t: t.count("\n")
    assert lines(with_ring) <= 1.6 * lines(without), (
        lines(with_ring), lines(without))
    # 8 matrices of one shape, the head and the packed biases: 3 bodies
    assert with_ring.count("func.func private @_allreduce_ring") == 3
    assert with_ring.count("call @_allreduce_ring") == LAYERS + 2
