"""The phases of the fused sync step: the `ps.*` scopes of `MPI_PS.step`'s
program read back out of its compiled text (`utils.timing.step_phase`), the
`sync.*` spans of its host path, and the bounded `timings`.

The strings `step_phase` matches are JAX's own (`transpose(`,
`rematted_computation`), so they are pinned against programs compiled here,
not against literals only."""

import re

import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import Adam, SGD
from pytorch_ps_mpi_tpu.ops.codecs import QuantizeCodec
from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
from pytorch_ps_mpi_tpu.utils.timing import (SPAN_LOG_CAPACITY,
                                             STEP_METRIC_KEYS, STEP_SCOPES,
                                             BoundedList, in_scope,
                                             program_fusions, program_scopes,
                                             span_log, step_phase)

PROGRAM = "MPI_PS.step"
# An instruction of the compiled text whose opcode is a cross-rank
# collective (synchronous, or the start of an asynchronous pair).
COLLECTIVE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? (?:all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\(", re.M)


@pytest.fixture(scope="module")
def mesh4():
    return make_ps_mesh(4)


def make_problem(seed=0, d_in=6, d_out=3):
    rng = np.random.RandomState(seed)
    params = [("w", rng.randn(d_in, d_out).astype(np.float32) * 0.1),
              ("b", np.zeros(d_out, np.float32))]
    return params, {"x": rng.randn(32, d_in).astype(np.float32),
                    "y": rng.randn(32, d_out).astype(np.float32)}


def loss_fn(params, batch):
    pred = jnp.tanh(batch["x"] @ params["w"]) + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def stepped(mesh, *, remat=False, **kw):
    """One optimizer after one step, its program's ``{instruction:
    op_name}`` and the program's text."""
    named, batch = make_problem()
    opt = Adam(named, lr=0.05, mesh=mesh, **kw)
    opt.compile_step(loss_fn, remat=remat)
    opt.step(batch)
    (program,) = opt._step_programs.values()
    return opt, program_scopes(PROGRAM), program.as_text()


def phases_of(scopes):
    return {step_phase(op_name) for op_name in scopes.values()}


# -- (b) the classifier on literal op_names ----------------------------------


@pytest.mark.parametrize("op_name, phase", [
    ("jit(step)/grad/jvp(mla)/dot_general", None),      # no scope of the step
    ("jit(step)/ps.grad/jvp(mla)/dot_general", "forward"),
    ("jit(step)/ps.grad/transpose(jvp(ps.grad))/jvp()/checkpoint/"
     "rematted_computation/mla/dot_general", "remat"),
    ("jit(step)/ps.grad/transpose(jvp(ps.grad))/jvp()/checkpoint/mla/"
     "dot_general", "backward"),
    ("jit(step)/ps.update/sub", "update"),
    ("jit(spmd_step)/shard_map/ps.grad/transpose(ps.grad)/jvp(ps.exchange)/"
     "psum", "exchange"),                # a step scope inside backward
    ("jit(step)/ps.update/ps.exchange/psum", "exchange"),
    ("jit(step)/ps.exchange/ps.update/mul", "exchange"),   # not by depth
    ("jit(step)/ps.encode/round", None),    # no such scope of the step's
    ("jit(step)/ps.gradient/mul", None),    # a whole component, not a prefix
    ("jit(step)/psXgrad/mul", None),        # the dot is a dot
    ("params['w']", None),
    ("", None),
], ids=lambda v: str(v)[-40:])
def test_step_phase_on_literal_op_names(op_name, phase):
    assert step_phase(op_name) == phase


def test_the_scope_names_cannot_collide_with_a_model_scope():
    assert list(STEP_SCOPES) == ["exchange", "update", "grad"]  # grad last
    assert all(name.startswith("ps.") for name in STEP_SCOPES.values())
    assert len(set(STEP_SCOPES.values())) == len(STEP_SCOPES)


# -- (a) the scopes in compiled programs -------------------------------------


# ids spelt out: an id made from a set's order differs between xdist workers
@pytest.mark.parametrize("kw", [
    pytest.param({}, id="default"),
    pytest.param({"bucket_mb": 0}, id="per-parameter"),
    pytest.param({"zero": True}, id="zero"),
    pytest.param({"zero": True, "bucket_mb": 0}, id="zero-per-parameter"),
    pytest.param({"code": QuantizeCodec(8)}, id="codec"),
    pytest.param({"code": QuantizeCodec(8), "bucket_mb": 0},
                 id="codec-per-parameter"),
    pytest.param({"clip_norm": 1.0, "skip_nonfinite": True,
                  "ema_decay": 0.9}, id="clip-guard-ema"),
    pytest.param({"zero": True, "clip_norm": 1.0, "skip_nonfinite": True},
                 id="zero-clip-guard"),
])
def test_every_phase_is_in_the_program_and_every_collective_is_exchange(
        mesh4, kw):
    _, scopes, text = stepped(mesh4, **kw)
    got = phases_of(scopes)
    assert {"forward", "backward", "update", "exchange"} <= got
    assert "remat" not in got
    collectives = COLLECTIVE.findall(text)
    assert collectives                      # four ranks: there is an exchange
    for name in collectives:
        assert step_phase(scopes[name]) == "exchange", (name, scopes[name])


@pytest.mark.parametrize("kw", [
    pytest.param({}, id="bucketed"),
    pytest.param({"bucket_mb": 0}, id="per-parameter")])
def test_remat_adds_the_phase_remat_and_nothing_else_has_it(mesh4, kw):
    _, plain, _ = stepped(mesh4, **kw)
    _, again, _ = stepped(mesh4, remat=True, **kw)
    assert "remat" not in phases_of(plain)
    assert "remat" in phases_of(again)
    # the same forward work, once under jvp and once more inside backward
    ends = lambda scopes, phase: {
        op.rsplit("/", 1)[-1] for op in scopes.values()
        if step_phase(op) == phase}
    assert "tanh" in ends(plain, "forward") and "tanh" in ends(again, "remat")
    assert "backward" in phases_of(again)
    # JAX's own words, as the classifier spells them
    assert any("rematted_computation" in op for op in again.values())
    assert any("transpose(" in op for op in plain.values())


def test_program_fusions_names_what_xla_fused_into_each_fusion(mesh4):
    """A trace shows a fusion as one operation under its root's name; the
    compiled text says what else is inside (`program_fusions`), which is how
    `sync_update_fused_ms_step` finds an optimizer's rule that rides in a
    backward fusion."""
    _, scopes, text = stepped(mesh4)
    fusions = program_fusions(PROGRAM)
    in_text = re.findall(
        r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = [^\n]*? fusion\(", text, re.M)
    assert in_text and sorted(in_text) == sorted(fusions)
    bodies = [body for body in fusions.values() if body]
    assert bodies and all(n in scopes for body in bodies for n in body)
    # a fused instruction is in no other fusion, and is no fusion itself
    held = [n for body in bodies for n in body]
    assert len(held) == len(set(held)) and not set(held) & set(fusions)
    assert {step_phase(scopes[n]) for n in held} - {None}
    assert program_fusions("no such program") is None


def test_accumulation_scans_the_gradient_scope(mesh4):
    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh4)
    opt.compile_step(loss_fn, accum_steps=2)
    opt.step(batch)
    scopes = program_scopes(PROGRAM)
    assert {"forward", "backward", "update", "exchange"} \
        <= phases_of(scopes)


def test_aux_statistics_are_averaged_under_exchange(mesh4):
    named, batch = make_problem()

    def aux_loss(params, aux, batch):
        return loss_fn(params, batch), {"seen": aux["seen"] + 1.0}

    opt = SGD(named, lr=0.1, mesh=mesh4)
    opt.compile_step(aux_loss, has_aux=True,
                     aux={"seen": np.zeros((), np.float32)})
    opt.step(batch)
    (program,) = opt._step_programs.values()
    scopes = program_scopes(PROGRAM)
    for name in COLLECTIVE.findall(program.as_text()):
        assert step_phase(scopes[name]) == "exchange"
    assert {"forward", "backward"} <= phases_of(scopes)


def test_make_lm_loss_has_head_loss_in_forward_and_backward(mesh4):
    from lm_helpers import VOCAB, toy_tokens
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)

    model = TransformerLM(vocab_size=VOCAB, d_model=16, n_heads=2,
                          n_layers=1, d_ff=32, max_len=16)
    opt = SGD(list(build_lm(model, 8).items()), lr=0.1, mesh=mesh4)
    opt.compile_step(make_lm_loss(model))
    opt.step(lm_batch(toy_tokens(4, 8)))
    head = {step_phase(op) for op in program_scopes(PROGRAM).values()
            if in_scope(op, "head_loss")}
    assert {"forward", "backward"} <= head
    assert head <= {"forward", "backward"}      # nothing of the update
    # the head's matrix product and the softmax are inside, the blocks not
    inside = [op for op in program_scopes(PROGRAM).values()
              if in_scope(op, "head_loss")]
    assert any("lm_head" in op for op in inside)
    assert any("log_softmax" in op or "reduce_max" in op or "exp" in op
               for op in inside)
    assert not any("block_0" in op for op in inside)


def test_a_step_with_other_scopes_is_not_served_from_the_compile_cache(
        mesh4, monkeypatch):
    """JAX's persistent cache (on in this suite) by default keys a program
    without its scope names, so a step that differs from a cached one in a
    scope's name alone would come back with the cached one's `op_name`s;
    `utils.compile_cache.configure_compile_cache` (called by `conftest.py`,
    as by every entry point) puts the names into the key."""
    _, before, _ = stepped(mesh4)                # in the cache from here on
    assert any(in_scope(op, "ps.update") for op in before.values())
    monkeypatch.setitem(STEP_SCOPES, "update", "ps.update_renamed")
    _, after, _ = stepped(mesh4)                 # the same operations
    assert any(in_scope(op, "ps.update_renamed") for op in after.values())
    assert not any(in_scope(op, "ps.update") for op in after.values())


# -- (c) the host path's spans -----------------------------------------------


@pytest.fixture
def log():
    span_log().clear()
    yield span_log()
    span_log().clear()


def test_one_sync_step_span_a_call_and_the_dict_is_read_off_its_children(
        mesh4, log, monkeypatch):
    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh4)
    calls = []
    static = type(opt)._static_byte_metrics
    monkeypatch.setattr(type(opt), "_static_byte_metrics",
                        lambda self: calls.append(1) or static(self))
    opt.compile_step(loss_fn)
    datas = [opt.step(batch, block=(i != 2))[1] for i in range(4)]
    assert len(calls) == 1              # two constants, worked out once
    assert all(d["msg_bytes"] == datas[0]["msg_bytes"] > 0 for d in datas)
    assert all(set(STEP_METRIC_KEYS) <= set(d) for d in datas)

    steps = log.records("sync.step")
    assert [s["step"] for s in steps] == [0, 1, 2, 3]
    assert [s["block"] for s in steps] == [True, True, False, True]
    assert all(s["parent"] is None for s in steps)
    for i, (step, data) in enumerate(zip(steps, datas)):
        kids = {r["name"]: r for r in log.records()
                if r["parent"] == step["id"]}
        want = {"sync.shard_batch", "sync.dispatch"} | (
            {"sync.block"} if step["block"] else set())
        assert set(kids) == want
        dispatch = kids["sync.dispatch"]
        seconds = dispatch["end"] - dispatch["start"]
        assert kids["sync.shard_batch"]["end"] <= dispatch["start"]
        if i == 0:      # the call that compiled
            assert dispatch["compiled"] is True
            assert data["iallgather_prepare_time"] == seconds
            assert data["isend_time"] == 0.0
        else:
            assert "compiled" not in dispatch
            assert data["isend_time"] == seconds
            assert data["iallgather_prepare_time"] == 0.0
        if step["block"]:
            wait = kids["sync.block"]
            assert data["comm_wait"] == wait["end"] - wait["start"]
            assert dispatch["end"] <= wait["start"]
        else:
            assert data["comm_wait"] == 0.0
            assert "nonfinite_skip" not in data
    assert list(opt.timings) == datas


def test_the_consensus_check_runs_inside_sync_step(mesh4, log):
    """No span of its own (no metric reads one): a firing check is time of
    its step's `sync.step`, after the children."""
    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh4, consensus_every=2)
    opt.compile_step(loss_fn)
    datas = [opt.step(batch)[1] for _ in range(4)]
    assert ["sdc_mismatch" in d for d in datas] == [False, True, False, True]
    assert opt.fault_stats["sdc_checks"] == 2
    steps = log.records("sync.step")
    assert len(steps) == 4
    assert {r["name"] for r in log.records() if r["parent"] is not None} \
        == {"sync.shard_batch", "sync.dispatch", "sync.block"}


def test_a_new_batch_shape_compiles_again_and_says_so(mesh4, log):
    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh4)
    opt.compile_step(loss_fn)
    opt.step(batch)
    opt.step(batch)
    _, data = opt.step({k: v[:16] for k, v in batch.items()})
    compiled = [r.get("compiled", False)
                for r in log.records("sync.dispatch")]
    assert compiled == [True, False, True]
    assert data["iallgather_prepare_time"] > 0 and data["isend_time"] == 0.0


# -- the bounded timings ------------------------------------------------------


def test_bounded_list_is_a_list_until_it_is_full(monkeypatch):
    from pytorch_ps_mpi_tpu.utils import timing
    monkeypatch.setattr(timing, "SPAN_LOG_CAPACITY", 32)
    t = BoundedList()
    assert t == []
    for i in range(32):
        t.append({"i": i})
    assert len(t) == 32
    assert t[30:] == [{"i": 30}, {"i": 31}] and t[-1]["i"] == 31
    t.append({"i": 32})                 # full: the oldest sixteenth goes
    assert len(t) == 31
    assert t[0]["i"] == 2 and t[-1]["i"] == 32
    for i in range(33, 1000):
        t.append({"i": i})
    assert len(t) <= 32 and t[-1]["i"] == 999
    assert [r["i"] for r in t] == list(range(1000 - len(t), 1000))


def test_the_optimizers_keep_their_timings_bounded(mesh4):
    from pytorch_ps_mpi_tpu import AsyncSGD
    named, _ = make_problem()
    assert SPAN_LOG_CAPACITY == 65536
    assert isinstance(SGD(named, lr=0.1, mesh=mesh4).timings, BoundedList)
    assert isinstance(AsyncSGD(named, lr=0.1).timings, BoundedList)
