"""PS optimizer step tests — the L3 behavior contract
(`/root/reference/ps.py:53-193`): replicated params, per-rank grads on batch
shards, cross-rank **sum** (`ps.py:176`), identical update on every rank,
``(loss, metrics)`` return, name-uniqueness validation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import Adam, MPI_PS, SGD
from pytorch_ps_mpi_tpu.ops.codecs import QuantizeCodec, TopKCodec
from pytorch_ps_mpi_tpu.optim import rules
from pytorch_ps_mpi_tpu.utils.timing import STEP_METRIC_KEYS


def make_problem(seed=0, d_in=6, d_out=3):
    rng = np.random.RandomState(seed)
    params = [("w", rng.randn(d_in, d_out).astype(np.float32) * 0.1),
              ("b", np.zeros(d_out, np.float32))]
    X = rng.randn(32, d_in).astype(np.float32)
    Y = rng.randn(32, d_out).astype(np.float32)
    return params, {"x": X, "y": Y}


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def manual_summed_grads(params, batch, n_shards):
    """Reference semantics: each rank grads its shard's mean loss; d_p = sum."""
    total = {n: np.zeros_like(p) for n, p in params.items()}
    B = batch["x"].shape[0]
    per = B // n_shards
    for r in range(n_shards):
        shard = {k: v[r * per:(r + 1) * per] for k, v in batch.items()}
        g = jax.grad(loss_fn)(params, shard)
        for n in total:
            total[n] += np.asarray(g[n])
    return total


def test_step_sums_grads_across_ranks(mesh8):
    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh8)
    opt.compile_step(loss_fn)
    p_before = {n: np.asarray(p) for n, p in opt.params.items()}
    loss, data = opt.step(batch)

    d_p = manual_summed_grads(dict(named), batch, 8)
    for n, p0 in p_before.items():
        expected = p0 - 0.1 * d_p[n]
        np.testing.assert_allclose(np.asarray(opt.params[n]), expected,
                                   rtol=1e-5, atol=1e-6)
    assert isinstance(loss, float) and loss > 0
    for k in STEP_METRIC_KEYS:
        assert k in data
    assert data["msg_bytes"] > 0 and data["packaged_bytes"] > 0


def test_decompose_allreduce_matches_default(mesh8):
    """``decompose_allreduce=True`` (per-bucket reduce-scatter+all-gather,
    the identity-path overlap lowering) must train identically to the
    default combined all-reduce — same sum, different wire schedule."""
    named, batch = make_problem(seed=5)
    ref = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8)
    ref.compile_step(loss_fn)
    dec = SGD(named, lr=0.05, momentum=0.9, mesh=mesh8,
              decompose_allreduce=True)
    dec.compile_step(loss_fn)
    for _ in range(5):
        loss_r, _ = ref.step(batch)
        loss_d, _ = dec.step(batch)
    assert abs(loss_r - loss_d) < 1e-6 * max(1.0, abs(loss_r))
    for n in ref.params:
        np.testing.assert_allclose(np.asarray(dec.params[n]),
                                   np.asarray(ref.params[n]),
                                   rtol=1e-5, atol=1e-7)


def test_momentum_steps_match_sequential_rule(mesh8):
    named, batch = make_problem(seed=3)
    hyper = dict(lr=0.05, momentum=0.9, weight_decay=0.01)
    opt = SGD(named, mesh=mesh8, **hyper)
    opt.compile_step(loss_fn)

    # Shadow run of the pure update rule with manually summed grads.
    shadow = {n: jnp.asarray(p) for n, p in named}
    sstate = {n: rules.sgd_init(p) for n, p in shadow.items()}
    for _ in range(3):
        d_p = manual_summed_grads(
            {n: np.asarray(p) for n, p in shadow.items()}, batch, 8)
        for n in shadow:
            shadow[n], sstate[n] = rules.sgd_update(
                shadow[n], jnp.asarray(d_p[n]), sstate[n], **hyper)
        opt.step(batch)

    for n in shadow:
        np.testing.assert_allclose(np.asarray(opt.params[n]),
                                   np.asarray(shadow[n]),
                                   rtol=1e-4, atol=1e-5)


def test_adam_variant_runs(mesh8):
    named, batch = make_problem(seed=4)
    opt = Adam(named, lr=1e-2, mesh=mesh8)
    opt.compile_step(loss_fn)
    losses = [opt.step(batch)[0] for _ in range(5)]
    assert losses[-1] < losses[0]  # optimizing
    assert int(opt.state["w"]["step"]) == 5


@pytest.mark.parametrize("codec", [QuantizeCodec(8), TopKCodec(fraction=0.3)])
def test_codec_path_matches_manual_encode_decode_sum(mesh8, codec):
    """Lossy codecs apply per-rank BEFORE the sum (`ps.py:165-176`)."""
    named, batch = make_problem(seed=5)
    opt = SGD(named, lr=0.1, mesh=mesh8, code=codec)
    opt.compile_step(loss_fn)
    p_before = {n: np.asarray(p) for n, p in opt.params.items()}
    opt.step(batch)

    # Manual: per-rank grad -> encode -> decode -> sum -> sgd.
    B = batch["x"].shape[0]
    per = B // 8
    params_np = dict(named)
    d_p = {n: np.zeros_like(p) for n, p in params_np.items()}
    for r in range(8):
        shard = {k: v[r * per:(r + 1) * per] for k, v in batch.items()}
        g = jax.grad(loss_fn)(params_np, shard)
        for n in d_p:
            code = codec.encode(g[n])
            d_p[n] += np.asarray(codec.decode(
                code, shape=g[n].shape, dtype=jnp.float32))
    for n, p0 in p_before.items():
        expected = p0 - 0.1 * d_p[n]
        np.testing.assert_allclose(np.asarray(opt.params[n]), expected,
                                   rtol=1e-4, atol=1e-5)


def _aux_loss(params, aux, batch):
    return loss_fn(params, batch), {"seen": aux["seen"] + 1.0}


# (constructor arguments, compile_step arguments) of the fused step's
# feature sets; the metrics contract below holds for each of them.
_FEATURE_SETS = {
    "identity": (dict(momentum=0.9), {}),
    "quantize": (dict(code="quantize"), {}),
    "zero": (dict(momentum=0.9, zero=True), {}),
    "ef_ema": (dict(code=TopKCodec(fraction=0.5), error_feedback=True,
                    ema_decay=0.9), {}),
    "skip_nonfinite": (dict(skip_nonfinite=True), {}),
    "aux": ({}, dict(has_aux=True, aux={"seen": np.zeros((), np.float32)})),
}


@pytest.mark.parametrize("features", sorted(_FEATURE_SETS))
def test_step_metrics_contract(mesh8, features):
    """What `step()` hands back, whatever the step fuses: exactly the
    reference's keys plus ``nonfinite_skip`` (a blocking step reads the
    flag), every value a float, the compile of the first call under
    ``iallgather_prepare_time`` and every later dispatch under
    ``isend_time`` (`dispatch_ms_p50` reads it), and the phases the fused
    program does not time apart at 0.0."""
    kw, ckw = _FEATURE_SETS[features]
    named, batch = make_problem(seed=6)
    opt = SGD(named, lr=0.05, mesh=mesh8, **kw)
    opt.compile_step(_aux_loss if ckw else loss_fn, **ckw)
    first = opt.step(batch)[1]
    later = [opt.step(batch)[1] for _ in range(2)]
    for data in [first] + later:
        assert set(data) == set(STEP_METRIC_KEYS) | {"nonfinite_skip"}
        assert all(type(v) is float for v in data.values()), data
        for phase in ("code_wait", "decode_time", "optim_step_time"):
            assert data[phase] == 0.0
        assert data["nonfinite_skip"] == 0.0
        assert data["comm_wait"] > 0
        assert data["msg_bytes"] > 0 and data["packaged_bytes"] > 0
    assert first["iallgather_prepare_time"] > 0 and first["isend_time"] == 0
    for data in later:
        assert data["iallgather_prepare_time"] == 0 and data["isend_time"] > 0
    assert opt.timings == [first] + later
    if features == "quantize":
        assert first["packaged_bytes"] < first["msg_bytes"]


def test_aux_is_the_same_on_every_device_after_a_step(mesh8):
    """BatchNorm statistics come out of each rank's own batch shard; the
    step averages them over the mesh, so ``aux`` moves and stays one value
    on all eight devices."""
    from pytorch_ps_mpi_tpu.models import (build_model, make_classifier_loss,
                                           resnet18)

    model = resnet18(num_classes=10, small_inputs=True)
    params, aux = build_model(model, (1, 8, 8, 3))
    loss_fn_r, has_aux = make_classifier_loss(model, has_aux=bool(aux))
    assert has_aux
    rng = np.random.RandomState(0)
    batch = {"x": rng.randn(16, 8, 8, 3).astype(np.float32),
             "y": rng.randint(0, 10, 16).astype(np.int32)}
    opt = SGD(list(params.items()), lr=0.1, mesh=mesh8)
    opt.compile_step(loss_fn_r, has_aux=True, aux=aux)
    before = [np.asarray(v).copy() for v in jax.tree.leaves(opt.aux)]
    for _ in range(3):
        loss, _ = opt.step(batch)
    assert np.isfinite(loss)
    after = jax.tree.leaves(opt.aux)
    assert any(not np.allclose(a, np.asarray(b))
               for a, b in zip(before, after))
    for leaf in after:
        copies = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(copies) == 8
        for c in copies[1:]:
            np.testing.assert_array_equal(c, copies[0])


def test_dp_sp_mesh_trains_and_its_replicas_agree():
    """dp x sp with ring attention and the batch split over both axes: the
    gradients are averaged over ``sp`` and summed over ``ps``, five steps
    lower the loss, and the parameters are bit for bit the same on all
    eight devices afterwards."""
    import functools

    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)
    from pytorch_ps_mpi_tpu.parallel.mesh import make_dp_sp_mesh
    from pytorch_ps_mpi_tpu.parallel.ring_attention import ring_attention

    mesh = make_dp_sp_mesh(dp=4, sp=2)
    dense = TransformerLM(vocab_size=17, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_len=64)
    sharded = dense.copy(attn=functools.partial(ring_attention, axis="sp",
                                                causal=True))
    params = build_lm(dense, seq_len=8)
    opt = SGD(list(params.items()), lr=0.05, mesh=mesh,
              batch_spec=P("ps", "sp"))
    opt.compile_step(make_lm_loss(sharded))
    toks = np.random.RandomState(1).randint(0, 17, size=(8, 9))
    losses = [opt.step(lm_batch(toks))[0] for _ in range(5)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]
    assert opt.check_consensus() == {"ok": True, "mismatched": [],
                                     "first_leaf": None}


def test_duplicate_names_rejected(mesh8):
    """`ps.py:150-153` parity: names must be unique."""
    p = np.zeros((2,), np.float32)
    with pytest.raises(ValueError, match="unique"):
        MPI_PS([("a", p), ("a", p)], mesh=mesh8)


def test_unknown_hyper_rejected(mesh8):
    p = np.zeros((2,), np.float32)
    with pytest.raises(TypeError):
        SGD([("a", p)], mesh=mesh8, lr=0.1, betas=(0.9, 0.99))


@pytest.mark.parametrize("gone", ["profile", "use_mpi"])
def test_options_of_the_reference_that_mean_nothing_here_are_refused(
        mesh8, gone):
    """`profile=True` chose a second, phase-split step and `use_mpi` was
    taken and thrown away: both now fall among the hyperparameters and are
    refused by name, so a caller that still passes one is told."""
    p = np.zeros((2,), np.float32)
    with pytest.raises(TypeError, match=f"unexpected sgd hyperparameters:"
                                        f".*'{gone}'"):
        MPI_PS([("a", p)], mesh=mesh8, **{gone: True})


def test_unknown_optim_rejected(mesh8):
    p = np.zeros((2,), np.float32)
    with pytest.raises(ValueError, match="not supported"):
        MPI_PS([("a", p)], mesh=mesh8, optim="rmsprop")


def test_loss_decreases_multistep(mesh2):
    named, batch = make_problem(seed=7)
    opt = SGD(named, lr=0.02, momentum=0.9, mesh=mesh2)
    opt.compile_step(loss_fn)
    losses = [opt.step(batch)[0] for _ in range(20)]
    assert losses[-1] < 0.9 * losses[0]
    assert len(opt.timings) == 20


# -- counters on the aux channel, and the step's compiled text ---------------


def counting_loss(params, aux, batch):
    """An aux-style loss that counts: rows with a positive first feature."""
    del aux
    seen = jnp.sum(batch["x"][:, 0] > 0).astype(jnp.float32)
    with jax.named_scope("scope_under_test"):
        loss = loss_fn(params, batch)
    return loss, {"counters": {"positive_rows": seen[None]}}


@pytest.mark.parametrize("n_dev", [1, 8])
def test_counters_leave_the_step_unread_and_survive_donation(mesh8, n_dev):
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    mesh = mesh8 if n_dev == 8 else make_ps_mesh(devices=jax.devices()[:1])
    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh)
    opt.compile_step(counting_loss, has_aux=True,
                     aux={"counters": {"positive_rows": np.zeros(1, "f")}})
    counter_log().clear()
    for _ in range(4):          # each step donates the aux of the one before
        opt.step(batch, block=False)
    records = counter_log().records("MPI_PS.step")
    assert [r["step"] for r in records] == [0, 1, 2, 3]
    assert all(isinstance(r["values"]["positive_rows"], jax.Array)
               for r in records)
    # aux is averaged over the ranks, and so are the counters: on eight
    # chips each sees four rows, and the log holds the mean of their counts
    want = float(np.sum(batch["x"][:, 0] > 0)) / n_dev
    for r in records:           # the first is still readable after the last
        assert float(r["values"]["positive_rows"][0]) == pytest.approx(want)
    assert float(opt.aux["counters"]["positive_rows"][0]) \
        == pytest.approx(want)


def test_a_step_without_counters_logs_none(mesh8):
    from pytorch_ps_mpi_tpu.utils.timing import counter_log

    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh8)
    opt.compile_step(loss_fn)
    counter_log().clear()
    opt.step(batch)
    assert counter_log().records() == []


def test_the_step_compiles_once_and_registers_its_text(mesh8, caplog):
    """One compile a batch shape, ahead of the first call; the registry is
    given the compiled program's text and nothing that holds the optimizer;
    a batch of another shape gets a program of its own."""
    import gc
    import logging
    import weakref

    from pytorch_ps_mpi_tpu.utils.timing import in_scope, program_scopes

    named, batch = make_problem()
    opt = SGD(named, lr=0.1, mesh=mesh8)
    opt.compile_step(counting_loss, has_aux=True,
                     aux={"counters": {"positive_rows": np.zeros(1, "f")}})
    with jax.log_compiles(), caplog.at_level(logging.WARNING):
        for _ in range(3):
            opt.step(batch)
        compiles = [r for r in caplog.records
                    if "Finished XLA compilation of jit(spmd_step)"
                    in r.getMessage()]
    assert len(compiles) == 1 and len(opt._step_programs) == 1
    scopes = program_scopes("MPI_PS.step")
    assert any(in_scope(op_name, "scope_under_test")
               for op_name in scopes.values())
    half = {k: v[:len(v) // 2] for k, v in batch.items()}
    loss, _ = opt.step(half)
    assert np.isfinite(loss) and len(opt._step_programs) == 2
    gone = weakref.ref(opt)
    del opt
    gc.collect()
    assert gone() is None       # the registry does not keep the optimizer
