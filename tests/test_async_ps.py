"""Async PS (AsySG-InCon) tests — the host-driven realization of the
reference's README pseudo-code (`/root/reference/README.md:56-77`): quota'd
gradient receipt, sum-then-step, inconsistent-read parameter publication.

Workers are virtual CPU devices driven by host threads; the tests exercise the
real async machinery (thread-dispatched jitted programs, cross-device
transfers, the unlocked publish/snapshot surface)."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu import AsyncAdam, AsyncPS, AsyncSGD
from pytorch_ps_mpi_tpu.async_ps import dataset_batch_fn
from pytorch_ps_mpi_tpu.ops.codecs import QuantizeCodec, TopKCodec
from pytorch_ps_mpi_tpu.optim import rules


def make_problem(seed=0, d_in=6, d_out=3, n=256):
    rng = np.random.RandomState(seed)
    w_true = rng.randn(d_in, d_out).astype(np.float32)
    X = rng.randn(n, d_in).astype(np.float32)
    Y = (X @ w_true + 0.01 * rng.randn(n, d_out)).astype(np.float32)
    params = [("w", rng.randn(d_in, d_out).astype(np.float32) * 0.1),
              ("b", np.zeros(d_out, np.float32))]
    return params, X, Y


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return jnp.mean((pred - batch["y"]) ** 2)


def test_async_converges_multiworker():
    named, X, Y = make_problem()
    opt = AsyncSGD(named, lr=0.05, quota=2)
    assert opt.num_workers >= 1
    opt.compile_step(loss_fn)
    hist = opt.run(dataset_batch_fn(X, Y, 32), steps=60)

    assert len(hist["losses"]) == 60
    assert hist["grads_consumed"] == 60 * 2
    # Noisy async trajectory: compare smoothed start vs end.
    assert np.mean(hist["losses"][-10:]) < 0.5 * np.mean(hist["losses"][:5])
    assert all(s >= 0 for s in hist["staleness"])
    assert hist["versions"][-1] == 60
    assert len(opt.timings) == 60
    assert opt.timings[0]["msg_bytes"] > 0


def test_run_leaves_spans_and_timings_is_a_view_of_them():
    """Every boundary of the in-process loop is one span of the process-wide
    log; `timings` reads the same clock reads; `history` is as it was."""
    from pytorch_ps_mpi_tpu.utils.timing import span_log

    named, X, Y = make_problem(seed=5)
    opt = AsyncSGD(named, lr=0.05, quota=1, devices=[jax.devices()[0]])
    opt.compile_step(loss_fn)
    steps = 6
    span_log().clear()
    hist = opt.run(dataset_batch_fn(X, Y, 16, seed=5), steps=steps)
    log = span_log().records()
    assert span_log().dropped == 0

    updates = [r for r in log if r["name"] == "async.update"]
    assert [u["update"] for u in updates] == list(range(steps))
    assert {u["thread"] for u in updates} == {"MainThread"}
    for i, u in enumerate(updates):
        children = sorted((r for r in log if r["parent"] == u["id"]),
                          key=lambda r: r["start"])
        assert [c["name"] for c in children] == [
            "async.fill", "async.stack", "async.apply", "async.publish",
            "async.read_loss"]
        assert all(u["start"] <= c["start"] <= c["end"] <= u["end"]
                   for c in children)
        fill, stack, apply, publish, _ = children
        assert fill["n"] == 1 and fill["ranks"] == [0]
        assert fill["staleness"] == hist["staleness"][i]
        assert publish["version"] == hist["versions"][i]
        # one measurement, two views: exactly the same clock reads
        t = opt.timings[i]
        assert t["comm_wait"] == fill["end"] - fill["start"]
        assert t["optim_step_time"] == (stack["end"] - stack["start"]) \
            + (apply["end"] - apply["start"])
        assert t["isend_time"] == publish["end"] - publish["start"]
        assert t["msg_bytes"] > 0

    iters = [r for r in log if r["name"] == "async.worker_iter"]
    assert len(iters) >= steps
    assert {r["thread"] for r in iters} == {"async-ps-worker-0"}
    assert [r["it"] for r in iters] == list(range(len(iters)))
    assert all(r["rank"] == 0 and r["parent"] is None for r in iters)
    # versions only grow, and a gradient the PS used was read no later; only
    # the iteration that `stop` caught waiting for its batch read none
    assert all("version" in r for r in iters[:-1])
    versions = [r["version"] for r in iters if "version" in r]
    assert versions == sorted(versions)
    first = sorted((r for r in log if r["parent"] == iters[0]["id"]),
                   key=lambda r: r["start"])
    assert [c["name"] for c in first] == [
        "async.await_batch", "async.snapshot", "async.put_batch",
        "async.grad", "async.send", "async.enqueue"]
    assert first[0]["it"] == 0 and first[0]["ready"] in (True, False)
    assert first[-1]["retries"] >= 0
    assert all(c["thread"] == "async-ps-worker-0" for c in first)
    # the draws are the drawer thread's, one iteration ahead, in order
    draws = [r for r in log if r["name"] == "async.draw"]
    assert {r["thread"] for r in draws} == {"async-ps-worker-0-draw"}
    assert [r["it"] for r in draws] == list(range(len(draws)))
    assert all(r["rank"] == 0 and r["parent"] is None for r in draws)
    assert len(iters) <= len(draws) <= len(iters) + 2

    assert set(hist) == {"losses", "staleness", "versions", "contributors",
                         "grads_consumed", "wall_time", "fault_stats"}
    assert all(len(hist[k]) == steps for k in
               ("losses", "staleness", "versions", "contributors"))
    assert hist["grads_consumed"] == steps and len(opt.timings) == steps
    assert set(opt.timings[0]) == {"comm_wait", "optim_step_time",
                                   "isend_time", "msg_bytes"}


def test_async_quota_one_fully_async():
    """quota=1: update on every arriving grad.  With W workers the gradient
    delay is O(W) updates (each update drains 1 of W outstanding grads) — the
    AsySG regime where the step size must shrink with staleness, so the test
    uses a small momentum-free lr."""
    named, X, Y = make_problem(seed=1)
    opt = AsyncSGD(named, lr=0.01, quota=1)
    opt.compile_step(loss_fn)
    hist = opt.run(dataset_batch_fn(X, Y, 32, seed=1), steps=120)
    assert np.mean(hist["losses"][-20:]) < 0.5 * np.mean(hist["losses"][:5])


def test_async_lockstep_single_worker_matches_sequential_sgd():
    """With one worker in lockstep mode the async pipeline degenerates to
    sequential SGD — the update math and codec plumbing must then be exact."""
    named, X, Y = make_problem(seed=2)
    batch_fn = dataset_batch_fn(X, Y, 16, seed=2)

    opt = AsyncSGD(named, lr=0.05, momentum=0.9, quota=1,
                   devices=[jax.devices()[0]])
    assert opt.num_workers == 1
    opt._lockstep = True
    opt.compile_step(loss_fn)
    steps = 10
    hist = opt.run(batch_fn, steps=steps)
    # Lockstep: every grad was computed from the freshest params.
    assert all(s == 0 for s in hist["staleness"])

    # Shadow sequential run of the pure rule on the same batch stream.
    shadow = {n: jnp.asarray(p) for n, p in named}
    sstate = {n: rules.sgd_init(p) for n, p in shadow.items()}
    for it in range(steps):
        batch = batch_fn(0, it)
        g = jax.grad(loss_fn)(shadow, jax.tree.map(jnp.asarray, batch))
        for n in shadow:
            shadow[n], sstate[n] = rules.sgd_update(
                shadow[n], g[n], sstate[n], lr=0.05, momentum=0.9)
    for n in shadow:
        np.testing.assert_allclose(np.asarray(opt.params[n]),
                                   np.asarray(shadow[n]),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("codec", [QuantizeCodec(8), TopKCodec(fraction=0.5)])
def test_async_codec_path(codec):
    named, X, Y = make_problem(seed=3)
    opt = AsyncSGD(named, lr=0.02, quota=2, code=codec)
    opt.compile_step(loss_fn)
    hist = opt.run(dataset_batch_fn(X, Y, 32, seed=3), steps=40)
    assert np.isfinite(hist["losses"]).all()
    assert np.mean(hist["losses"][-10:]) < np.mean(hist["losses"][:5])


def test_async_adam_runs():
    named, X, Y = make_problem(seed=4)
    opt = AsyncAdam(named, lr=1e-2, quota=2)
    opt.compile_step(loss_fn)
    hist = opt.run(dataset_batch_fn(X, Y, 32, seed=4), steps=30)
    assert np.mean(hist["losses"][-5:]) < np.mean(hist["losses"][:5])
    assert int(opt.state["w"]["step"]) == 30


def test_async_validation():
    p = np.zeros((2,), np.float32)
    with pytest.raises(ValueError, match="unique"):
        AsyncPS([("a", p), ("a", p)])
    with pytest.raises(ValueError, match="quota"):
        AsyncPS([("a", p)], quota=0)
    with pytest.raises(TypeError):
        AsyncSGD([("a", p)], lr=0.1, betas=(0.9, 0.99))
    opt = AsyncSGD([("a", p)], lr=0.1)
    with pytest.raises(RuntimeError, match="compile_step"):
        opt.run(lambda r, i: {}, steps=1)
    # Lockstep with quota > workers can never fill the quota: hard error,
    # not a hang.
    opt2 = AsyncSGD([("a", p)], lr=0.1, quota=5,
                    devices=[jax.devices()[0]])
    opt2._lockstep = True
    opt2.compile_step(lambda params, batch: jnp.sum(params["a"] ** 2))
    with pytest.raises(ValueError, match="lockstep"):
        opt2.run(lambda r, i: {}, steps=1)


def test_async_worker_failure_surfaces():
    """A dying worker must raise in run(), not hang the PS loop forever."""
    named, X, Y = make_problem(seed=6)
    opt = AsyncSGD(named, lr=0.05)
    opt.compile_step(loss_fn)

    def bad_batch_fn(rank, it):
        raise RuntimeError("data pipeline exploded")

    with pytest.raises(RuntimeError, match="worker"):
        opt.run(bad_batch_fn, steps=1)


def _draw_threads():
    return [t.name for t in threading.enumerate() if t.name.endswith("-draw")]


@pytest.mark.parametrize("lockstep", [True, False])
def test_batches_are_drawn_in_order_once_each_on_one_thread(lockstep):
    """`batch_fn(rank, it)` is called for it = 0, 1, 2, ... in that order,
    once each, from one thread per rank, and never more than two past the
    last batch its worker used (in lockstep: the last gradient the PS
    consumed)."""
    from pytorch_ps_mpi_tpu.utils.timing import span_log

    named, X, Y = make_problem(seed=7)
    inner = dataset_batch_fn(X, Y, 16, seed=7)
    calls, lock = [], threading.Lock()

    def batch_fn(rank, it):
        with lock:
            calls.append((rank, it, threading.current_thread().name))
        return inner(rank, it)

    opt = AsyncSGD(named, lr=0.02, quota=1, devices=jax.devices()[:3])
    assert opt.num_workers == 2
    opt._lockstep = lockstep
    opt.compile_step(loss_fn)
    span_log().clear()
    hist = opt.run(batch_fn, steps=12)
    assert hist["grads_consumed"] == 12 and not _draw_threads()

    for rank in range(opt.num_workers):
        worker = f"async-ps-worker-{rank}"
        mine = [c for c in calls if c[0] == rank]
        assert [it for _, it, _ in mine] == list(range(len(mine)))
        assert {name for *_, name in mine} <= {worker + "-draw"}
        # a batch the worker took is one it asked for, in the same order
        iters = span_log().records("async.worker_iter", thread=worker)
        took = [r["it"] for r in iters if "version" in r]
        assert took == list(range(len(took)))
        # one batch waits while the next is drawn: two ahead of the last
        # one the worker took (which `stop` may have made it drop), no more
        assert len(took) <= len(mine) <= len(iters) + 2
        if lockstep:
            # the worker takes one batch more after the PS's last ack (as it
            # drew one more before there was a drawer), the drawer two
            consumed = sum(r == rank for rs in hist["contributors"]
                           for r in rs)
            assert consumed <= len(took) <= consumed + 1


def test_next_batch_is_drawn_while_the_worker_works():
    """With a `batch_fn` that takes 30 ms, the draw of `it + 1` starts before
    iteration `it` ends; with one that returns at once, the batch is there
    when the worker asks (`await_batch.ready`)."""
    from pytorch_ps_mpi_tpu.utils.timing import span_log

    named, X, Y = make_problem(seed=8)
    inner = dataset_batch_fn(X, Y, 16, seed=8)

    def slow_batch_fn(rank, it):
        time.sleep(0.03)
        return inner(rank, it)

    opt = AsyncSGD(named, lr=0.02, quota=1, devices=[jax.devices()[0]])
    opt.compile_step(loss_fn)
    span_log().clear()
    opt.run(slow_batch_fn, steps=8)
    log = span_log()
    iters = {r["it"]: r for r in log.records("async.worker_iter")}
    draws = {r["it"]: r for r in log.records("async.draw")}
    assert len(iters) >= 8
    for it, r in iters.items():
        if it + 1 in iters:      # not the iteration `stop` cut short
            assert draws[it + 1]["start"] < r["end"]
            assert draws[it + 1]["end"] - draws[it + 1]["start"] >= 0.03
    awaits = log.records("async.await_batch")
    assert not awaits[0]["ready"]     # nothing is drawn before the run

    span_log().clear()
    opt.run(lambda rank, it: inner(0, 0), steps=20)
    ready = [r["ready"] for r in span_log().records("async.await_batch")]
    assert len(ready) >= 20 and sum(ready) >= len(ready) // 2


def test_batch_fn_failure_surfaces_at_its_iteration_and_no_drawer_outlives_run():
    from pytorch_ps_mpi_tpu.errors import WorkerFailedError
    from pytorch_ps_mpi_tpu.utils.timing import span_log

    named, X, Y = make_problem(seed=9)
    inner = dataset_batch_fn(X, Y, 16, seed=9)

    def batch_fn(rank, it):
        if it == 3:
            raise KeyError("row 3 is missing")
        return inner(rank, it)

    opt = AsyncSGD(named, lr=0.02, quota=1, devices=[jax.devices()[0]])
    opt.compile_step(loss_fn)
    span_log().clear()
    with pytest.raises(WorkerFailedError, match="worker 0") as failure:
        opt.run(batch_fn, steps=50)
    assert isinstance(failure.value.__cause__, KeyError)
    assert "row 3 is missing" in str(failure.value.__cause__)
    assert not _draw_threads()
    # the three batches before it were used, and nothing was drawn after it
    assert [r["it"] for r in span_log().records("async.draw")] == [0, 1, 2, 3]
    iters = span_log().records("async.worker_iter")
    assert [r["it"] for r in iters if "version" in r] == [0, 1, 2]

    # the next run starts its drawer again at it = 0
    hist = opt.run(inner, steps=5)
    assert hist["grads_consumed"] == 5 and not _draw_threads()


@pytest.mark.parametrize("n", [1, 3])
def test_the_fill_is_stacked_by_one_program(n):
    """`run()` stacks a fill's code trees with one jitted call; leaf for
    leaf it is the eager `jnp.stack` it replaced, whatever the codec's code
    looks like (a tuple of arrays here)."""
    from pytorch_ps_mpi_tpu.async_ps import _stack_codes

    rng = np.random.RandomState(n)
    codec = QuantizeCodec(8)
    codes = [{"w": codec.encode(jnp.asarray(rng.randn(6, 3), jnp.float32)),
              "b": codec.encode(jnp.asarray(rng.randn(3), jnp.float32))}
             for _ in range(n)]
    got = _stack_codes(*codes)
    want = jax.tree.map(lambda *xs: jnp.stack(xs), *codes)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.shape == w.shape and g.shape[0] == n and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_dataset_batch_fn_large_seed_and_distinct_streams():
    X = np.arange(40, dtype=np.float32).reshape(10, 4)
    Y = np.zeros((10, 1), np.float32)
    bf = dataset_batch_fn(X, Y, 4, seed=2**40)  # large seeds must not overflow
    b00, b10, b01 = bf(0, 0), bf(1, 0), bf(0, 1)
    assert b00["x"].shape == (4, 4)
    assert bf(0, 0)["x"].tolist() == b00["x"].tolist()  # deterministic
    # Distinct (rank, it) cells give distinct streams (w.h.p.).
    assert not (b00["x"].tolist() == b10["x"].tolist()
                == b01["x"].tolist())


def test_async_ps_is_worker_topology():
    named, X, Y = make_problem(seed=5)
    n_dev = len(jax.devices())
    opt = AsyncSGD(named, lr=0.05, ps_is_worker=True)
    expected = n_dev if n_dev > 1 else 1
    assert opt.num_workers == expected


def test_staleness_weighting_runs_and_damps():
    """Weighted async run: converges, and the recorded mean weight is <= 1
    (equal to 1 only if every gradient was perfectly fresh)."""
    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn

    rng = np.random.RandomState(0)
    params = init_mlp(rng, sizes=(12, 16, 4))
    opt = AsyncSGD(list(params.items()), lr=0.1, quota=2,
                   staleness_weighting=True)
    opt.compile_step(mlp_loss_fn)
    # One FIXED batch: async interleaving stays nondeterministic, but the
    # optimization signal is deterministic (memorization), so the windowed
    # convergence assert cannot flake on unlucky batch draws.
    fixed = {"x": rng.randn(32, 12).astype(np.float32),
             "y": rng.randint(0, 4, 32).astype(np.int32)}
    hist = opt.run(lambda rank, i: fixed, steps=60, log_every=0)
    assert hist["grads_consumed"] == 120
    weights = [t["mean_weight"] for t in opt.timings]
    assert all(0 < w <= 1.0 for w in weights), weights[:5]
    assert (np.mean(hist["losses"][-10:])
            < 0.7 * np.mean(hist["losses"][:5])), hist["losses"][::12]


@pytest.mark.slow  # ~80s CNN convergence run on the CPU mesh; async
# correctness/accounting is covered by the fast tests above, so the
# tier-1 lane skips this endurance check.
def test_async_resnet18_converges():
    """BASELINE.md ladder rung 3: AsySG-InCon on ResNet-18 itself (not an
    MLP stand-in) — quota >= 2, loss decreases, staleness recorded.  BN runs
    in eval mode (frozen init stats): the async PS mirrors the reference
    pseudo-code's plain-params contract (`/root/reference/README.md:56-77`),
    which has no aux-state channel.  Tiny synthetic CIFAR batch, fixed, so
    the convergence assert is deterministic (memorization signal)."""
    from pytorch_ps_mpi_tpu.models import (build_model, cross_entropy,
                                           resnet18)
    from pytorch_ps_mpi_tpu.utils.flatten import unflatten_params

    model = resnet18(num_classes=10, small_inputs=True)
    params, aux = build_model(model, (1, 32, 32, 3))

    def r18_loss(params_named, batch):
        variables = {"params": unflatten_params(params_named),
                     "batch_stats": aux}
        logits = model.apply(variables, batch["x"], train=False)
        return cross_entropy(logits, batch["y"])

    rng = np.random.RandomState(0)
    fixed = {"x": rng.randn(16, 32, 32, 3).astype(np.float32),
             "y": rng.randint(0, 10, 16).astype(np.int32)}

    # PS + 2 workers: bounds staleness (~2 with this queue depth) so the
    # convergence window is stable; quota=2 SUMS two grads per update
    # (reference semantics), so lr is set for an effective 2x step.
    opt = AsyncSGD(list(params.items()), lr=0.05, quota=2,
                   devices=jax.devices()[:3])
    opt.compile_step(r18_loss)
    hist = opt.run(lambda rank, i: fixed, steps=30)

    assert hist["grads_consumed"] == 60
    assert len(hist["staleness"]) == 30
    assert all(s >= 0 for s in hist["staleness"])
    assert np.isfinite(hist["losses"]).all()
    # Memorizing one fixed batch: the tail must sit clearly below the head.
    assert (np.mean(hist["losses"][-5:])
            < 0.9 * np.mean(hist["losses"][:3])), hist["losses"]
