"""Mixture-of-experts + expert parallelism.

Oracles: with ample capacity (no dropped tokens) the expert-parallel model
is an exact reformulation of the dense-MoE model — cross-entropy matches
bitwise-close and training trajectories match; with tight capacity the
layer degrades gracefully (dropped tokens ride the residual).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_ps_mpi_tpu import SGD
from pytorch_ps_mpi_tpu.models.moe import MoEMLP
from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM, build_lm,
                                                   lm_batch, make_lm_loss)
from pytorch_ps_mpi_tpu.parallel.mesh import make_dp_ep_mesh, make_ps_mesh

from lm_helpers import toy_tokens

VOCAB = 29


def _model(**kw):
    base = dict(vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2,
                d_ff=64, max_len=64, moe_experts=8)
    base.update(kw)
    return TransformerLM(**base)


def test_moe_layer_routes_every_kept_token():
    """With capacity >= T every token gets exactly its expert's output."""
    layer = MoEMLP(d_model=8, d_ff=16, n_experts=4, capacity_factor=4.0)
    x = jnp.asarray(np.random.RandomState(0).randn(2, 8, 8), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    out, aux = layer.apply(variables, x)
    assert out.shape == x.shape
    assert np.isfinite(np.asarray(out)).all()
    assert float(aux) > 0  # load-balance loss is positive


def test_moe_tight_capacity_degrades_gracefully():
    layer = MoEMLP(d_model=8, d_ff=16, n_experts=4, capacity_factor=0.1)
    x = jnp.asarray(np.random.RandomState(1).randn(2, 8, 8), jnp.float32)
    variables = layer.init(jax.random.PRNGKey(0), x)
    out, _ = layer.apply(variables, x)
    # Most tokens dropped -> most outputs exactly zero (residual-only).
    zeros = np.mean(np.abs(np.asarray(out)).sum(-1) == 0)
    assert zeros > 0.5
    assert np.isfinite(np.asarray(out)).all()


def test_moe_dense_trains(mesh8):
    model = _model(moe_capacity=2.0)
    params = build_lm(model, seq_len=16)
    opt = SGD(list(params.items()), lr=0.01, momentum=0.9, mesh=mesh8)
    opt.compile_step(make_lm_loss(model))
    losses = [opt.step(lm_batch(toy_tokens(8, 16, seed=s)))[0]
              for s in range(30)]
    assert losses[-1] < losses[0] * 0.7, losses[::6]


def test_ep_training_matches_dense_moe():
    """(dp=2, ep=4) with axis=('ps','ep') == flat 8-rank dense MoE, given
    ample capacity (identical routing, no drops)."""
    dense = _model(moe_capacity=16.0)
    ep_model = _model(moe_capacity=16.0, ep_axis="ep")
    params = build_lm(dense, seq_len=16)

    opt_ep = SGD(list(params.items()), lr=0.05,
                 mesh=make_dp_ep_mesh(2, 4), axis=("ps", "ep"),
                 batch_spec=P(("ps", "ep")))
    opt_ep.compile_step(make_lm_loss(ep_model, aux_weight=0.0))

    opt_dp = SGD(list(params.items()), lr=0.05, mesh=make_ps_mesh(8))
    opt_dp.compile_step(make_lm_loss(dense, aux_weight=0.0))

    for step in range(5):
        batch = lm_batch(toy_tokens(8, 16, seed=step))
        le, _ = opt_ep.step(batch)
        ld, _ = opt_dp.step(batch)
    assert abs(le - ld) < 1e-4
    for n in opt_dp.params:
        np.testing.assert_allclose(
            np.asarray(opt_ep.params[n]), np.asarray(opt_dp.params[n]),
            rtol=2e-3, atol=2e-5, err_msg=n)


def test_ep_trains_with_aux_loss():
    ep_model = _model(moe_capacity=2.0, ep_axis="ep")
    params = build_lm(_model(moe_capacity=2.0), seq_len=16)
    opt = SGD(list(params.items()), lr=0.02, mesh=make_dp_ep_mesh(2, 4),
              axis=("ps", "ep"), batch_spec=P(("ps", "ep")))
    opt.compile_step(make_lm_loss(ep_model))
    losses = [opt.step(lm_batch(toy_tokens(8, 16, seed=s)))[0]
              for s in range(25)]
    assert losses[-1] < losses[0] * 0.75, losses[::5]


def test_ep_indivisible_experts_rejected():
    ep_model = _model(moe_experts=6, ep_axis="ep")
    params = build_lm(_model(moe_experts=6), seq_len=8)
    opt = SGD(list(params.items()), lr=0.05, mesh=make_dp_ep_mesh(2, 4),
              axis=("ps", "ep"), batch_spec=P(("ps", "ep")))
    with pytest.raises(ValueError, match="not divisible by ep"):
        opt.compile_step(make_lm_loss(ep_model))
        opt.step(lm_batch(toy_tokens(8, 8)))


def _tiny_moe():
    """The smallest honest MoE LM: sparse per-expert gradients with a
    router — the hierarchy stress workload (ROADMAP item 5)."""
    model = _model(d_model=16, n_heads=2, n_layers=1, d_ff=32,
                   max_len=32, moe_experts=4, moe_capacity=2.0)
    params = build_lm(model, seq_len=8)
    return model, params


def test_moe_async_worker_path_through_aggregator():
    """Satellite (ISSUE 8): `models/moe.py` rides the ASYNC worker path
    — sparse per-expert gradients, encoded by a lossy codec, filled and
    pre-reduced by a group-local aggregator, applied by the root.  The
    fast tier-1 variant: in-process threads, a handful of fills."""
    import threading

    from pytorch_ps_mpi_tpu.async_ps import lm_batch_fn
    from pytorch_ps_mpi_tpu.multihost_async import AsyncSGDServer
    from pytorch_ps_mpi_tpu.shard import GroupWorker, Hierarchy

    model, params = _tiny_moe()
    loss_fn = make_lm_loss(model)
    toks = np.stack([np.asarray(toy_tokens(1, 8, seed=s))[0]
                     for s in range(32)])
    root = AsyncSGDServer(list(params.items()), lr=0.05, quota=1,
                          code="topk")
    root.compile_step(loss_fn)
    out: dict = {}

    def serve():
        try:
            out["hist"] = root.serve(steps=3, idle_timeout=120.0)
        except BaseException as exc:  # noqa: BLE001 - asserted below
            out["error"] = exc

    rt = threading.Thread(target=serve, daemon=True)
    rt.start()
    hier = Hierarchy(list(params.items()), groups=1, group_size=2,
                     upstream=[("127.0.0.1", root.address[1])],
                     code="topk")
    hier.compile()
    results: dict = {}

    def work(i):
        try:
            gw = GroupWorker(hier.addresses[0][0], hier.addresses[0][1],
                             root_endpoints=[root.address], group=0,
                             code="topk")
            results[i] = gw.run(loss_fn,
                                lm_batch_fn(toks, 4, seed=3 + i))
        except BaseException as exc:  # noqa: BLE001 - asserted below
            results[i] = exc

    ts = [threading.Thread(target=work, args=(i,), daemon=True)
          for i in range(2)]
    for t in ts:
        t.start()
    view = hier.serve(idle_timeout=120.0)
    rt.join(timeout=240)
    for t in ts:
        t.join(timeout=240)
        assert not t.is_alive()
    assert "error" not in out, out
    hist = out["hist"]
    assert len(hist["losses"]) == 3
    assert all(np.isfinite(hist["losses"]))
    # Expert + router params actually moved (the sparse grads arrived).
    moved = [n for n in params
             if not np.allclose(np.asarray(root.params[n]),
                                np.asarray(params[n]))]
    assert any("moe" in n for n in moved), moved
    assert hist["fault_stats"]["agg_frames"] >= 3
    assert view["fault_stats"]["agg_forwards"] >= 3
    for i in results:
        assert isinstance(results[i], int), results[i]


@pytest.mark.slow
def test_cli_moe_hier_endurance(tmp_path):
    """The MoE hierarchy workload through the REAL CLI roles, separate
    processes: --serve --aggregators with a kill_agg_at chaos plan (the
    supervisor restarts the aggregator mid-run), two MoE workers riding
    their redial budget; everyone exits 0."""
    import subprocess
    import sys as _sys

    from pytorch_ps_mpi_tpu.utils.faults import FaultPlan

    from test_multihost_async import ChildProc, _reap_all

    env_setup = ("import os; os.environ['XLA_FLAGS']=os.environ.get("
                 "'XLA_FLAGS','')+' --xla_force_host_platform_device_count=1'"
                 ";import jax; jax.config.update('jax_platforms','cpu');"
                 "from pytorch_ps_mpi_tpu import train; train.main(")
    chaos = FaultPlan(kill_agg_at={0: 4}).to_json().replace("'", "\\'")
    base = ("'--model','transformer','--moe-experts','4','--seq-len','16',"
            "'--batch-size','8','--n-examples','64','--steps','8',"
            "'--codec','topk'")

    server = ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--serve','0','--aggregators','1','--group-size','2',"
         f"'--quota','1',{base},'--chaos','{chaos}'])"])
    l1 = server.stdout.readline()
    assert l1.startswith("serving on port"), l1
    root_port = l1.strip().rsplit(" ", 1)[1]
    l2 = server.stdout.readline()
    assert l2.startswith("aggregators on ports"), l2
    agg_port = l2.strip().rsplit(" ", 1)[1]

    workers = [ChildProc(
        [_sys.executable, "-c", env_setup +
         f"['--connect','127.0.0.1:{agg_port}',"
         f"'--fallback','127.0.0.1:{root_port}',{base},"
         "'--reconnect-retries','100'])"])
        for _ in range(2)]

    outs = _reap_all([server] + workers, timeout=420)
    (s_out, s_err) = outs[0]
    assert server.returncode == 0, f"server failed:\n{s_out}\n{s_err}"
    assert "restarted aggregator for group 0" in s_err, s_err
    assert "agg_restarts=1" in s_err, s_err
    for w, (w_out, w_err) in zip(workers, outs[1:]):
        assert w.returncode == 0, f"worker failed:\n{w_out}\n{w_err}"
        assert "gradients pushed" in w_err


def test_moe_checkpoint_roundtrip(tmp_path, mesh8):
    from pytorch_ps_mpi_tpu import checkpoint

    model = _model(moe_capacity=2.0)
    params = build_lm(model, seq_len=16)
    opt = SGD(list(params.items()), lr=0.01, mesh=mesh8)
    opt.compile_step(make_lm_loss(model))
    opt.step(lm_batch(toy_tokens(8, 16)))
    checkpoint.save_optimizer(tmp_path / "moe.psz", opt, step=1)
    fresh = SGD(list(params.items()), lr=0.01, mesh=mesh8)
    fresh.compile_step(make_lm_loss(model))
    checkpoint.load_optimizer(tmp_path / "moe.psz", fresh)
    for n in opt.params:
        np.testing.assert_array_equal(np.asarray(opt.params[n]),
                                      np.asarray(fresh.params[n]))


# -- the share-aware dropless layer ------------------------------------------

from perfbench.models.kimi_linear import _moe_layer as reference_moe_layer
from pytorch_ps_mpi_tpu.models import moe
from pytorch_ps_mpi_tpu.models.moe import ShareOfExperts
from pytorch_ps_mpi_tpu.utils.flatten import named_params

E, TOP_K, SCALE = 16, 4, 2.446


def _share(held):
    return ShareOfExperts(d_model=8, d_expert=12, n_experts=E, held=held,
                          top_k=TOP_K, scale=SCALE, d_shared=12)


@pytest.fixture(autouse=True)
def _small_blocks(monkeypatch):
    """74 tokens make ~19 assignments an expert: blocks of 8 rows give every
    expert several blocks and a ragged last one, which the production
    constant (one mostly empty block each) would not."""
    monkeypatch.setattr(moe, "BLOCK_ROWS", 8)


def _sizes(held):
    return {"top_k": TOP_K, "routed_scale": SCALE, "n_shared": 1,
            "experts_held": tuple(held)}


@pytest.fixture(scope="module")
def whole_layer():
    """All 16 experts in one layer, and a batch."""
    layer = _share(tuple(range(E)))
    x = jnp.asarray(np.random.RandomState(0).randn(2, 37, 8), jnp.float32)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    return params, x


def _cut(params, held):
    """The parameters a chip holding `held` has: the whole router, its own
    experts' weights."""
    take = lambda w: w[jnp.asarray(held)]
    return {**params, "w_gate": take(params["w_gate"]),
            "w_up": take(params["w_up"]), "w_down": take(params["w_down"])}


@pytest.fixture(autouse=True)
def _f32_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.mark.parametrize("held", [(4, 5, 6, 7), (0, 9, 15), (3,)])
@pytest.mark.parametrize("block_rows", [8, None])   # None: as it is run
def test_share_matches_the_masked_loop_over_its_experts(whole_layer, held,
                                                        block_rows,
                                                        monkeypatch):
    params, x = whole_layer
    mine = _cut(params, held)
    if block_rows is None:
        monkeypatch.undo()
    got, load = _share(held).apply({"params": mine}, x)
    want = reference_moe_layer(_sizes(held), named_params(mine), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    assert load.shape == (len(held) + 1,)
    assert float(load[-1]) == float(load[:-1].sum()) <= 2 * 37 * TOP_K


def test_share_gradients_match_the_masked_loop(whole_layer):
    params, x = whole_layer
    held = (4, 5, 6, 7)
    mine = _cut(params, held)
    f = lambda p, x: jnp.sum(jnp.sin(_share(held).apply({"params": p}, x)[0]))
    g = lambda p, x: jnp.sum(jnp.sin(reference_moe_layer(
        _sizes(held), named_params(p), x)))
    got, got_x = jax.grad(f, argnums=(0, 1))(mine, x)
    want, want_x = jax.grad(g, argnums=(0, 1))(mine, x)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               rtol=1e-4, atol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=str(path))


def test_every_token_on_one_held_expert_drops_nothing(whole_layer):
    """A selection bias that sends all 74 tokens to expert 5: 74 assignments
    on one expert of four held, ten blocks of eight rows, none lost."""
    params, x = whole_layer
    held = (4, 5, 6, 7)
    mine = {**_cut(params, held),
            "e_score_correction_bias": jnp.zeros(E).at[5].set(100.0)}
    got, load = _share(held).apply({"params": mine}, x)
    want = reference_moe_layer(_sizes(held), named_params(mine), x)
    assert float(load[1]) == 2 * 37
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_no_token_routed_here_leaves_the_shared_expert(whole_layer):
    params, x = whole_layer
    held = (4, 5, 6, 7)
    away = jnp.zeros(E).at[jnp.asarray(held)].set(-100.0)
    mine = {**_cut(params, held), "e_score_correction_bias": away}
    got, load = _share(held).apply({"params": mine}, x)
    assert float(load[-1]) == 0.0
    want = reference_moe_layer(_sizes(()), named_params(mine), x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_the_shares_add_up_to_the_uncut_layer(whole_layer):
    """16 experts over 4 shares: the four shares' routed parts, plus the
    shared expert once, are the uncut reference layer."""
    params, x = whole_layer
    flat = named_params(params)
    whole = reference_moe_layer(_sizes(range(E)), flat, x)
    shared = reference_moe_layer(_sizes(()), flat, x)
    shares = [tuple(range(4 * r, 4 * r + 4)) for r in range(4)]
    routed, loads = [], []
    for held in shares:
        y, load = _share(held).apply({"params": _cut(params, held)}, x)
        routed.append(y - shared)
        loads.append(float(load[-1]))
    np.testing.assert_allclose(np.asarray(sum(routed) + shared),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    assert sum(loads) == 2 * 37 * TOP_K       # every assignment, once


# -- the plan without scalar gathers and scatters (PR 36) ---------------------
# XLA's TPU gather and scatter take the `T * k` scalars one after another;
# `routed_here` compares, sums and sorts instead, to the same numbers.

@pytest.mark.parametrize("n,groups", [(296, 5), (64, 1), (1000, 9)])
def test_sorted_by_is_the_stable_argsort_its_gather_and_its_scatter(n,
                                                                    groups):
    rng = np.random.RandomState(n)
    where = jnp.asarray(rng.randint(0, groups, n), jnp.int32)   # many ties
    weight = jnp.asarray(rng.rand(n), jnp.float32)
    order, in_order = moe._sorted_by(where, weight)
    want = jnp.argsort(where, stable=True)
    np.testing.assert_array_equal(order, want)
    np.testing.assert_array_equal(in_order, weight[want])
    pull = jnp.asarray(rng.randn(n), jnp.float32)
    got = jax.grad(lambda w: jnp.sum(moe._sorted_by(where, w)[1] * pull))(
        weight)
    np.testing.assert_array_equal(
        got, jax.grad(lambda w: jnp.sum(w[want] * pull))(weight))


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_the_layer_gathers_and_scatters_rows_and_nothing_smaller(whole_layer):
    """Forward and backward of the layer: every gather and scatter-add left
    moves rows of ``d`` numbers or an expert's slice of a weight gradient
    (the sweep's), is `route_top_k`'s ``[T, k]`` reading of the chosen
    scores, or reads the 16-entry table of held experts; the plan walks no
    list of ``T * k`` scalars (the count an expert was a scatter-add of
    ones, the sorted weights a gather with a scatter-add behind it)."""
    params, x = whole_layer
    held = (4, 5, 6, 7)
    f = lambda p, x: jnp.sum(_share(held).apply({"params": p}, x)[0])
    jaxpr = jax.make_jaxpr(jax.grad(f, (0, 1)))(_cut(params, held), x)
    moved = [(e.primitive.name, e.outvars[0].aval.shape)
             for e in _equations(jaxpr.jaxpr)
             if e.primitive.name in ("gather", "scatter-add", "scatter")]
    assert {name for name, _ in moved} >= {"gather", "scatter-add"}
    scalars = [(name, shape) for name, shape in moved if len(shape) < 2]
    assert scalars == [("gather", (2 * 37 * TOP_K,))], scalars  # local[chosen]


def test_the_experts_gradients_are_rounded_as_their_reading_is(whole_layer):
    """In bf16 the experts' weights are read in bf16 inside the sweep; their
    gradients come back f32, rounded to bf16 as the transpose of that
    reading rounds them, and near the f32 layer's."""
    params, x = whole_layer
    held = (4, 5, 6, 7)
    mine = _cut(params, held)
    f = lambda dtype: lambda p: jnp.sum(jnp.sin(
        _share(held).clone(dtype=dtype).apply({"params": p}, x)[0]
        .astype(jnp.float32)))
    got, want = jax.grad(f(jnp.bfloat16))(mine), jax.grad(f(jnp.float32))(mine)
    for name in ("w_gate", "w_up", "w_down"):
        g = got[name]
        assert g.dtype == jnp.float32 and np.asarray(g).any()
        np.testing.assert_array_equal(
            g, g.astype(jnp.bfloat16).astype(jnp.float32))
        np.testing.assert_allclose(g, want[name], rtol=0.1, atol=0.05)
