"""Flash attention vs dense attention: forward and gradient equality.

The dense softmax attention is the oracle (same strategy as the ring
tests): the Pallas streaming-softmax kernel (run under the interpreter on
the CPU test mesh — same kernel logic, just emulated) and its blockwise
custom-vjp backward must match to numerical tolerance across causal
masking, non-multiple-of-block lengths, head-dim padding, and scale
overrides — and must plug into the transformer as the attention."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_ps_mpi_tpu.ops import flash_attention as _fa
from pytorch_ps_mpi_tpu.ops.flash_attention import BLOCK
from pytorch_ps_mpi_tpu.parallel.ring_attention import dense_attention

# The default lowers the Mosaic kernel; on the CPU mesh the interpreter is
# asked for by name.
flash_attention = functools.partial(_fa.flash_attention, impl="interpret")


def _qkv(seed, b=2, s=96, h=2, d=8):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(np.float32))
    return mk(), mk(), mk()


def _assert_out_and_grads_match(q, k, v, causal, **tol):
    """Output and the three gradients of sum(sin(attention)) against
    `dense_attention` on the same values in f32."""
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(
        fn(q, k, v, causal=causal).astype(jnp.float32)))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    want = [dense_attention(*f32, causal=causal),
            *jax.grad(loss(dense_attention), argnums=(0, 1, 2))(*f32)]
    got = [flash_attention(q, k, v, causal=causal),
           *jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)]
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == q.dtype
        np.testing.assert_allclose(np.asarray(g, dtype=np.float32),
                                   np.asarray(w), err_msg=name, **tol)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [64, 96, BLOCK, BLOCK + 40, 2 * BLOCK])
def test_flash_matches_dense(causal, s):
    q, k, v = _qkv(0, s=s)
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_flash_scale_and_headdim_padding():
    # d=20 exercises the lane-padding path; scale override must thread.
    q, k, v = _qkv(1, b=1, s=40, h=3, d=20)
    want = dense_attention(q, k, v, causal=True, scale=0.2)
    got = flash_attention(q, k, v, causal=True, scale=0.2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_match_dense(causal):
    q, k, v = _qkv(2, b=1, s=BLOCK + 24, h=2, d=16)
    tgt = jnp.asarray(np.random.RandomState(3)
                      .randn(*q.shape).astype(np.float32))

    def loss(attn):
        def f(q, k, v):
            return jnp.sum((attn(q, k, v, causal=causal) - tgt) ** 2)
        return f

    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_multi_tile_grid(causal):
    """s=640 pads past the backward's 512-row tile but is not a multiple of
    it: the backward runs a 2x2 tile grid, exercising scratch accumulation
    across grid steps, the init/finish gating, the causal tile skip, AND
    the edge-tile re-pad guard (off-tile rows would otherwise read out of
    bounds on hardware)."""
    s = 640
    tiles = _fa.tile_plan(s, BLOCK, BLOCK, causal).tiles
    assert tiles["flash_bwd_dkdv"] == tiles["flash_bwd_dq"] == (512,) * 4
    q, k, v = _qkv(6, b=1, s=s, h=1, d=16)

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            jnp.sin(attn(q, k, v, causal=causal)))

    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=name)


def test_flash_under_jit_and_bf16_io():
    q, k, v = _qkv(4, s=64, d=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    got = jax.jit(functools.partial(flash_attention, causal=True))(qb, kb, vb)
    assert got.dtype == jnp.bfloat16
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, dtype=np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_transformer_trains_with_flash_attention():
    """flash_attention plugs into TransformerLM as the attention and the
    model trains; forward parity with the dense-attn model at init.
    (The 8-virtual-device environment comes from conftest; SGD(mesh=None)
    builds the default all-device mesh.)"""
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)

    dense = TransformerLM(vocab_size=17, d_model=16, n_heads=2, n_layers=1,
                          d_ff=32, max_len=64)
    flash = dense.copy(
        attn=functools.partial(flash_attention, causal=True))
    params = build_lm(dense, seq_len=16)
    toks = np.random.RandomState(5).randint(0, 17, size=(8, 17))

    ld = make_lm_loss(dense)(dict(params), lm_batch(toks))
    lf = make_lm_loss(flash)(dict(params), lm_batch(toks))
    np.testing.assert_allclose(float(lf), float(ld), rtol=1e-5)

    opt = SGD(list(params.items()), lr=0.1, mesh=None)
    # mesh=None -> all devices; use default mesh for a quick train check.
    opt.compile_step(make_lm_loss(flash))
    losses = [opt.step(lm_batch(toks))[0] for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# -- a v width other than the q / k width (latent attention: 192 / 128) ------


def _qkv_widths(seed, b=2, s=200, h=2, d=24, dv=16, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda w: jnp.asarray(rng.randn(b, s, h, w), dtype)
    return mk(d), mk(d), mk(dv)


@pytest.mark.parametrize("d,dv", [(24, 16), (192, 128), (16, 24), (130, 8)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_with_another_v_width_matches_dense(d, dv, causal):
    q, k, v = _qkv_widths(0, s=BLOCK + 40, d=d, dv=dv)
    want = dense_attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal)
    assert got.shape == want.shape == (2, BLOCK + 40, 2, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("d,dv", [(24, 16), (192, 128)])
def test_flash_gradients_with_another_v_width(d, dv):
    q, k, v = _qkv_widths(1, s=150, d=d, dv=dv)
    scale = d ** -0.5
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(
        fn(q, k, v, causal=True, scale=scale)))
    want = jax.grad(loss(dense_attention), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    for g, w, x in zip(got, want, (q, k, v)):
        assert g.shape == x.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5)


def _pallas_calls(q, k, v):
    """The keyword arguments of every `pallas_call` that a causal forward
    and backward trace, in order."""
    seen = []
    real = _fa.pl.pallas_call

    def spy(kernel, **kw):
        seen.append(kw)
        return real(kernel, **kw)

    _fa.pl.pallas_call = spy
    try:
        jax.clear_caches()   # the calls are jitted: make them trace here
        jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True)), argnums=(0, 1, 2))(q, k, v)
    finally:
        _fa.pl.pallas_call = real
    return seen


def test_v_is_not_widened_to_the_q_width():
    """At 192 / 128 the kernels see q and k at 256 lanes and v, the
    accumulator and the output at 128: v is not padded to q's width."""
    seen = [[s.shape[-1] for s in jax.tree.leaves(kw["out_shape"])]
            for kw in _pallas_calls(*_qkv_widths(2, s=64, d=192, dv=128))]
    # forward: o 128 (+ the row statistics' lane tile); the one backward call
    # (the q side is whole at 64 rows): dk 256, dv 128, dq 256
    assert seen == [[128, BLOCK], [256, 128, 256]]


# PR 28 pinned its refactor to the parent's bits with digests of these three
# cases; the two-level tiles reorder f32 sums on purpose, so the same inputs
# are now held to `dense_attention` in f32.
@pytest.mark.parametrize("d,dtype", [(64, "bfloat16"), (64, "float32"),
                                     (128, "bfloat16")])
def test_matches_dense_at_equal_widths(d, dtype):
    rng = np.random.RandomState(7)
    q, k, v = (jnp.asarray(rng.randn(2, 200, 2, d), dtype) for _ in range(3))
    tol = (dict(rtol=2e-4, atol=2e-5) if dtype == "float32"
           else dict(rtol=2e-2, atol=2e-2))
    _assert_out_and_grads_match(q, k, v, True, **tol)


# -- two-level tiles: sub-blocks that follow the mask -------------------------


@pytest.mark.parametrize("kernel", _fa.KERNELS)
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t,s_pad,true_len", [
    (_fa.Tiles(512, 512, 128, 128), 1024, 1024),
    (_fa.Tiles(512, 1024, 256, 128), 1024, 1000),
    (_fa.Tiles(1024, 512, 128, 256), 1024, 700),
    (_fa.Tiles(384, 384, 128, 384), 768, 768),
    (_fa.Tiles(256, 256, 256, 256), 512, 300),
])
def test_the_bounds_enter_what_the_mask_keeps(kernel, causal, t, s_pad,
                                              true_len):
    """Brute force over positions: a sub-block is entered iff the mask
    keeps an entry of it, runs the interior body iff it keeps all of them
    (a padded q row counts only in the backward's row-major kernel, which
    has to mask its `NEG_INF` logsumexp), and is entered once."""
    seq_len = None if true_len == s_pad else true_len
    entered = list(_fa._entered(kernel, t, s_pad, s_pad, causal, seq_len))
    got = {(q0, k0): masked for q0, k0, masked in entered}
    assert len(got) == len(entered)
    pos = np.arange(s_pad)
    keep = (pos[None, :] < true_len) & np.ones((s_pad, 1), bool)
    if causal:
        keep &= pos[:, None] >= pos[None, :]
    rows_live = pos < true_len
    for q0 in range(0, s_pad, t.sub_q):
        for k0 in range(0, s_pad, t.sub_k):
            block = keep[q0:q0 + t.sub_q, k0:k0 + t.sub_k]
            live = rows_live[q0:q0 + t.sub_q]
            if kernel != "flash_fwd":
                block = block & live[:, None]
            if not block.any():
                assert (q0, k0) not in got, (q0, k0)
            elif block.all():
                assert got[(q0, k0)] is False, (q0, k0)
            elif kernel == "flash_fwd" or live.all():
                assert got[(q0, k0)] is True, (q0, k0)
            else:   # some padded q rows: entered, and masked
                assert got.get((q0, k0), True) is True, (q0, k0)


def _forced(monkeypatch, blk_q, blk_k):
    """Small sub-blocks for every call under the public entry point."""
    if blk_q is not None:
        monkeypatch.setattr(_fa, "tile_plan", functools.partial(
            _fa.tile_plan, blk_q=blk_q, blk_k=blk_k))


@pytest.mark.parametrize("d,dv", [(64, 64), (192, 128)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,blk_q,blk_k", [
    (384, 128, 128),    # one tile of 3 x 3 sub-blocks: the unrolled loops
    (600, 128, 128),    # several tiles, device loops, the padded tail
    (600, 256, 128),    # sub-blocks that are not square
    (520, 128, 256),
    (1100, None, None),   # the shape's own plan: a head in one tile, unrolled
])
def test_sub_blocks_match_dense(monkeypatch, d, dv, causal, s, blk_q, blk_k):
    """Outputs and the three gradients against `dense_attention` with every
    branch of the two-level kernels run under the interpreter: a skipped
    sub-block, an interior one, the diagonal one, the padded tail, a grid
    tile of several sub-blocks and several grid tiles."""
    _forced(monkeypatch, blk_q, blk_k)
    plan = _fa.tile_plan(-(-s // BLOCK) * BLOCK, 256 if d > 128 else 128, 128,
                         causal, true_len=s)
    for kernel, c in plan.counts.items():
        t = plan.tiles[kernel]
        assert c.entered > c.masked > 0 or not causal, (kernel, c)
        assert c.skipped > 0 or not causal, (kernel, c)
        if blk_q is not None:
            assert (t.sub_q, t.sub_k) == (blk_q, blk_k)
        assert t.tile_q > t.sub_q or t.tile_k > t.sub_k or not causal
    q, k, v = _qkv_widths(3, b=1, s=s, h=2, d=d, dv=dv)
    _assert_out_and_grads_match(q, k, v, causal, rtol=2e-4, atol=2e-5)


def _one_backward_call_at(monkeypatch, t):
    """The backward as the one call at the tiles ``t`` whatever the shape;
    the forward keeps the shape's own."""
    real = _fa.tile_plan

    def plan(*a, **kw):
        shape = real(*a, **kw)
        return _fa.Plan({"flash_fwd": shape.tiles["flash_fwd"],
                         "flash_bwd_dkdv": t}, shape.counts)
    monkeypatch.setattr(_fa, "tile_plan", plan)


@pytest.mark.parametrize("d,dv", [(192, 128), (256, 256)])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s,t", [
    (384, _fa.Tiles(384, 384, 128, 128)),   # a head in one tile: unrolled
    (600, _fa.Tiles(640, 128, 128, 128)),   # 5 k tiles, a padded tail
    (600, _fa.Tiles(768, 256, 256, 128)),   # both sides padded to the tile
])
def test_one_backward_call_matches_dense(monkeypatch, d, dv, causal, s, t):
    """`flash_bwd_dkdv` with the whole q side in its tile writes dq too: dq
    adds up in VMEM over the k tiles of a head (a mask, a padded tail, a
    second head that must not see the first one's sum) and no
    `flash_bwd_dq` is traced."""
    _one_backward_call_at(monkeypatch, t)
    q, k, v = _qkv_widths(4, b=1, s=s, h=2, d=d, dv=dv)
    _assert_out_and_grads_match(q, k, v, causal, rtol=2e-4, atol=2e-5)
    assert [kw["name"] for kw in _pallas_calls(q, k, v)] == [
        "flash_fwd", "flash_bwd_dkdv"]


def test_one_backward_call_gives_the_dq_of_two(monkeypatch):
    """Past `_WHOLE_SIDE` (patched down to the length's half) the q side
    comes in tiles and `flash_bwd_dq` is called as before; the one call's
    dq on the same inputs is that dq to f32 rounding, dk and dv too."""
    s = 1200
    q, k, v = _qkv(10, b=1, s=s, h=2, d=64)
    grads = lambda: jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, causal=True))), argnums=(0, 1, 2))(q, k, v)
    assert "flash_bwd_dq" not in _fa.tile_plan(1280, 128, 128, True).tiles
    one = grads()
    monkeypatch.setattr(_fa, "_WHOLE_HEAD", 512)
    monkeypatch.setattr(_fa, "_WHOLE_SIDE", 512)
    assert _fa.tile_plan(1280, 128, 128, True).tiles["flash_bwd_dq"] \
        == (1024, 512, 512, 512)
    for a, b, name in zip(one, grads(), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6, err_msg=name)


def test_the_plan_of_a_long_sequence_matches_dense():
    """Past `_WHOLE_HEAD` the shape's own plan runs device loops over
    512 x 512 sub-blocks with the other side whole in VMEM, and the backward
    is one call over two k tiles."""
    s = 2100
    plan = _fa.tile_plan(2176, 128, 128, True, true_len=s)
    assert plan.tiles == {"flash_fwd": (1024, 2560, 512, 512),
                          "flash_bwd_dkdv": (2560, 2048, 512, 512)}
    q, k, v = _qkv(9, b=1, s=s, h=1, d=64)
    _assert_out_and_grads_match(q, k, v, True, rtol=2e-4, atol=2e-5)


_LOOPED = {"flash_fwd": (1024, 8192, 512, 512),
           "flash_bwd_dkdv": (8192, 2048, 512, 512)}


@pytest.mark.parametrize("shape,tiles,counts", [
    # gpt2m-*: [128, 1024, 128 / 128], a head in one tile, unrolled
    ((1024, 128, 128), {"flash_fwd": (1024, 1024, 256, 256),
                        "flash_bwd_dkdv": (1024, 1024, 512, 512)},
     {"flash_fwd": (10, 4, 6), "flash_bwd_dkdv": (3, 2, 1)}),
    # kimi-linear-sync-1chip: [64, 8192, 256 / 128], device loops
    ((8192, 256, 128), _LOOPED, {k: (136, 16, 120) for k in _LOOPED}),
    # glm47-flash-sync-1chip: [20, 8192, 256 / 256], the same plan: the
    # tiles follow the length and the mask, v's width only sizes the VMEM
    ((8192, 256, 256), _LOOPED, {k: (136, 16, 120) for k in _LOOPED}),
    # a short sequence: one tile, one sub-block, as before the sweep
    ((512, 128, 128), {k: (512, 512, 512, 512) for k in _LOOPED},
     {k: (1, 1, 0) for k in _LOOPED}),
    # past `_WHOLE_SIDE` the q side comes in tiles: dq's rows are not all in
    # VMEM while the k tiles go by, and `flash_bwd_dq` is called for them
    ((16384, 128, 128), {**_LOOPED, "flash_bwd_dq": (1024, 8192, 512, 512)},
     {k: (528, 32, 496) for k in _fa.KERNELS}),
])
def test_tile_plan_counts(shape, tiles, counts):
    """The calls a shape makes (the plan's keys: one backward call where the
    q side of a head is one tile) and the entered / masked / skipped
    sub-blocks a head, for the three shapes the benchmark's cells run, for
    the fallback and for a length past `_WHOLE_SIDE` (PERF.md quotes these).
    Before the two-level tiles GPT-2's shape read forward 2 / 2 / 0 and
    each backward kernel 3 / 3 / 1 (every tile that ran paid the mask); of
    the score elements of the square a head now computes 0.625 (forward)
    and 0.75 (the one backward call, whose sweep chose 512 x 512 sub-blocks
    over the 0.5625 of 128 x 128) where the mask keeps 0.50."""
    plan = _fa.tile_plan(*shape, True)
    assert plan.tiles == tiles and plan.counts == counts
    assert list(plan.tiles) == list(plan.counts) == list(
        _fa.KERNELS[:len(tiles)])
    for kernel, (entered, masked, skipped) in counts.items():
        t = plan.tiles[kernel]
        assert entered + skipped == (shape[0] // t.sub_q) * (shape[0] // t.sub_k)
    # not causal, nothing padded: every sub-block interior
    free = _fa.tile_plan(*shape, False)
    assert all(c.masked == c.skipped == 0 for c in free.counts.values())
    # the same length reached by padding: in the forward the padded columns
    # lie in sub-blocks the diagonal masks already; the backward also masks
    # the padded q rows' whole band
    tail = _fa.tile_plan(*shape, True, true_len=shape[0] - 5)
    assert tail.counts["flash_fwd"] == plan.counts["flash_fwd"]
    if shape[0] > 512:
        assert all(tail.counts[k].masked > plan.counts[k].masked
                   and tail.counts[k].entered == plan.counts[k].entered
                   for k in list(tiles)[1:])


def test_the_names_the_benchmark_and_the_smoke_look_for():
    """`mla_flash_ms_step` matches the calls by name and the smoke's flash
    phases ask the compiled step for those `tile_plan` names at their shape:
    both must be the names the module gives its calls (the smoke drifted
    unseen from PR 28 to 30), whichever of them a shape makes."""
    import ast
    import os

    from perfbench.models import glm_moe, kimi_linear

    for s, calls in ((40, _fa.KERNELS[:2]), (640, _fa.KERNELS)):
        seen = [(kw["name"], kw["metadata"])
                for kw in _pallas_calls(*_qkv(8, b=1, s=s, h=1, d=8))]
        assert tuple(n for n, _ in seen) == calls == tuple(_fa.tile_plan(
            -(-s // BLOCK) * BLOCK, BLOCK, BLOCK, True).tiles)
        assert all(m == {"kernel": n} for n, m in seen)
    assert tuple(kimi_linear.FLASH_KERNELS) == _fa.KERNELS
    assert glm_moe.FLASH_KERNELS is kimi_linear.FLASH_KERNELS

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chip_smoke.py")) as f:
        tree = ast.parse(f.read())
    def fn(name):
        return next(n for n in ast.walk(tree)
                    if isinstance(n, ast.FunctionDef) and n.name == name)

    # the phases ask `flash_calls`, which asks `tile_plan`: no list of names
    # of the smoke's own, and not all of `KERNELS` (a shape may not call one)
    assert {a.name for n in ast.walk(fn("flash_calls"))
            if isinstance(n, ast.ImportFrom)
            and n.module == "pytorch_ps_mpi_tpu.ops.flash_attention"
            for a in n.names} == {"BLOCK", "tile_plan"}
    for name in ("phase_lm_flash", "phase_glm_flash", "phase_phi_flash"):
        called = {n.func.id for n in ast.walk(fn(name))
                  if isinstance(n, ast.Call) and isinstance(n.func, ast.Name)}
        names = {n.id for n in ast.walk(fn(name)) if isinstance(n, ast.Name)}
        literals = {n.value for n in ast.walk(fn(name))
                    if isinstance(n, ast.Constant)
                    and isinstance(n.value, str)}
        assert "flash_calls" in called and "KERNELS" not in names, name
        assert not literals & {"_fwd_kernel", "_bwd_dkdv_kernel",
                               "_bwd_dq_kernel", *_fa.KERNELS}, name


# -- a sliding window: the band's lower edge ----------------------------------


def _dense_band(q, k, v, window, causal=True):
    """Dense softmax attention over the keys ``0 <= i - j < window``."""
    del causal
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    age = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    scores = jnp.where((age >= 0) & (age < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)


@pytest.mark.parametrize("kernel", _fa.KERNELS)
@pytest.mark.parametrize("window", [1, 50, 128, 129, 300, 2000])
@pytest.mark.parametrize("t,s_pad,true_len", [
    (_fa.Tiles(512, 512, 128, 128), 1024, 1024),
    (_fa.Tiles(512, 1024, 256, 128), 1024, 1000),
    (_fa.Tiles(1024, 512, 128, 256), 1024, 700),
    (_fa.Tiles(384, 384, 128, 384), 768, 768),
    (_fa.Tiles(256, 256, 256, 256), 512, 300),
])
def test_the_bounds_enter_what_the_band_keeps(kernel, window, t, s_pad,
                                              true_len):
    """Brute force over positions, as `test_the_bounds_enter_what_the_mask_
    keeps`, with the band ``0 <= i - j < window`` in the mask: a sub-block
    wholly under the band is never entered, one the band's lower edge
    crosses is masked, one between the edges is interior, each entered
    once.  (The forward may enter,
    masked, a sub-block in which only padded q rows would have had keys:
    their output is cut off.)"""
    seq_len = None if true_len == s_pad else true_len
    entered = list(_fa._entered(kernel, t, s_pad, s_pad, True, seq_len,
                                window))
    got = {(q0, k0): masked for q0, k0, masked in entered}
    assert len(got) == len(entered)
    pos = np.arange(s_pad)
    age = pos[:, None] - pos[None, :]
    keep = (pos[None, :] < true_len) & (age >= 0) & (age < window)
    rows_live = pos < true_len
    for q0 in range(0, s_pad, t.sub_q):
        for k0 in range(0, s_pad, t.sub_k):
            block = keep[q0:q0 + t.sub_q, k0:k0 + t.sub_k]
            live = rows_live[q0:q0 + t.sub_q]
            if kernel != "flash_fwd":
                block = block & live[:, None]
            if not block.any():
                assert (q0, k0) not in got or (
                    kernel == "flash_fwd" and not live.all()
                    and got[(q0, k0)]), (q0, k0)
            elif block.all():
                assert got[(q0, k0)] is False, (q0, k0)
            elif kernel == "flash_fwd" or live.all():
                assert got[(q0, k0)] is True, (q0, k0)
            else:   # some padded q rows: entered, and masked
                assert got.get((q0, k0), True) is True, (q0, k0)


@pytest.mark.parametrize("s,window,blk_q,blk_k", [
    (384, 100, 128, 128),   # a window smaller than a sub-block, unrolled
    (384, 128, 128, 128),   # ... equal to one
    (600, 200, 128, 128),   # ... larger; device loops, S no multiple of 128
    (600, 50, 256, 128),    # sub-blocks that are not square
    (520, 300, 128, 256),
    (300, 1000, 128, 128),  # a window longer than the sequence: causal
    (1100, 256, None, None),    # the shape's own plan, unrolled
])
def test_window_matches_a_dense_band_at_64_and_128(monkeypatch, s, window,
                                                   blk_q, blk_k):
    """Output and the three gradients under ``window=`` against a dense
    softmax over the band, at the widths the differential layers run (q / k
    64, v 128), in f32 under the interpreter: a sub-block under the band
    skipped, one on its lower edge, an interior one, the diagonal, the
    padded tail."""
    _forced(monkeypatch, blk_q, blk_k)
    counts = _fa.tile_plan(-(-s // BLOCK) * BLOCK, 128, 128, True,
                           window=window, true_len=s).counts
    causal = _fa.tile_plan(-(-s // BLOCK) * BLOCK, 128, 128, True,
                           true_len=s).counts
    for kernel, c in counts.items():
        assert c.entered + c.skipped == sum(causal[kernel][::2])
        assert c.entered <= causal[kernel].entered
        if window + 2 * 256 <= s:   # some sub-block lies under the band
            assert c.entered < causal[kernel].entered
    q, k, v = _qkv_widths(11, b=1, s=s, h=2, d=64, dv=128)
    loss = lambda fn: lambda q, k, v: jnp.sum(jnp.sin(fn(q, k, v)))
    flash = functools.partial(flash_attention, causal=True, window=window)
    dense = functools.partial(_dense_band, window=window)
    want = [dense(q, k, v), *jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)]
    got = [flash(q, k, v), *jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)]
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_the_plan_under_the_cells_window():
    """`[40, 8192, 128 / 128]` under ``window=512`` (the window layer of
    `phi4flash-sync-1chip`): the looped tiles of the causal plan, and of a
    head's 256 sub-blocks of 512 x 512 the band touches 31 (two a row of
    sub-blocks, both crossed by an edge) where the causal mask alone leaves
    136; 256 x 256 sub-blocks would enter 93 of 1,024, a third of them
    interior; a window of 4,096 enters 108, 84 of them interior."""
    plan = _fa.tile_plan(8192, 128, 128, True, window=512)
    assert plan.tiles == _LOOPED == _fa.tile_plan(8192, 128, 128, True).tiles
    assert plan.counts == {k: (31, 31, 225) for k in _LOOPED}
    small = _fa.tile_plan(8192, 128, 128, True, window=512, blk_q=256,
                          blk_k=256)
    assert small.counts == {k: (93, 62, 931) for k in _LOOPED}
    assert _fa.tile_plan(8192, 128, 128, True, window=4096).counts \
        == {k: (108, 24, 148) for k in _LOOPED}
    # a window as long as the sequence is the causal mask, counted the same
    assert _fa.tile_plan(8192, 128, 128, True, window=8192).counts \
        == {k: (136, 16, 120) for k in _LOOPED}


def test_without_a_window_the_program_is_the_one_it_was():
    """``window=None`` (the default) traces what a call without the
    argument traces, to the letter of the jaxpr, and plans what it planned;
    a window needs the causal mask."""
    q, k, v = _qkv(12, b=1, s=300, h=2, d=64)
    text = lambda **kw: str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(q, k, v, causal=True, **kw)),
        argnums=(0, 1, 2)))(q, k, v))
    assert text() == text(window=None)
    assert text() != text(window=100)
    for shape in ((1024, 128, 128), (8192, 256, 128), (8192, 256, 256)):
        assert _fa.tile_plan(*shape, True, window=None) \
            == _fa.tile_plan(*shape, True)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=100)
