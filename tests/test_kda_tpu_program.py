"""The KDA layer as the chip's compiler sees it, without the chip: a toy
`KDAttention` differentiated and compiled for a described v5e.  What the
benchmark's `kda_ms_step` rests on is pinned here: the program built for a
TPU gets the Mosaic kernels though this process's backend is the CPU, every
kernel call of the forward, the rematerialised forward and the backward
stays under the program's ``kda`` scope and carries its kernel's name, and
no loop of the plain code is left under that scope.  And the kernels compile
at the cell's shape — the flash kernels' two-level tiles too, at the shapes
of the cells that run them (PRs 31, 33, 35; the last under a window too), EVA
attention's kernels' form at EvaByte's (PR 40), and the selective scan's
kernels, and the state-space duality scan's `ssd_fwd` / `ssd_bwd` in a toy
`Mamba2Mixer` under `nn.remat` and at `nemotron3-nano-sync-1chip`'s shape:
they sit here because this is the one file that may describe a topology.

This is the one test file that describes a TPU topology (the
`on-chip-measurement` guide, section 2): only inside a fixture, never while
a module is imported.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from pytorch_ps_mpi_tpu.models.kimi_linear import KDAttention
from pytorch_ps_mpi_tpu.ops import kda_pallas
from pytorch_ps_mpi_tpu.utils import timing

KERNELS = {"kda_fwd", "kda_bwd"}
_CALL = re.compile(
    r'^\s*%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"'
    r'[^\n]*kernel_metadata=\{\s*"kernel":"(\w+)"', re.MULTILINE)


@pytest.fixture(scope="module")
def one_chip():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A program compiled for a described chip can be written to the
    # persistent cache but not read back without one.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.fixture(scope="module")
def program(one_chip):
    """`jax.grad` of a rematerialised toy layer (2 heads of 128, 256
    tokens), compiled for the chip and registered as `MPI_PS.step`
    registers its program."""
    layer = KDAttention(d_model=256, n_heads=2, head_dim=128, conv_size=4,
                        gate_rank=32, eps=1e-5, dtype=jnp.bfloat16)
    x = jnp.zeros((1, 256, 256), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))

    def loss(params, x):
        y = jax.checkpoint(layer.apply)(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    compiled = jax.jit(jax.grad(loss)).lower(
        _shapes(params, one_chip), _shapes(x, one_chip)).compile()
    timing.register_program("test.kda_tpu", compiled.as_text)
    return compiled.as_text(), timing.program_scopes("test.kda_tpu")


def test_every_kernel_call_is_under_the_kda_scope_by_name(program):
    text, scopes = program
    calls = _CALL.findall(text)
    assert {kernel for _, kernel in calls} == KERNELS
    # forward, the forward again under `jax.checkpoint`, and the backward
    assert sorted(kernel for _, kernel in calls) == [
        "kda_bwd", "kda_fwd", "kda_fwd"]
    for name, kernel in calls:
        assert name.startswith(kernel), (name, kernel)
        assert name in scopes, f"{name}: the registry did not read it"
        assert timing.in_scope(scopes[name], "kda"), scopes[name]
    backward = [scopes[n] for n, kernel in calls if kernel == "kda_bwd"]
    assert "transpose(" in backward[0]


def test_no_loop_of_the_plain_code_is_left_under_the_scope(program):
    _, scopes = program
    loops = [n for n, op in scopes.items()
             if timing.in_scope(op, "kda") and "while" in op]
    assert loops == []


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_the_kernels_compile_at_the_cells_shape(one_chip, what):
    """``[2, 8192, 32, 128]`` in bf16, decays and beta in f32: tiling and
    VMEM are the chip's compiler's to refuse."""
    b, s, h, d = 2, 8192, 32, 128
    wide = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    args = (wide, wide, wide,
            jax.ShapeDtypeStruct((b, s, h, d), jnp.float32, sharding=one_chip),
            jax.ShapeDtypeStruct((b, s, h), jnp.float32, sharding=one_chip))
    fn = functools.partial(kda_pallas.kda_kernels, impl="mosaic")
    if what == "backward":
        fn = jax.grad(lambda *a: jnp.sum(kda_pallas.kda_kernels(*a).astype(
            jnp.float32)), argnums=range(5))
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert {kernel for _, kernel in _CALL.findall(text)} <= KERNELS
    assert ("kda_bwd" if what == "backward" else "kda_fwd") in text


@pytest.mark.parametrize("b,s,h,d,dv,window", [
    (8, 1024, 16, 64, 64, None),    # gpt2m-*: a head in one tile, unrolled
    (2, 8192, 32, 192, 128, None),  # kimi-linear-sync-1chip's MLA: loops
    (1, 8192, 20, 256, 256, None),  # glm47-flash-sync-1chip: v as wide as q
    (1, 8192, 40, 64, 128, None),   # phi4flash-sync-1chip: full and cross
    (1, 8192, 40, 64, 128, 512),    # ... and its window layer (PR 35)
    (1, 8192, 32, 128, 128, None),  # nemotron3-nano-sync-1chip
])
def test_the_flash_kernels_compile_at_the_cells_shapes(one_chip, b, s, h, d,
                                                       dv, window):
    """The two-level flash kernels as `tile_plan` sizes them for the
    shapes the benchmark runs, forward and backward, through the chip's
    compiler: slices, loop bounds and VMEM are its to refuse; and the
    compiled program holds exactly the calls `tile_plan` names: at these
    shapes the q side of a head is whole in `flash_bwd_dkdv`'s tile, which
    writes dq too (8 MiB more of VMEM scratch at 256 / 256), so the backward
    is that one call (PR 34).  The row statistics cross HBM as rows: the
    forward returns the logsumexp as ``f32[BH, 1, S_pad]``, the backward
    takes it and ``delta`` so, and no call takes or returns a lane-replicated
    ``f32[BH, S_pad, 128]`` copy."""
    from pytorch_ps_mpi_tpu.ops import flash_attention as fa

    qk = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16, sharding=one_chip)
    grad = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, window=window).astype(jnp.float32)),
        argnums=(0, 1, 2))
    text = jax.jit(grad).lower(qk, qk, v).compile().as_text()
    pad = lambda n: -(-n // fa.BLOCK) * fa.BLOCK
    calls = list(fa.tile_plan(s, pad(d), pad(dv), True, window=window).tiles)
    assert calls == ["flash_fwd", "flash_bwd_dkdv"]
    assert sorted(kernel for _, kernel in _CALL.findall(text)) == sorted(calls)
    bh, s_pad = b * h, pad(s)
    f32 = {kernel: (results, operands)
           for kernel, results, operands in _flash_arrays(text)}
    assert sorted(f32) == sorted(calls)
    for kernel, (results, operands) in f32.items():
        assert [bh, s_pad, fa.BLOCK] not in results + operands, kernel
    assert f32["flash_fwd"] == ([[bh, 1, s_pad]], [])
    assert f32["flash_bwd_dkdv"] == ([], [[bh, 1, s_pad]] * 2)


def _flash_arrays(text):
    """``(kernel, results, operands)`` of each `flash_*` call in a compiled
    program, each list the dimensions of its f32 arrays."""
    f32 = lambda part: [[int(n) for n in dims.split(",")]
                        for dims in re.findall(r"\bf32\[([\d,]+)\]", part)]
    for call in _CALL.finditer(text):
        if not call.group(2).startswith("flash_"):
            continue
        results, _, rest = call.group(0).partition(" custom-call(")
        operands = rest.partition("operand_layout_constraints={")[2]
        yield (call.group(2), f32(results.partition(" = ")[2]),
               f32(operands.partition("}}")[0]))


def test_eva_attention_compiles_at_the_cells_shape(one_chip):
    """`ops.eva_attention`'s kernels' form at one row of `evabyte-sync-1chip`
    (`[1, 8192, 16, 128]`: the flash calls see `[4, 2048, 16, 128]`, a
    head-window one grid tile at 128 / 128, and give the row statistics as a
    second output), differentiated through both outputs and compiled by the
    chip's compiler: the two calls `tile_plan` names, each under the
    `eva_local` scope inside `eva_attn` (PR 40).  The statistics come out
    of `flash_fwd` as rows one sublane deep (``T(1,128)``) and reach the
    join copied once into full ``(8, 128)`` tiles, heads down the
    sublanes."""
    from pytorch_ps_mpi_tpu.ops import flash_attention as fa
    from pytorch_ps_mpi_tpu.ops.eva_attention import eva_attention

    x = jax.ShapeDtypeStruct((1, 8192, 16, 128), jnp.bfloat16,
                             sharding=one_chip)
    pool = jax.ShapeDtypeStruct((16, 128), jnp.float32, sharding=one_chip)

    def loss(q, k, v, phi, mu):
        o, mass = eva_attention(q, k, v, phi, mu, window=2048, chunk=16,
                                impl="mosaic")
        return jnp.sum(o.astype(jnp.float32)) + mass

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
        x, x, x, pool, pool).compile().as_text()
    calls = list(fa.tile_plan(2048, 128, 128, True).tiles)
    assert calls == ["flash_fwd", "flash_bwd_dkdv"]
    found = _CALL.findall(text)
    assert sorted(kernel for _, kernel in found) == sorted(calls)
    rows = re.findall(r"%(\S+) = f32\[64,1,2048\]\{2,1,0:T\(1,128\)[^}]*\} "
                      r"get-tuple-element\(%flash_fwd[\w.]*\), index=1", text)
    assert len(rows) == 1, rows
    assert re.search(r"= f32\[64,1,2048\]\{2,0,1:T\(8,128\)[^}]*\} "
                     rf"copy\(%{re.escape(rows[0])}\)", text)
    timing.register_program("eva_attention", lambda: text)
    scopes = timing.program_scopes("eva_attention")
    for name, _ in found:
        assert timing.in_scope(scopes[name], "eva_local") \
            and timing.in_scope(scopes[name], "eva_attn"), scopes[name]


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_the_selective_scan_compiles_at_the_cells_shape(one_chip, what):
    """`ops.selective_scan` at ``[1, 8192, 5120, 16]`` lowered for the TPU:
    the sizes are whole tiles, so the program holds the `ssm_fwd` /
    `ssm_bwd` kernels (tiling, the dynamic first index of the spread ``B``
    and ``C`` blocks and of the block's states, and VMEM are the chip's
    compiler's to refuse) and nothing near the 2.7 GB that the states of a
    whole row would be."""
    from pytorch_ps_mpi_tpu.ops.selective_scan import selective_scan

    rows, s, d, n = 1, 8192, 5120, 16
    shape = lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    args = (shape((rows, s, d), jnp.bfloat16), shape((rows, s, d)),
            shape((d, n)), shape((rows, s, n)), shape((rows, s, n)),
            shape((d,)))
    fn = selective_scan
    if what == "backward":
        fn = jax.grad(lambda *a: jnp.sum(selective_scan(*a).astype(
            jnp.float32)), argnums=range(6))
    compiled = jax.jit(fn).lower(*args).compile()
    kernels = sorted(kernel for _, kernel in _CALL.findall(compiled.as_text()))
    assert kernels == (["ssm_bwd", "ssm_fwd"] if what == "backward"
                       else ["ssm_fwd"])
    assert compiled.memory_analysis().temp_size_in_bytes < 3 << 29


SSD_KERNELS = {"ssd_fwd", "ssd_bwd"}


@pytest.fixture(scope="module")
def mamba_program(one_chip):
    """`jax.grad` of a toy `Mamba2Mixer` under `nn.remat` (2 groups of 2
    heads of 64, a state of 128, 256 tokens), compiled for the chip and
    registered as `MPI_PS.step` registers its program."""
    import flax.linen as nn

    from pytorch_ps_mpi_tpu.models.nemotron_h import (Mamba2Mixer,
                                                      NemotronHConfig)

    cfg = NemotronHConfig(
        vocab_size=64, d_model=128, pattern="M", d_expert=8, d_shared=8,
        n_experts=2, experts_held=(0,), top_k=1, routed_scale=1.0,
        n_heads=2, n_kv_heads=1, head_dim=64, mamba_heads=4,
        mamba_head_dim=64, n_groups=2, d_state=128, chunk=128,
        dtype=jnp.bfloat16)
    layer = nn.remat(Mamba2Mixer)(cfg)
    x = jnp.zeros((1, 256, 128), jnp.bfloat16)
    params = jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), x))

    def loss(params, x):
        y, _ = layer.apply(params, x)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    compiled = jax.jit(jax.grad(loss)).lower(
        _shapes(params, one_chip), _shapes(x, one_chip)).compile()
    timing.register_program("test.ssd_tpu", compiled.as_text)
    return compiled.as_text(), timing.program_scopes("test.ssd_tpu")


def test_every_ssd_kernel_call_is_under_the_ssd_scope_by_name(mamba_program):
    text, scopes = mamba_program
    calls = [(n, k) for n, k in _CALL.findall(text) if k in SSD_KERNELS]
    # forward, the forward again under the remat, and the backward
    assert sorted(kernel for _, kernel in calls) == [
        "ssd_bwd", "ssd_fwd", "ssd_fwd"]
    for name, kernel in calls:
        assert name.startswith(kernel), (name, kernel)
        assert name in scopes, f"{name}: the registry did not read it"
        assert timing.in_scope(scopes[name], "ssd"), scopes[name]
    backward = [scopes[n] for n, kernel in calls if kernel == "ssd_bwd"]
    assert "transpose(" in backward[0]


def test_no_loop_of_the_plain_chunk_carry_is_left_under_the_ssd_scope(
        mamba_program):
    _, scopes = mamba_program
    loops = [n for n, op in scopes.items()
             if timing.in_scope(op, "ssd") and "while" in op]
    assert loops == []


@pytest.mark.parametrize("what", ["forward", "backward"])
def test_the_ssd_kernels_compile_at_the_cells_shape(one_chip, what):
    """One Mamba-2 layer of `nemotron3-nano-sync-1chip`: x ``[1, 8192, 64,
    64]`` bf16, B and C ``[1, 8192, 8, 128]`` bf16, dt ``[1, 8192, 64]``
    f32: tiling and VMEM are the chip's compiler's to refuse."""
    from pytorch_ps_mpi_tpu.ops import ssd_pallas

    shape = lambda dims, dtype=jnp.float32: jax.ShapeDtypeStruct(
        dims, dtype, sharding=one_chip)
    group = shape((1, 8192, 8, 128), jnp.bfloat16)
    args = (shape((1, 8192, 64, 64), jnp.bfloat16), shape((1, 8192, 64)),
            shape((64,)), group, group, shape((64,)))
    fn = functools.partial(ssd_pallas.ssd_kernels, impl="mosaic")
    if what == "backward":
        fn = jax.grad(lambda *a: jnp.sum(ssd_pallas.ssd_kernels(*a)),
                      argnums=range(6))
    kernels = sorted(k for _, k in _CALL.findall(
        jax.jit(fn).lower(*args).compile().as_text()))
    assert kernels == (["ssd_bwd", "ssd_fwd"] if what == "backward"
                       else ["ssd_fwd"])
