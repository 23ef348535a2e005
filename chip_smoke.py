#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the PS training paths run on the chip.

    python chip_smoke.py               # every phase, on the chips JAX finds
    python chip_smoke.py --devices 4   # the same, refusing anything but 4 chips
    python chip_smoke.py --phase NAME  # one phase, in this process (debugging)

This is smoke output, not a benchmark: seconds printed here say that the
program started, compiled and stepped — nothing about how fast it is.

One process holds a chip at a time.  The launcher below never imports JAX; it
runs each phase as its own child process, one after another, with a time
limit that kills the child's whole process group, and stops with a non-zero
exit at the first phase that fails.  Every phase first asserts
``jax.devices()[0].platform == "tpu"`` — with ``JAX_PLATFORMS=cpu``, or with
no chip, the script fails within seconds and prints no result — and no phase
catches an exception and carries on.

Phases (each a few steps on a fixed synthetic batch; each asserts a finite,
falling loss, parameters resident on TPU devices and one compiled program
for all steps, and prints compile seconds, seconds per step and peak HBM):

  sync_resnet18         train.main: ResNet-18 / CIFAR-10 shapes, bf16, 1024/chip
  sync_resnet18_blockq  the same through --codec blockq (and --codec bf16 on
                        several chips); the compiled step must hold the
                        Mosaic custom call
  lm_flash              d1024 x L12 x 16 heads, seq 1024, 16/chip, bf16, flash
                        attention, through SGD(...).compile_step().step();
                        then train.main --model transformer --attn flash
  glm_flash             GLM-4.7-Flash at published widths, 1 dense + 1 expert
                        layer + the MTP module, 1 x 8192 tokens a chip, bf16,
                        through Adam(...).compile_step(has_aux).step(): the
                        flash kernels at a 256-wide q / k and a 256-wide v
  phi_flash             Phi-4-mini-flash-reasoning at published widths, one
                        Mamba, one window and one full differential-attention
                        layer, 1 x 8192 tokens a chip, bf16, through
                        Adam(...).compile_step(has_aux).step(): the scan at
                        [1, 8192, 5120, 16] and the flash kernels at a 64-wide
                        q / k and a 128-wide v, with and without window=512
  kernel_parity         every Pallas kernel against its jnp reference
  kda_kernels           the KDA recurrence's kernels against kda_chunked at
                        [1, 8192, 32, 128]: o and the five gradients
  async_inprocess       train.main --async-ps (transformer, flash) and the
                        AsyncSGD ResNet-18 program
  tcp_pair              the TCP roles: --serve with the CPU forced in its
                        environment, and one --connect worker per chip
  multichip             (several chips) 4-chip vs 1-chip loss, ZeRO shards,
                        the sp/tp/ep/pp/hybrid rungs on the real devices
  cache_reuse           a fresh process finds sync_resnet18's program in the
                        persistent compile cache

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

``--tiny-cpu`` is the development mode: toy sizes on the CPU platform with
the kernels under the Pallas interpreter, to debug a phase before spending
chip time on it.  It never prints a result and always exits non-zero.
"""

from __future__ import annotations

import argparse
import functools
import json
import logging
import os
import re
import signal
import subprocess
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))

PHASES = ("sync_resnet18", "sync_resnet18_blockq", "lm_flash", "glm_flash",
          "phi_flash", "kernel_parity", "kda_kernels", "async_inprocess",
          "tcp_pair", "multichip", "cache_reuse")
# Seconds a phase may take before its process group is killed.  The sum
# stays under the 1200 s the whole script is allowed.
PHASE_TIMEOUT_S = {"lm_flash": 420, "glm_flash": 300, "kernel_parity": 300,
                   "multichip": 420}
DEFAULT_TIMEOUT_S = 300



def chip_env(i: int) -> dict:
    """One worker process per chip: what a child's environment must say
    BEFORE it imports jax so that libtpu 0.0.34 opens chip ``i`` only, as a
    1x1x1 slice of its own (README "Running on the chip").  Found on the
    four-chip v5e host: all three are needed — with TPU_VISIBLE_CHIPS
    alone, three of four concurrent processes abort on libtpu's
    multi-process lockfile."""
    return {"TPU_VISIBLE_CHIPS": str(i),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1"}


# --------------------------------------------------------------------------
# sizes
# --------------------------------------------------------------------------

FULL = dict(
    resnet_batch=1024, resnet_steps=10,
    lm=dict(vocab_size=32768, d_model=1024, n_heads=16, n_layers=12,
            d_ff=4096), lm_seq=1024, lm_batch=16, lm_steps=4,
    cli_lm_seq=1024, cli_lm_batch=8,
    glm=dict(vocab_size=19360, d_model=2048, n_layers=2, first_k_dense=1,
             d_ff=10240, d_expert=1536, n_experts=64,
             experts_held=tuple(range(8)), top_k=4, n_shared=1,
             routed_scale=1.8, n_heads=20, q_lora_rank=768, kv_lora_rank=512,
             qk_nope_dim=192, qk_rope_dim=64, v_dim=256, rope_theta=1e6),
    glm_seq=8192, glm_steps=4,
    phi=dict(vocab_size=25008, d_model=2560, d_ff=10240, n_heads=40,
             n_kv_heads=20, window=512, d_inner=5120, d_state=16, d_conv=4,
             dt_rank=160,
             layers=(("mamba", 0), ("swa", 1), ("full_kv", 17))),
    phi_seq=8192, phi_steps=4,
    async_lm_seq=256, async_lm_batch=8, async_updates=20,
    async_resnet_batch=512, async_resnet_updates=24,
    flash_shapes=((2, 1024, 16, 64), (2, 777, 16, 64)),
    kda_shape=(1, 8192, 32, 128),
    mlp_batch=512)
TINY = dict(
    resnet_batch=8, resnet_steps=10,
    lm=dict(vocab_size=128, d_model=64, n_heads=4, n_layers=2, d_ff=128),
    lm_seq=128, lm_batch=2, lm_steps=4,
    cli_lm_seq=128, cli_lm_batch=2,
    glm=dict(vocab_size=128, d_model=64, n_layers=2, first_k_dense=1,
             d_ff=96, d_expert=32, n_experts=16, experts_held=(0, 1, 2, 3),
             top_k=4, n_shared=1, routed_scale=1.8, n_heads=2, q_lora_rank=24,
             kv_lora_rank=32, qk_nope_dim=24, qk_rope_dim=8, v_dim=32,
             rope_theta=1e6),
    glm_seq=128, glm_steps=4,
    phi=dict(vocab_size=128, d_model=64, d_ff=96, n_heads=4, n_kv_heads=2,
             window=48, d_inner=128, d_state=4, d_conv=4, dt_rank=4,
             layers=(("mamba", 0), ("swa", 1), ("full_kv", 17))),
    phi_seq=160, phi_steps=4,
    async_lm_seq=128, async_lm_batch=2, async_updates=20,
    async_resnet_batch=8, async_resnet_updates=24,
    flash_shapes=((1, 256, 2, 64), (1, 200, 2, 64)),
    kda_shape=(1, 200, 2, 128),
    mlp_batch=64)


def emit(rec: dict) -> None:
    print("SMOKE " + json.dumps(rec), flush=True)


# --------------------------------------------------------------------------
# phase plumbing (child side: these import jax)
# --------------------------------------------------------------------------


class CompileLog(logging.Handler):
    """Every program JAX compiles in this process, by name, from its own
    ``Compiling <name> with global shapes ...`` records — how a phase shows
    that all its steps ran ONE compiled program."""

    def __init__(self):
        super().__init__()
        self.names: list = []
        log = logging.getLogger("jax._src.interpreters.pxla")
        log.setLevel(logging.DEBUG)
        log.propagate = False       # collected here, not printed
        log.addHandler(self)

    def emit(self, record) -> None:
        if str(record.msg).startswith("Compiling ") and record.args:
            self.names.append(str(record.args[0]))

    def count(self, name: str) -> int:
        return self.names.count(f"jit({name})")


class Run:
    """What every phase starts with: the compile cache placed, the platform
    asserted, the device line printed; and what it ends with: the result
    line (peak HBM, cache hits)."""

    def __init__(self, phase: str, args):
        import importlib.metadata

        import jax
        import jaxlib

        from pytorch_ps_mpi_tpu import native
        from pytorch_ps_mpi_tpu.ops.pallas_kernels import impl_for_platform
        from pytorch_ps_mpi_tpu.utils.compile_cache import (
            CacheCounter, configure_compile_cache)

        self.phase = phase
        self.tiny = args.tiny_cpu
        self.sizes = TINY if self.tiny else FULL
        cache_dir = configure_compile_cache()
        self.cache = CacheCounter()
        self.compiles = CompileLog()
        self.devices = jax.devices()
        self.platform = self.devices[0].platform
        want = "cpu" if self.tiny else "tpu"
        if self.platform != want:
            raise SystemExit(
                f"chip_smoke: phase {phase} needs platform {want!r}, JAX "
                f"reports {self.platform!r} ({self.devices[0].device_kind})")
        if args.devices and len(self.devices) != args.devices:
            raise SystemExit(
                f"chip_smoke: --devices {args.devices}, but JAX reports "
                f"{len(self.devices)}: {self.devices}")
        self.n = len(self.devices)
        # Mosaic on the chip; in --tiny-cpu the interpreter, by name.
        self.impl = impl_for_platform(self.platform, cpu="interpret")
        emit({"phase": phase, "platform": self.platform,
              "device_kind": self.devices[0].device_kind, "count": self.n,
              "jax": jax.__version__, "jaxlib": jaxlib.__version__,
              "libtpu": importlib.metadata.version("libtpu"),
              "cache_dir": cache_dir,
              # The C++ serializer this tree's sources build (and load):
              "native_build": native.build_id()})

    def done(self, **fields) -> None:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in self.devices]
        peaks = [p for p in peaks if p is not None]
        emit({"phase": self.phase, "ok": True, **fields,
              "peak_bytes_in_use": max(peaks) if peaks else None,
              "cache_hits": self.cache.hits,
              "cache_misses": self.cache.misses,
              "note": "smoke output, not a benchmark"})


class _Tee:
    def __init__(self, *streams):
        self.streams = streams

    def write(self, s):
        for st in self.streams:
            st.write(s)
        return len(s)

    def flush(self):
        for st in self.streams:
            st.flush()

    def close(self):
        pass


def run_cli(argv: list):
    """``train.main(argv)`` in this process — the entry point a user calls
    — returning what it returned and everything it printed."""
    import contextlib
    import io

    from pytorch_ps_mpi_tpu import train

    print("$ python -m pytorch_ps_mpi_tpu.train " + " ".join(argv),
          flush=True)
    buf = io.StringIO()
    with contextlib.redirect_stdout(_Tee(sys.stdout, buf)), \
            contextlib.redirect_stderr(_Tee(sys.stderr, buf)):
        ret = train.main(argv)
    return ret, buf.getvalue()


def logged_losses(log: str, what: str = "step") -> list:
    """The losses the training loop itself printed (``step N  loss X`` /
    ``async update N  loss X``)."""
    return [float(m.group(1)) for m in re.finditer(
        what + r"\s+\d+\s+loss\s+(\S+)", log)]


def check_falling(losses: list, what: str) -> None:
    import math

    if len(losses) < 2 or not all(math.isfinite(x) for x in losses):
        raise AssertionError(f"{what}: need >= 2 finite losses, got {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{what}: loss did not fall: {losses}")


def check_on_platform(tree, platform: str, what: str) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        bad = [d for d in leaf.devices() if d.platform != platform]
        if bad:
            raise AssertionError(f"{what}: leaf lives on {bad}")


def sync_opt_fields(run: Run, opt, losses: list, what: str) -> dict:
    """The checks every sync-PS phase makes on the optimizer train.main (or
    the API) handed back, and the numbers it prints."""
    import statistics

    check_falling(losses, what)
    check_on_platform((opt.params, opt.state), run.platform, what)
    if opt.mesh.size != run.n:
        raise AssertionError(f"{what}: mesh of {opt.mesh.size}, {run.n} "
                             f"devices visible")
    n_compiles = run.compiles.count("spmd_step")
    run.compiles.names.clear()
    if n_compiles != 1:
        raise AssertionError(
            f"{what}: the step program was compiled {n_compiles} times — "
            f"a step after the first recompiled")
    t = opt.timings
    return {"what": what, "losses": [round(x, 4) for x in losses],
            "mesh_devices": opt.mesh.size,
            "compile_s": round(t[0]["iallgather_prepare_time"], 2),
            "step_s": round(statistics.median(
                x["isend_time"] + x["comm_wait"] for x in t[1:]), 4)}


def mosaic_kernels(run: Run, opt, batch, need: set) -> list:
    """Names of the Mosaic kernels in the step program ``opt`` runs; fails
    unless the COMPILED step holds the ``tpu_custom_call`` and every kernel
    in ``need`` — a reference or interpreter lowering cannot pass."""
    if run.platform != "tpu":
        return []
    lowered = opt._step_fn.lower(opt.params, opt.state, opt.aux,
                                 opt._shard_batch(batch))
    names = set(re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    if "tpu_custom_call" not in lowered.compile().as_text():
        raise AssertionError("compiled step holds no tpu_custom_call")
    if not need <= names:
        raise AssertionError(f"step lowers kernels {sorted(names)}, "
                             f"needs {sorted(need)}")
    return sorted(names)


def flash_calls(seq: int, d: int, dv: int) -> set:
    """The flash calls `tile_plan` names for a causal head of ``seq`` rows
    at the widths ``d`` / ``dv``: one backward call where the q side is
    whole in VMEM."""
    from pytorch_ps_mpi_tpu.ops.flash_attention import BLOCK, tile_plan

    pad = lambda n: -(-n // BLOCK) * BLOCK
    return set(tile_plan(pad(seq), pad(d), pad(dv), True).tiles)


def check_batch_on_all_devices(opt, batch) -> None:
    import jax

    for leaf in jax.tree.leaves(opt._shard_batch(batch)):
        devs = {s.device for s in leaf.addressable_shards}
        if len(devs) != opt.mesh.size:
            raise AssertionError(
                f"batch leaf on {len(devs)} of {opt.mesh.size} devices")
        if leaf.addressable_shards[0].data.shape[0] * len(devs) \
                != leaf.shape[0]:
            raise AssertionError("batch is not split evenly over the mesh")


# --------------------------------------------------------------------------
# the phases
# --------------------------------------------------------------------------


def resnet_argv(run: Run, *extra) -> list:
    b = run.sizes["resnet_batch"] * run.n
    return ["--model", "resnet18", "--dataset", "cifar10", "--bf16",
            "--batch-size", str(b), "--n-examples", str(b),
            "--steps", str(run.sizes["resnet_steps"]), "--lr", "0.05",
            *extra]


def resnet_batch(run: Run) -> dict:
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_cifar10

    x, y = synthetic_cifar10(run.sizes["resnet_batch"] * run.n)
    return {"x": x, "y": y}


def phase_sync_resnet18(run: Run) -> None:
    """The README's quick-start path at the flagship's full width:
    identity codec, default bucket_mb."""
    opt, log = run_cli(resnet_argv(run))
    fields = sync_opt_fields(run, opt, logged_losses(log), "resnet18")
    check_batch_on_all_devices(opt, resnet_batch(run))
    run.done(**fields)


def phase_sync_resnet18_blockq(run: Run) -> None:
    opt, log = run_cli(resnet_argv(run, "--codec", "blockq"))
    fields = sync_opt_fields(run, opt, logged_losses(log), "resnet18 blockq")
    fields["mosaic_kernels"] = mosaic_kernels(
        run, opt, resnet_batch(run),
        {"_quantize_kernel", "_dequant_sum_kernel"})
    fields["codec_impl"] = opt.code.impl
    if run.n > 1:
        # cast_sum earns its keep where there is more than one rank to sum.
        del opt
        opt, log = run_cli(resnet_argv(run, "--codec", "bf16"))
        bf = sync_opt_fields(run, opt, logged_losses(log), "resnet18 bf16")
        bf["mosaic_kernels"] = mosaic_kernels(
            run, opt, resnet_batch(run), {"_cast_sum_kernel"})
        fields["bf16"] = bf
    run.done(**fields)


def phase_lm_flash(run: Run) -> None:
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_lm
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    # The LM the r05 record measured, through the optimizer API (the CLI's
    # transformer is fixed at d256 x L4).
    sz = run.sizes
    seq, batch = sz["lm_seq"], sz["lm_batch"] * run.n
    model = TransformerLM(
        **sz["lm"], max_len=seq, dtype=jnp.bfloat16,
        attn=functools.partial(flash_attention, causal=True, impl=run.impl))
    params = build_lm(model, seq_len=seq)
    opt = SGD(list(params.items()), lr=0.01, momentum=0.9,
              mesh=make_ps_mesh())
    opt.compile_step(make_lm_loss(model))
    b = lm_batch(synthetic_lm(batch, seq_len=seq,
                              vocab=sz["lm"]["vocab_size"], seed=0))
    losses = [opt.step(b)[0] for _ in range(sz["lm_steps"])]
    head = sz["lm"]["d_model"] // sz["lm"]["n_heads"]
    fields = sync_opt_fields(run, opt, losses, "lm d%d x L%d" % (
        sz["lm"]["d_model"], sz["lm"]["n_layers"]))
    fields["mosaic_kernels"] = mosaic_kernels(
        run, opt, b, flash_calls(seq, head, head))
    check_batch_on_all_devices(opt, b)
    del opt, params

    # The CLI's own LM path.
    cb = sz["cli_lm_batch"] * run.n
    opt, log = run_cli(
        ["--model", "transformer", "--attn", "flash", "--bf16",
         "--seq-len", str(sz["cli_lm_seq"]), "--batch-size", str(cb),
         "--n-examples", str(cb), "--steps", "10", "--lr", "0.05"])
    cli = sync_opt_fields(run, opt, logged_losses(log), "cli transformer")
    cli["mosaic_kernels"] = mosaic_kernels(
        run, opt, lm_batch(synthetic_lm(cb, seq_len=sz["cli_lm_seq"])),
        flash_calls(sz["cli_lm_seq"], 32, 32))   # the CLI's d256 / 8 heads
    fields["cli"] = cli
    run.done(**fields)


def phase_glm_flash(run: Run) -> None:
    """The GLM-MoE LM (rotary latent attention, expert layer, MTP module
    through the shared head) at its published widths and a small depth: the
    compiled step holds the flash kernels `tile_plan` names, here at
    256 / 256."""
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import Adam
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_lm
    from pytorch_ps_mpi_tpu.models.glm_moe import (GlmMoeConfig, GlmMoeLM,
                                                   glm_aux, make_glm_loss)
    from pytorch_ps_mpi_tpu.models.transformer import lm_batch
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.utils.flatten import named_params

    sz = run.sizes
    seq, cfg = sz["glm_seq"], GlmMoeConfig(**sz["glm"], dtype=jnp.bfloat16)
    scale = (cfg.qk_nope_dim + cfg.qk_rope_dim) ** -0.5
    model = GlmMoeLM(cfg, attn=functools.partial(
        flash_attention, causal=True, scale=scale, impl=run.impl))
    ids = jnp.zeros((1, 8), jnp.int32)
    params = jax.jit(lambda key: named_params(GlmMoeLM(
        GlmMoeConfig(**sz["glm"])).init(key, ids, ids, ids)["params"]))(
            jax.random.PRNGKey(0))
    opt = Adam(list(params.items()), lr=1e-4, mesh=make_ps_mesh())
    del params
    opt.compile_step(make_glm_loss(model), has_aux=True, aux=glm_aux(model))
    b = lm_batch(synthetic_lm(run.n, seq_len=seq, vocab=cfg.vocab_size,
                              seed=0))
    losses = [opt.step(b)[0] for _ in range(sz["glm_steps"])]
    fields = sync_opt_fields(run, opt, losses, "glm-moe d%d x L%d + mtp" % (
        cfg.d_model, cfg.n_layers))
    widths = [cfg.qk_nope_dim + cfg.qk_rope_dim, cfg.v_dim]
    fields["mosaic_kernels"] = mosaic_kernels(run, opt, b,
                                              flash_calls(seq, *widths))
    fields["flash_widths"] = widths
    check_batch_on_all_devices(opt, b)
    run.done(**fields)


def phase_phi_flash(run: Run) -> None:
    """SambaY with differential attention at Phi-4-mini-flash-reasoning's
    published widths and a small depth: a Mamba layer (the blocked scan at
    `[1, 8192, 5120, 16]`), a window layer and a full layer, whose flash
    calls at 64 / 128 the compiled step has to hold."""
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import Adam
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_lm
    from pytorch_ps_mpi_tpu.models.sambay import (SambaYConfig, SambaYLM,
                                                  make_sambay_loss,
                                                  sambay_aux)
    from pytorch_ps_mpi_tpu.models.transformer import lm_batch
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.utils.flatten import named_params

    sz = run.sizes
    seq, cfg = sz["phi_seq"], SambaYConfig(**sz["phi"], dtype=jnp.bfloat16)
    model = SambaYLM(cfg, attn=functools.partial(
        flash_attention, causal=True, scale=cfg.head_dim ** -0.5,
        impl=run.impl))
    params = jax.jit(lambda key: named_params(SambaYLM(
        SambaYConfig(**sz["phi"])).init(
            key, jnp.zeros((1, 8), jnp.int32))["params"]))(
                jax.random.PRNGKey(0))
    opt = Adam(list(params.items()), lr=1e-4, mesh=make_ps_mesh())
    del params
    opt.compile_step(make_sambay_loss(model), has_aux=True,
                     aux=sambay_aux(model))
    b = lm_batch(synthetic_lm(run.n, seq_len=seq, vocab=cfg.vocab_size,
                              seed=0))
    losses = [opt.step(b)[0] for _ in range(sz["phi_steps"])]
    fields = sync_opt_fields(run, opt, losses, "sambay d%d x L%d" % (
        cfg.d_model, len(cfg.layers)))
    widths = [cfg.head_dim, 2 * cfg.head_dim]
    fields["mosaic_kernels"] = mosaic_kernels(run, opt, b,
                                              flash_calls(seq, *widths))
    fields["flash_widths"], fields["window"] = widths, cfg.window
    fields["scan"] = [run.n, seq, cfg.d_inner, cfg.d_state]
    check_batch_on_all_devices(opt, b)
    run.done(**fields)


def phase_kernel_parity(run: Run) -> None:
    """Each Pallas kernel against its jnp reference, on this device, at the
    shapes the phases above use and at awkward ones.  Codes must be equal
    bit for bit; scales and f32 sums close."""
    from collections import OrderedDict

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.ops import pallas_kernels as pk
    from pytorch_ps_mpi_tpu.ops.codecs import BlockQuantizeCodec
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel import overlap
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh
    from pytorch_ps_mpi_tpu.parallel.ring_attention import dense_attention

    impl = run.impl
    rng = np.random.RandomState(0)
    eps = float(np.finfo(np.float32).eps)
    checks = []

    def close(got, want, tol, what):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        err = float(np.abs(got - want).max())
        bound = tol * max(float(np.abs(want).max()), 1e-30)
        if not err <= bound:
            raise AssertionError(f"{what}: max err {err:g} > {bound:g}")
        return err

    # (n elements, block rows, world): awkward ones (a whole tile, a ragged
    # tail, fewer elements than a block), then ResNet-18's largest and
    # smallest leaf at this world size.
    world = max(run.n, 2)
    cases = [(512 * 128, 512, 1), (100_000, 512, 4), (37, 8, 2),
             (3 * 512 * 128 + 5, 512, 8),
             (3 * 3 * 512 * 512, 512, world), (64, 8, world)]
    if run.tiny:
        cases = cases[:4]
    for n, rows, w in cases:
        flat = jnp.asarray(rng.randn(n).astype(np.float32))
        x2d, _ = pk.pad_to_blocks(flat, rows)
        for bits in (8, 16):
            q_k, s_k = pk.block_quantize(x2d, bits=bits, block_rows=rows,
                                         impl=impl)
            q_r, s_r = pk.block_quantize(x2d, bits=bits, block_rows=rows,
                                         impl="ref")
            if not np.array_equal(np.asarray(q_k), np.asarray(q_r)):
                raise AssertionError(
                    f"block_quantize codes differ (n={n} rows={rows} "
                    f"bits={bits})")
            close(s_k, s_r, 2 * eps, f"block_quantize scales n={n}")
        # Rank-distinct contributions for the cross-rank sums.
        per_rank = [pk.block_quantize(x2d * (r + 1.0), bits=8,
                                      block_rows=rows, impl="ref")
                    for r in range(w)]
        qs = jnp.stack([q for q, _ in per_rank])
        ss = jnp.stack([s for _, s in per_rank])
        d_k = pk.block_dequant_sum(qs, ss, block_rows=rows, impl=impl)
        d_r = pk.block_dequant_sum(qs, ss, block_rows=rows, impl="ref")
        close(d_k, d_r, 8 * eps, f"block_dequant_sum n={n} world={w}")
        xb = jnp.stack([(x2d * (r + 1.0)).astype(jnp.bfloat16)
                        for r in range(w)])
        c_k = pk.cast_sum(xb, block_rows=rows, impl=impl)
        c_r = pk.cast_sum(xb, block_rows=rows, impl="ref")
        close(c_k, c_r, 8 * eps, f"cast_sum n={n} world={w}")
        checks.append(f"codec n={n} rows={rows} world={w}")

    # Fused bucket encode: a 4 MiB bucket of uneven leaves, codes compared
    # with the reference bit for bit; then the whole fused exchange on the
    # mesh of every visible device.
    shapes = ([(3, 3, 256, 256), (3, 3, 256, 128), (256,), (1000, 77)]
              if not run.tiny else [(40, 7), (111,), (5, 3, 2)])
    cot = OrderedDict(("g%d" % i, jnp.asarray(
        rng.randn(*s).astype(np.float32))) for i, s in enumerate(shapes))
    enc = {i: jax.jit(lambda c, i=i: overlap._blockq_bucket_encode(
        c, BlockQuantizeCodec(impl=i))[:2]) for i in (impl, "ref")}
    (q_k, s_k), (q_r, s_r) = enc[impl](cot), enc["ref"](cot)
    if not np.array_equal(np.asarray(q_k), np.asarray(q_r)):
        raise AssertionError("fused bucket encode: codes differ")
    close(s_k, s_r, 2 * eps, "fused bucket encode scales")
    mesh = make_ps_mesh()

    def fused(i):
        codec = BlockQuantizeCodec(impl=i)

        def body(scale):
            return overlap._sync_blockq_fused(
                OrderedDict((n, g * scale[0]) for n, g in cot.items()),
                "ps", codec)
        return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("ps"),
                                     out_specs=P(), check_vma=False))(
            np.arange(1, run.n + 1, dtype=np.float32))
    f_k, f_r = fused(impl), fused("ref")
    for n in cot:
        close(f_k[n], f_r[n], 8 * eps * run.n, f"fused exchange {n}")
    checks.append(f"fused bucket encode {sum(g.size for g in cot.values())}"
                  f" elements, exchange on {run.n} device(s)")

    # Flash attention, forward and all three gradients, against dense
    # attention computed in f32 from the same bf16 inputs.
    for shape in run.sizes["flash_shapes"]:
        mk = lambda: jnp.asarray(rng.randn(*shape).astype(np.float32)
                                 ).astype(jnp.bfloat16)
        q, k, v, tgt = mk(), mk(), mk(), mk()

        def loss(attn, up):
            def f(q, k, v):
                o = attn(up(q), up(k), up(v), causal=True)
                return jnp.sum((o.astype(jnp.float32)
                                - tgt.astype(jnp.float32)) ** 2), o
            return f
        f32 = lambda x: x.astype(jnp.float32)
        flash = functools.partial(flash_attention, impl=impl)
        (_, o_k), g_k = jax.jit(jax.value_and_grad(
            loss(flash, lambda x: x), argnums=(0, 1, 2), has_aux=True))(
            q, k, v)
        (_, o_r), g_r = jax.jit(jax.value_and_grad(
            loss(dense_attention, f32), argnums=(0, 1, 2), has_aux=True))(
            q, k, v)
        close(o_k, o_r, 2e-2, f"flash forward {shape}")
        for name, a, b in zip("qkv", g_k, g_r):
            close(a, b, 4e-2, f"flash d{name} {shape}")
        checks.append(f"flash fwd+dq+dk+dv {list(shape)} bf16 causal")
    run.done(impl=impl, checks=checks)


def phase_kda_kernels(run: Run) -> None:
    """The KDA recurrence as kernels (`ops/kda_pallas.py`) against the plain
    `kda_chunked`, on this device, from the same bf16 inputs: the largest
    difference of o and of each of the five gradients, as a share of the
    plain code's largest entry.  Both round their products' inputs to bf16,
    at different places, so they differ by rounding; the same plain code in
    f32 says how far either is from the recurrence itself."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_ps_mpi_tpu.ops.kda import kda_chunked
    from pytorch_ps_mpi_tpu.ops.kda_pallas import kda_kernels

    b, s, h, d = shape = run.sizes["kda_shape"]
    rng = np.random.RandomState(0)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    q, k = (f32(unit(rng.randn(*shape))) for _ in range(2))
    v, tgt = f32(rng.randn(*shape)), f32(rng.randn(*shape))
    # log-decays from a thousandth to two a token and channel
    g = f32(-np.exp(rng.uniform(np.log(1e-3), np.log(2.0), shape)))
    beta = f32(1.0 / (1.0 + np.exp(-rng.randn(b, s, h))))
    exact = (q * d ** -0.5, k, v, g, beta)
    rounded = (*(x.astype(jnp.bfloat16) for x in exact[:3]), g, beta)

    def both(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum((o.astype(jnp.float32) - tgt) ** 2), o
        return jax.jit(jax.value_and_grad(loss, argnums=range(5),
                                          has_aux=True))

    def worst(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        return float(np.abs(got - want).max() / np.abs(want).max())

    def compare(got, want):
        ((_, o_g), g_g), ((_, o_w), g_w) = got, want
        return {"o": worst(o_g, o_w), **{
            "d" + n: worst(a, c)
            for n, a, c in zip(("q", "k", "v", "g", "beta"), g_g, g_w)}}

    kernels = both(functools.partial(kda_kernels, impl=run.impl))(*rounded)
    plain = both(kda_chunked)(*rounded)
    with jax.default_matmul_precision("highest"):
        truth = both(kda_chunked)(*exact)
    against_plain = compare(kernels, plain)
    against_f32 = compare(kernels, truth)
    plain_against_f32 = compare(plain, truth)
    print(f"[kda_kernels] {list(shape)} bf16, max relative error of the "
          f"kernels against kda_chunked: {against_plain}; against "
          f"kda_chunked in f32: {against_f32} (kda_chunked itself: "
          f"{plain_against_f32})", flush=True)
    for name, err in against_plain.items():
        if not err <= 4e-2:
            raise AssertionError(
                f"kda kernels {name}: {err:g} of the plain code's largest "
                f"entry")
    for name, err in against_f32.items():
        if not err <= 2 * max(plain_against_f32[name], 5e-3):
            raise AssertionError(
                f"kda kernels {name}: {err:g} from the f32 recurrence, the "
                f"plain code {plain_against_f32[name]:g}")
    run.done(impl=run.impl, shape=list(shape), against_plain=against_plain,
             against_f32=against_f32, plain_against_f32=plain_against_f32)


def async_lm_argv(run: Run) -> list:
    """The transformer the async phases train — async_inprocess and the TCP
    worker share it, so the worker finds its step program in the cache."""
    sz = run.sizes
    return ["--model", "transformer", "--attn", "flash", "--bf16",
            "--seq-len", str(sz["async_lm_seq"]),
            "--batch-size", str(sz["async_lm_batch"]),
            "--n-examples", str(sz["async_lm_batch"]), "--lr", "0.05",
            "--momentum", "0.5"]


def check_async_layout(run: Run, opt) -> dict:
    """PS on chip 0; with several chips, one worker on each OTHER chip."""
    want_workers = run.devices[1:] if run.n > 1 else run.devices[:1]
    if opt.ps_device != run.devices[0] \
            or list(opt.worker_devices) != list(want_workers):
        raise AssertionError(
            f"async layout: PS on {opt.ps_device}, workers on "
            f"{opt.worker_devices}; visible {run.devices}")
    check_on_platform(opt.params, run.platform, "async PS params")
    return {"ps_device": str(opt.ps_device),
            "worker_devices": [str(d) for d in opt.worker_devices]}


def phase_async_inprocess(run: Run) -> None:
    import jax.numpy as jnp
    import numpy as np

    from pytorch_ps_mpi_tpu.async_ps import AsyncSGD
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_cifar10
    from pytorch_ps_mpi_tpu.models import (build_model, cross_entropy,
                                           resnet18)
    from pytorch_ps_mpi_tpu.utils.flatten import unflatten_params

    sz = run.sizes
    opt, log = run_cli(["--async-ps", *async_lm_argv(run),
                        "--steps", str(sz["async_updates"])])
    cli_losses = logged_losses(log, "async update")
    check_falling(cli_losses, "async transformer")
    fields = {"cli_losses": cli_losses, **check_async_layout(run, opt)}
    del opt
    run.compiles.names.clear()

    # The AsyncSGD ResNet-18 program (BatchNorm in eval mode: the async PS
    # mirrors the reference's plain-params contract, no aux channel).
    model = resnet18(num_classes=10, small_inputs=True, dtype=jnp.bfloat16)
    params, aux = build_model(model, (1, 32, 32, 3))

    def loss_fn(params_named, batch):
        variables = {"params": unflatten_params(params_named),
                     "batch_stats": aux}
        logits = model.apply(variables, batch["x"], train=False)
        return cross_entropy(logits, batch["y"])

    # Default quota: one gradient per worker per update (1 on one chip, as
    # in the `resnet50-async-1chip` cell).  Gradients SUM, so lr scales
    # down with it.
    workers = max(1, run.n - 1)
    opt = AsyncSGD(list(params.items()), lr=0.02 / workers, momentum=0.5)
    opt.compile_step(loss_fn)
    check_async_layout(run, opt)
    x, y = synthetic_cifar10(sz["async_resnet_batch"], seed=0)
    fixed = {"x": x, "y": y}
    hist = opt.run(lambda rank, it: fixed, steps=sz["async_resnet_updates"])
    losses = hist["losses"]
    k = max(1, len(losses) // 3)
    check_falling([float(np.mean(losses[:k])), float(np.mean(losses[-k:]))],
                  "async resnet18")
    ranks = sorted({r for c in hist["contributors"] for r in c})
    if ranks != list(range(opt.num_workers)):
        raise AssertionError(f"async resnet18: contributions from ranks "
                             f"{ranks} of {opt.num_workers} workers")
    n_compiles = (run.compiles.count("worker_step"),
                  run.compiles.count("ps_apply"))
    if n_compiles[0] > opt.num_workers or n_compiles[1] != 1:
        raise AssertionError(
            f"async resnet18: worker_step / ps_apply compiled {n_compiles} "
            f"times for {opt.num_workers} worker device(s) and one PS")
    run.done(**fields, resnet_losses=[round(x, 4) for x in losses],
             contributing_ranks=ranks,
             mean_staleness=round(float(np.mean(hist["staleness"])), 2))


def phase_tcp_worker(run: Run, connect: str) -> None:
    """One --connect worker: the chip-holding half of tcp_pair."""
    worker, log = run_cli(["--connect", connect, *async_lm_argv(run)])
    m = re.search(r"done: (\d+) gradients pushed", log)
    if not m or int(m.group(1)) < 1:
        raise AssertionError("worker pushed no gradient")
    if worker.device.platform != run.platform:
        raise AssertionError(f"worker computed on {worker.device}")
    run.done(rank=worker.rank, device=str(worker.device),
             visible_chip=os.environ.get("TPU_VISIBLE_CHIPS"),
             pushed=int(m.group(1)))


def phase_multichip(run: Run) -> None:
    """Only with several chips: sum semantics across the real mesh, ZeRO
    shards on distinct chips, and the sp/tp/ep/pp/hybrid rungs."""
    b = str(run.sizes["mlp_batch"])
    mlp = ["--model", "mlp", "--dataset", "mnist", "--batch-size", b,
           "--n-examples", b, "--steps", "10"]
    # Gradients SUM over ranks: n chips at lr/n take the step one chip takes
    # at lr on the same global batch (the SKILL's probe, through the CLI).
    # (lr small enough that the step-10 loss is still far from zero.)
    _, log1 = run_cli([*mlp, "--n-devices", "1", "--lr", "0.002"])
    optn, logn = run_cli([*mlp, "--lr", str(0.002 / run.n)])
    l1, ln = logged_losses(log1), logged_losses(logn)
    check_falling(ln, f"mlp on {run.n} devices")
    if optn.mesh.size != run.n or len(l1) != len(ln) or any(
            abs(a - b) > 2e-3 * max(abs(a), 1.0) for a, b in zip(l1, ln)):
        raise AssertionError(f"{run.n}-device losses {ln} != 1-device {l1}")
    del optn

    optz, logz = run_cli([*mlp, "--zero"])
    check_falling(logged_losses(logz), "mlp zero")
    shard_devices = set()
    for name, st in optz.state.items():
        buf = st["momentum_buffer"]
        devs = {s.device for s in buf.addressable_shards}
        if len(devs) != run.n or buf.addressable_shards[0].data.shape[0] != 1:
            raise AssertionError(
                f"zero: {name} momentum on {len(devs)} devices, shard "
                f"{buf.addressable_shards[0].data.shape} of {buf.shape}")
        shard_devices |= devs
    del optz

    import __graft_entry__
    __graft_entry__.dryrun_multichip(run.n)
    run.done(losses_1_device=l1, losses_n_devices=ln,
             zero_shard_devices=sorted(str(d) for d in shard_devices),
             rungs="zero ef hybrid dp_sp_tp ulysses ep pp")


def phase_cache_reuse(run: Run) -> None:
    """A fresh process, the first phase's program: it must come out of the
    persistent compile cache, not out of the compiler."""
    argv = resnet_argv(run)
    argv[argv.index("--steps") + 1] = "2"
    opt, _ = run_cli(argv)
    if run.cache.hits < 1:
        raise AssertionError(
            f"no persistent-cache hit (misses={run.cache.misses}) for a "
            f"program sync_resnet18 compiled")
    run.done(first_step_s=round(
        opt.timings[0]["iallgather_prepare_time"], 2))


CHILD_PHASES = {
    "sync_resnet18": phase_sync_resnet18,
    "sync_resnet18_blockq": phase_sync_resnet18_blockq,
    "lm_flash": phase_lm_flash,
    "glm_flash": phase_glm_flash,
    "phi_flash": phase_phi_flash,
    "kernel_parity": phase_kernel_parity,
    "kda_kernels": phase_kda_kernels,
    "async_inprocess": phase_async_inprocess,
    "multichip": phase_multichip,
    "cache_reuse": phase_cache_reuse,
}


# --------------------------------------------------------------------------
# launcher side: no jax below this line
# --------------------------------------------------------------------------


class Child:
    """One child process in its own process group; stdout is passed through
    and its SMOKE records kept.  Whatever happens, `stop` leaves no process
    of the group behind."""

    def __init__(self, cmd: list, env: dict):
        self.records: list = []
        self.lines: list = []
        self.proc = subprocess.Popen(
            cmd, cwd=HERE, env=env, stdout=subprocess.PIPE, text=True,
            start_new_session=True)
        self._pump = threading.Thread(target=self._read, daemon=True)
        self._pump.start()

    def _read(self) -> None:
        for line in self.proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            self.lines.append(line)
            if line.startswith("SMOKE "):
                self.records.append(json.loads(line[6:]))

    def wait(self, timeout: float) -> "int | None":
        """Exit code, or None when the time limit fired."""
        try:
            rc = self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            return None
        self._pump.join(timeout=10)
        return rc

    def stop(self) -> None:
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()


def child_env(args, devices: "int | None", extra: "dict | None" = None):
    env = dict(os.environ)
    if args.tiny_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            f"platform_device_count={devices or 1}")
    env.update(extra or {})
    return env


def phase_cmd(args, phase: str, devices: "int | None", *extra) -> list:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           *extra]
    if devices:
        cmd += ["--devices", str(devices)]
    if args.tiny_cpu:
        cmd += ["--tiny-cpu"]
    return cmd


def fail(msg: str) -> "None":
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def run_child_phase(args, phase: str) -> list:
    child = Child(phase_cmd(args, phase, args.devices),
                  child_env(args, args.devices))
    try:
        rc = child.wait(PHASE_TIMEOUT_S.get(phase, DEFAULT_TIMEOUT_S))
    finally:
        child.stop()
    if rc is None:
        fail(f"phase {phase} hit its time limit")
    if rc != 0:
        fail(f"phase {phase} exited {rc}")
    if not any(r.get("ok") for r in child.records):
        fail(f"phase {phase} printed no result")
    return child.records


def run_tcp_pair(args, n_chips: int) -> list:
    """The real TCP roles.  The server needs no chip, so its environment
    forces the CPU platform; every worker is a process that owns one chip
    (with several chips, given to it by `chip_env` before it imports jax)."""
    sizes = TINY if args.tiny_cpu else FULL
    updates = "20"
    srv_env = child_env(args, 1, {"JAX_PLATFORMS": "cpu"})
    server = Child(
        [sys.executable, "-m", "pytorch_ps_mpi_tpu.train", "--serve", "0",
         "--steps", updates, "--quota", str(n_chips),
         "--model", "transformer", "--seq-len", str(sizes["async_lm_seq"]),
         "--batch-size", str(sizes["async_lm_batch"]),
         "--n-examples", str(sizes["async_lm_batch"]), "--lr", "0.05",
         "--momentum", "0.5"], srv_env)
    workers: list = []
    deadline = time.monotonic() + DEFAULT_TIMEOUT_S
    try:
        port = None
        while port is None and time.monotonic() < deadline:
            for line in list(server.lines):
                if line.startswith("serving on port "):
                    port = line.split()[-1]
            if server.proc.poll() is not None and port is None:
                fail(f"tcp_pair: server exited {server.proc.returncode} "
                     f"before serving")
            time.sleep(0.2)
        if port is None:
            fail("tcp_pair: server never printed its port")
        for i in range(n_chips):
            # With several chips each worker must see exactly the one chip
            # its environment gives it (--devices 1 makes it check).
            one = 1 if n_chips > 1 else args.devices
            extra = chip_env(i) if (n_chips > 1 and not args.tiny_cpu) else {}
            workers.append(Child(
                phase_cmd(args, "tcp_worker", one, "--connect",
                          f"127.0.0.1:{port}"),
                child_env(args, 1, extra)))
        rcs = [w.wait(max(1.0, deadline - time.monotonic()))
               for w in workers]
        src = server.wait(max(1.0, deadline - time.monotonic()))
    finally:
        for c in (server, *workers):
            c.stop()
    if src != 0 or any(rc != 0 for rc in rcs):
        fail(f"tcp_pair: server rc {src}, worker rcs {rcs}")
    records = [r for w in workers for r in w.records]
    done = [r for r in records if r.get("ok")]
    if len(done) != n_chips:
        fail(f"tcp_pair: {len(done)} of {n_chips} workers reported")
    if len({r["rank"] for r in done}) != n_chips:
        fail(f"tcp_pair: worker ranks {[r['rank'] for r in done]}")
    losses = logged_losses("".join(server.lines), "async update")
    try:
        check_falling(losses, "tcp_pair server")
    except AssertionError as e:
        fail(str(e))
    emit({"phase": "tcp_pair", "ok": True,
          "server": "JAX_PLATFORMS=cpu, rc 0", "server_losses": losses,
          "workers": [{k: r[k] for k in ("rank", "device", "visible_chip",
                                         "pushed", "cache_hits")}
                      for r in done]})
    return records


def launcher(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "pytorch_ps_mpi_tpu")):
        print("chip_smoke: the pytorch_ps_mpi_tpu package is not next to "
              "this script — nothing to smoke", file=sys.stderr)
        return 2
    t0 = time.monotonic()
    device = None
    for phase in PHASES:
        if phase == "multichip" and device["count"] == 1:
            continue
        print(f"=== phase {phase} (t+{time.monotonic() - t0:.0f}s)",
              flush=True)
        if phase == "tcp_pair":
            records = run_tcp_pair(args, device["count"])
        else:
            records = run_child_phase(args, phase)
        head = records[0]
        if not args.tiny_cpu and head.get("platform") != "tpu":
            fail(f"phase {phase} ran on {head.get('platform')!r}")
        if device is None:
            device = {"platform": head["platform"],
                      "kind": head["device_kind"], "count": head["count"]}
    print(f"=== all phases passed in {time.monotonic() - t0:.0f}s",
          flush=True)
    if args.tiny_cpu:
        print("chip_smoke: --tiny-cpu is a development run on the CPU: "
              "no result", file=sys.stderr)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--devices", type=int, default=None,
                    help="fail unless JAX reports exactly this many chips")
    ap.add_argument("--phase", choices=sorted([*CHILD_PHASES, "tcp_worker",
                                               "tcp_pair"]),
                    help="run one phase in this process")
    ap.add_argument("--connect", metavar="HOST:PORT",
                    help="(--phase tcp_worker) the server to join")
    ap.add_argument("--tiny-cpu", action="store_true",
                    help="development: toy sizes on the CPU, kernels "
                         "interpreted; never a result")
    args = ap.parse_args(argv)
    if args.phase is None:
        return launcher(args)
    if args.phase == "tcp_pair":
        run_tcp_pair(args, args.devices or 1)
        return 0
    sys.path.insert(0, HERE)
    run = Run(args.phase, args)
    if args.phase == "tcp_worker":
        phase_tcp_worker(run, args.connect)
    else:
        CHILD_PHASES[args.phase](run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
