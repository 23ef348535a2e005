"""Benchmark harness — the workloads, one at a time or as a plan.

    python bench.py                  # the TPU plan, then the CPU suite
    python bench.py --worker NAME    # one workload, in this process

``python bench.py`` prints ONE JSON line: the primary metric (ResNet-18 /
CIFAR-10 sync-PS throughput, the BASELINE.md headline config) with compact
per-workload summaries under ``extra``::

  {"metric": "resnet18_cifar10_sync_ps_throughput", "value": N,
   "unit": "images/sec/chip", "vs_baseline": N,
   "extra": {"backend": ..., "throughput": {...key scalars...},
             "errors": {...}}}

The line is hard-capped at ``HEADLINE_LINE_CAP`` so a tail capture can
always parse it; ``--save PATH`` writes the full nested record.  Nothing is
written inside the checkout, nothing is read back from an earlier run, and
the exit code is non-zero when a workload failed or the platform is not
``tpu`` — a run without the chip is an error, not a record of zeros.

Processes: the parent never imports jax.  It runs the whole TPU plan in ONE
child (``--worker tpu_plan`` — one process holds the chip at a time), then
the CPU suite in another (``--worker cpu_suite``, forced onto 8 virtual CPU
devices), each in its own process group with a time limit that kills the
group; nothing outlives ``python bench.py``.

Workloads (TPU, ``_TPU_PLAN`` order):

* ``attention`` — flash-attention Pallas kernel vs XLA dense attention at
  long context, scan-chain slope method.
* ``kernels`` — Pallas kernel == jnp reference parity, asserted on the TPU.
* ``throughput_blockq`` — ResNet-18 with the Pallas block-quantize codec
  (+ per-phase timing + on-chip bucketing A/B).
* ``gradsync`` — single-chip encode/decode **kernel cost** per codec
  (labeled as such; the cross-rank *pattern* cost is ``gradsync_virtual``).
* ``throughput`` — ResNet-18/CIFAR-10 sync-PS images/sec/chip + **MFU**
  (FLOPs from XLA cost analysis / wall-clock / chip peak), identity codec.
* ``lm_throughput`` — transformer-LM tokens/sec/chip + MFU, flash attention.
* ``async_resnet18`` — AsySG-InCon async PS on ResNet-18, one chip
  (BASELINE.md ladder rung 3: throughput + loss-decrease evidence).
* ``resnet50`` — ResNet-50/synthetic-ImageNet throughput + MFU (rung 5).

Workloads (CPU — run SEQUENTIALLY in the one ``cpu_suite`` process; their
wall-clock fields are host-CPU numbers, never device metrics):

* ``gradsync_virtual`` — the cross-rank grad-sync pattern on a virtual CPU
  mesh at world=4 and world=8, same 1.86M-param payload as
  ``benchmarks/REFERENCE_BASELINE.json``'s measured reference-style host
  pipeline, so the comparison is same-payload/same-world/both-CPU; plus
  the per-param-vs-bucketed delta and the igather-lowering comparison.
* ``multihost_cpu`` — the TCP async PS with 4 real worker processes,
  quota swept 1/2/4 (throughput + staleness distribution + convergence).
* ``async_virtual`` — the device-level AsySG-InCon pattern, 1 PS device +
  7 virtual worker devices, quota swept (updates/s, staleness, loss).

Baseline (BASELINE.md): the driver target is ">=0.9x mpi4py + 4xV100
images/sec"; the reference publishes no numbers and no GPU exists here.
``vs_baseline`` therefore uses the MEASURED host-path baseline
(`benchmarks/reference_baseline.py`): the reference-style pickle+allgather
pipeline on the real ResNet-18 gradient payload bounds that architecture's
throughput at ``batch/step_time`` images/sec per rank (sync cost only —
compute-free, i.e. strictly favorable to the reference).  The old estimated
per-V100 constant is still reported, labeled, under ``extra.baseline``.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

TPU_PLAN_TIMEOUT_S = 3000.0   # the one chip-holding child
CPU_SUITE_TIMEOUT_S = 900.0

REF_IMG_S_PER_GPU_EST = 1000.0  # legacy estimate (labeled, non-headline)
REF_BATCH_PER_RANK = 128        # standard CIFAR per-rank batch for the bound

_REPO = os.path.dirname(os.path.abspath(__file__))
_BASELINE_PATH = os.path.join(_REPO, "benchmarks", "REFERENCE_BASELINE.json")

# Peak dense bf16 FLOP/s per chip, by `jax.devices()[0].device_kind` —
# public TPU spec sheet numbers (v5e 197T, v4 275T, v5p 459T, v6e 918T).
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def _load_reference_baseline() -> dict | None:
    """The measured host-path baseline artifact (schema 2: per-payload dict;
    legacy flat schema from r2 maps onto the mlp payload)."""
    try:
        with open(_BASELINE_PATH) as f:
            d = json.load(f)
    except (OSError, ValueError):
        return None
    if "payloads" in d:
        return d
    return {"schema": 1, "world": d.get("world"),
            "transport": d.get("transport"),
            "payloads": {"mlp_1p8m": d}}


# ---------------------------------------------------------------------------
# Workers (`python bench.py --worker NAME` runs one in this process)
# ---------------------------------------------------------------------------


def _probe() -> dict:
    """Tiny jit before any heavy build: if this fails, the runtime is down,
    not our program.  Reports the device as JAX sees it."""
    import jax
    import jax.numpy as jnp

    t0 = time.perf_counter()
    x = jnp.ones((256, 256), jnp.float32)
    jax.block_until_ready(x @ x)
    return {"backend": jax.devices()[0].platform,
            "device_kind": jax.devices()[0].device_kind,
            "device_count": len(jax.devices()),
            "probe_s": round(time.perf_counter() - t0, 2)}


def _mfu_fields(jitted, args, *, wall_per_step: float) -> dict:
    """FLOPs-per-step from XLA's compiled cost analysis → MFU against the
    chip's bf16 peak.  ``cost_analysis()["flops"]`` is the PER-DEVICE share
    of an SPMD program (verified empirically on an 8-device mesh), so it
    divides by per-chip wall-clock and peak directly — no world factor.
    ``mfu`` is None (never invented) when XLA reports no flops or the
    device kind has no peak in the table; a cost analysis that RAISES
    fails the workload."""
    import jax

    cost = jitted.lower(*args).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    f = float(cost.get("flops", 0.0))
    flops = f if f > 0 else None
    kind = jax.devices()[0].device_kind
    peak = _PEAK_BF16.get(kind)
    out = {"device_kind": kind,
           "flops_per_step_per_chip": flops,
           "peak_bf16_flops": peak}
    if flops and peak and wall_per_step > 0:
        out["mfu"] = round(flops / wall_per_step / peak, 4)
    else:
        out["mfu"] = None
    return out


def _throughput(code: str) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_cifar10
    from pytorch_ps_mpi_tpu.models import (build_model, make_classifier_loss,
                                           resnet18)
    from pytorch_ps_mpi_tpu.parallel.mesh import batch_sharded, make_ps_mesh

    mesh = make_ps_mesh()
    world = mesh.shape["ps"]
    # Per-chip batch sweep: batch is a free parameter of the throughput
    # headline, and the AOT roofline says the step is HBM-bound with a
    # ceiling that RISES with batch (b1024: AI 152 FLOPs/B, 63% MFU cap;
    # b4096: AI 178, 74% — weight/optimizer traffic amortizes).  Sweep and
    # report every point; headline = the best.  BENCH_RESNET_BATCH
    # overrides with a single size.
    env = os.environ.get("BENCH_RESNET_BATCH")
    # The sweep's point is the identity-codec HEADLINE; the codec
    # comparison (blockq) measures at the single standard batch so it does
    # not pay double compile time in the fixed-deadline plan.
    batches = ([int(env)] if env
               else [1024, 4096] if code == "identity" else [1024])

    model = resnet18(num_classes=10, small_inputs=True, dtype=jnp.bfloat16)
    params, aux = build_model(model, (1, 32, 32, 3))
    loss_fn, has_aux = make_classifier_loss(model, has_aux=bool(aux))

    opt = SGD(list(params.items()), lr=0.1, momentum=0.9, mesh=mesh,
              code=None if code == "identity" else code)
    opt.compile_step(loss_fn, has_aux=has_aux, aux=aux)
    sharding = batch_sharded(mesh)

    points, failures = [], {}
    for batch_per_chip in batches:
        try:
            batch = batch_per_chip * world
            x, y = synthetic_cifar10(batch, seed=0)
            # Stage the batch on device once: the benchmark measures the
            # train step (compute + grad sync), not host->device input
            # streaming.
            b = {"x": jax.device_put(x, sharding),
                 "y": jax.device_put(y, sharding)}
            for _ in range(3):  # warmup: compile + 2 steps
                opt.step(b)
            # Steady-state throughput: non-blocking dispatch lets XLA
            # pipeline successive steps; block once at the end.
            n_steps = 30
            t0 = time.perf_counter()
            for _ in range(n_steps):
                loss, _ = opt.step(b, block=False)
            jax.block_until_ready(loss)
            wall = time.perf_counter() - t0

            pt = {"images_per_sec_per_chip":
                  round(batch * n_steps / wall / world, 1),
                  "batch_per_chip": batch_per_chip,
                  "loss": round(float(loss), 4)}
            pt.update(_mfu_fields(opt._step_fn,
                                  (opt.params, opt.state, opt.aux, b),
                                  wall_per_step=wall / n_steps))
            if pt["flops_per_step_per_chip"]:
                pt["gflops_per_image"] = round(
                    pt["flops_per_step_per_chip"] / batch_per_chip / 1e9, 3)
            points.append(pt)
        except Exception as e:
            # A failing point (e.g. the big batch OOMs) must not lose the
            # points that already measured — headline from the survivors.
            failures[f"b{batch_per_chip}"] = repr(e)[:300]

    if not points:
        raise RuntimeError(f"all sweep points failed: {failures}")
    best = max(points, key=lambda p: p["images_per_sec_per_chip"])
    res = dict(best)
    res.update({"world": world, "code": code,
                "batch_sweep": [
                    {k: p[k] for k in ("batch_per_chip",
                                       "images_per_sec_per_chip", "mfu")}
                    for p in points]})
    if failures:
        res["sweep_failures"] = failures
    if code == "blockq":
        # The reference's signature observable — per-phase timing dicts
        # (`/root/reference/ps.py:116-148`) — measured on silicon via
        # profile mode's phase-split programs (backward / encode / sync /
        # update), once, on the codec path where every phase is real.
        try:
            popt = SGD(list(params.items()), lr=0.1, momentum=0.9,
                       mesh=mesh, code=code, profile=True)
            popt.compile_step(loss_fn, has_aux=has_aux, aux=aux)
            x, y = synthetic_cifar10(batches[0] * world, seed=0)
            b = {"x": jax.device_put(x, sharding),
                 "y": jax.device_put(y, sharding)}
            popt.step(b)  # compile all phase programs
            import numpy as np
            keys = ("backward_time", "code_wait", "comm_wait",
                    "optim_step_time")
            acc = {k: [] for k in keys}
            for _ in range(5):
                _, m = popt.step(b)
                for k in keys:
                    acc[k].append(m[k])
            res["phase_ms"] = {
                k: round(1e3 * float(np.median(v)), 3)
                for k, v in acc.items()}
        except Exception as e:
            res["phase_ms"] = {"error": repr(e)[:300]}
        # On-chip bucketed-vs-per-param A/B (VERDICT r4 #3): same model,
        # same codec, the exchange lowered per-parameter (bucket_mb=0 —
        # the reference's per-param collective loop shape,
        # /root/reference/ps.py:140-176) vs the default 4 MiB buckets.
        # This converts the compiled-schedule overlap evidence
        # (OVERLAP_EVIDENCE.json: 130 all-gathers -> 3 + 38 fused chunks)
        # into a measured wall-clock delta on silicon.
        try:
            # Free the sweep/profile optimizers' params+momentum (and their
            # staged batch) first: three resident optimizer states would
            # OOM the A/B on bigger models and lose the r4 #3 evidence.
            del opt
            try:
                del popt, b
            except NameError:
                pass
            import gc
            gc.collect()
            ab = {}
            for label, bmb in (("per_param", 0), ("bucketed_4mb", 4)):
                aopt = SGD(list(params.items()), lr=0.1, momentum=0.9,
                           mesh=mesh, code=code, bucket_mb=bmb)
                aopt.compile_step(loss_fn, has_aux=has_aux, aux=aux)
                x, y = synthetic_cifar10(batches[0] * world, seed=1)
                ab_b = {"x": jax.device_put(x, sharding),
                        "y": jax.device_put(y, sharding)}
                for _ in range(3):
                    aopt.step(ab_b)
                n_ab = 15
                t0 = time.perf_counter()
                for _ in range(n_ab):
                    loss_ab, _ = aopt.step(ab_b, block=False)
                jax.block_until_ready(loss_ab)
                ab[label] = {"ms_per_step": round(
                    1e3 * (time.perf_counter() - t0) / n_ab, 3)}
                del aopt
            res["bucketing_ab_tpu"] = {
                **ab,
                "bucketing_speedup_tpu": round(
                    ab["per_param"]["ms_per_step"]
                    / ab["bucketed_4mb"]["ms_per_step"], 3)
                if ab["bucketed_4mb"]["ms_per_step"] > 0 else None}
        except Exception as e:
            res["bucketing_ab_tpu"] = {"error": repr(e)[:300]}
    return res


def worker_throughput() -> dict:
    return _throughput("identity")


def worker_throughput_blockq() -> dict:
    return _throughput("blockq")


def worker_resnet50() -> dict:
    """ResNet-50 at ImageNet shapes, single chip — BASELINE.md ladder rung 5
    (the multi-chip scaling rung of the same model runs in
    ``__graft_entry__.dryrun_multichip`` on the hybrid (dcn, ps) mesh)."""
    import jax
    import jax.numpy as jnp

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_imagenet
    from pytorch_ps_mpi_tpu.models import (build_model, make_classifier_loss,
                                           resnet50)
    from pytorch_ps_mpi_tpu.parallel.mesh import batch_sharded, make_ps_mesh

    mesh = make_ps_mesh()
    world = mesh.shape["ps"]
    batch = 128 * world

    model = resnet50(num_classes=1000, small_inputs=False,
                     dtype=jnp.bfloat16)
    # Init at 64x64: ResNet is fully convolutional and global-average-
    # pooled, so param/aux shapes are spatial-size-independent and the
    # 224x224 eager init forward would be one more large compile.
    params, aux = build_model(model, (1, 64, 64, 3))
    loss_fn, has_aux = make_classifier_loss(model, has_aux=bool(aux))

    opt = SGD(list(params.items()), lr=0.1, momentum=0.9, mesh=mesh)
    opt.compile_step(loss_fn, has_aux=has_aux, aux=aux)

    x, y = synthetic_imagenet(batch, seed=0)
    sharding = batch_sharded(mesh)
    b = {"x": jax.device_put(x, sharding), "y": jax.device_put(y, sharding)}

    for _ in range(3):
        opt.step(b)
    n_steps = 15
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss, _ = opt.step(b, block=False)
    jax.block_until_ready(loss)
    wall = time.perf_counter() - t0

    img_s_chip = batch * n_steps / wall / world
    res = {"images_per_sec_per_chip": round(img_s_chip, 1),
           "world": world, "batch_per_chip": batch // world,
           "input": "224x224 synthetic imagenet", "dtype": "bfloat16",
           "loss": round(float(loss), 4)}
    res.update(_mfu_fields(opt._step_fn,
                           (opt.params, opt.state, opt.aux, b),
                           wall_per_step=wall / n_steps))
    if res["flops_per_step_per_chip"]:
        res["gflops_per_image"] = round(
            res["flops_per_step_per_chip"] / (batch // world) / 1e9, 3)
    return res


def worker_async_resnet18() -> dict:
    """AsySG-InCon async PS on ResNet-18 — BASELINE.md ladder rung 3 on real
    hardware.  One chip: the PS and its worker share the device (the
    degenerate-but-real deployment README.md:66-70's quota loop allows);
    convergence evidence (first/last loss over the measured window) and the
    staleness record ride along.  BatchNorm runs in eval mode (frozen init
    stats): the async PS deliberately mirrors the reference pseudo-code's
    plain-params contract (`/root/reference/README.md:56-77`), which has no
    aux-state channel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_ps_mpi_tpu.async_ps import AsyncSGD, dataset_batch_fn
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_cifar10
    from pytorch_ps_mpi_tpu.models import (build_model, cross_entropy,
                                           resnet18)
    from pytorch_ps_mpi_tpu.utils.flatten import unflatten_params

    model = resnet18(num_classes=10, small_inputs=True, dtype=jnp.bfloat16)
    params, aux = build_model(model, (1, 32, 32, 3))

    def loss_fn(params_named, batch):
        variables = {"params": unflatten_params(params_named),
                     "batch_stats": aux}
        logits = model.apply(variables, batch["x"], train=False)
        return cross_entropy(logits, batch["y"])

    batch_size = 512
    opt = AsyncSGD(list(params.items()), lr=0.02, momentum=0.9, quota=1)
    opt.compile_step(loss_fn)

    x, y = synthetic_cifar10(8192, seed=0)
    batch_fn = dataset_batch_fn(x, y, batch_size)

    opt.run(batch_fn, steps=4)  # warmup: compile both programs + fill queue
    n_updates = 40
    t0 = time.perf_counter()
    hist = opt.run(batch_fn, steps=n_updates)
    wall = time.perf_counter() - t0

    img_s = n_updates * opt.quota * batch_size / wall
    losses = hist["losses"]
    k = max(1, len(losses) // 5)
    return {"images_per_sec": round(img_s, 1),
            "updates": n_updates, "quota": opt.quota,
            "workers": opt.num_workers, "batch_per_grad": batch_size,
            "loss_first": round(float(np.mean(losses[:k])), 4),
            "loss_last": round(float(np.mean(losses[-k:])), 4),
            "mean_staleness": round(float(np.mean(hist["staleness"])), 3),
            "bn": "eval-mode (frozen init stats; async PS is plain-params "
                  "per the reference pseudo-code)"}


def worker_kernels() -> dict:
    """Pallas kernel vs jnp reference parity on the chip (``worker_main``
    refuses to run a TPU workload anywhere else, so there is no skipped
    or hollow "pass" here; ``chip_smoke.py``'s ``kernel_parity`` phase is
    the wider version).  A parity FAIL raises."""
    import jax
    import numpy as np

    from pytorch_ps_mpi_tpu.ops import pallas_kernels as pk

    checks = []
    rng = np.random.RandomState(0)
    for n, rows, world in [(512 * 128, 512, 1), (100_000, 512, 4),
                           (37, 8, 2), (3 * 512 * 128 + 5, 512, 8)]:
        flat = rng.randn(n).astype(np.float32)
        x2d, _ = pk.pad_to_blocks(jax.numpy.asarray(flat), rows)
        q_t, s_t = pk.block_quantize_tpu(x2d, bits=8, block_rows=rows)
        q_r, s_r = pk.block_quantize_ref(x2d, bits=8, block_rows=rows)
        q_ok = bool(np.array_equal(np.asarray(q_t), np.asarray(q_r)))
        s_ok = bool(np.allclose(np.asarray(s_t), np.asarray(s_r),
                                rtol=1e-6, atol=0))

        qs = jax.numpy.stack([q_r] * world)
        ss = jax.numpy.stack([s_r] * world)
        d_t = pk.block_dequant_sum_tpu(qs, ss, block_rows=rows)
        d_r = pk.block_dequant_sum_ref(qs, ss, block_rows=rows)
        d_ok = bool(np.allclose(np.asarray(d_t), np.asarray(d_r),
                                rtol=1e-5, atol=1e-5))
        checks.append({"n": n, "rows": rows, "world": world,
                       "q_equal": q_ok, "scales_close": s_ok,
                       "dequant_sum_close": d_ok})
    if not all(c["q_equal"] and c["scales_close"] and
               c["dequant_sum_close"] for c in checks):
        raise AssertionError(f"kernel parity failed: {checks}")
    return {"pallas_on_tpu": True, "parity": "pass", "checks": checks}


def _make_sync_body(codec, bucket_bytes: int | None = None):
    """The full grad-sync phase (encode → all_gather → decode-sum; for the
    identity codec the fused psum) as one function of a grads tree — shared
    by the single-chip kernel-cost and virtual-mesh pattern-cost workers so
    the two measure the same program.  ``bucket_bytes`` switches the
    exchange to the bucketed lowering (`parallel.collectives`) — the knob
    the before/after overlap comparison measures."""
    from collections import OrderedDict

    import jax
    from jax import lax

    from pytorch_ps_mpi_tpu.ops.codecs import IdentityCodec
    from pytorch_ps_mpi_tpu.parallel import collectives as C

    def sync_body(g):
        if isinstance(codec, IdentityCodec):
            return C.psum_tree_bucketed(g, "ps", bucket_bytes=bucket_bytes)
        meta = {n: (x.shape, x.dtype) for n, x in g.items()}
        codes = OrderedDict((n, codec.encode(x)) for n, x in g.items())
        gathered = C.allgather_tree_bucketed(codes, "ps",
                                             bucket_bytes=bucket_bytes)
        return OrderedDict(
            (n, codec.decode_sum(c, shape=meta[n][0], dtype=meta[n][1]))
            for n, c in gathered.items())

    return sync_body


def worker_gradsync() -> dict:
    """Single-chip grad-sync KERNEL COST per codec (world=1: encode +
    decode-sum with no cross-rank traffic — the Pallas/XLA compute cost of
    the compression hook, the c-blosc analogue the reference paid per step,
    `/root/reference/mpi_comms.py:18-30`).  The cross-rank *pattern* cost is
    measured separately on the virtual mesh (``gradsync_virtual``) — r2's
    VERDICT flagged conflating the two.

    Measured by the scan-chain slope method (see worker_attention: chained
    rounds are real sequential executions, and the two-length slope
    cancels the fixed launch cost)."""
    from collections import OrderedDict

    import jax
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.models import init_mlp
    from pytorch_ps_mpi_tpu.ops.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh, replicated

    import jax.numpy as jnp

    mesh = make_ps_mesh()
    world = mesh.shape["ps"]
    rng = np.random.RandomState(0)
    params = init_mlp(rng, sizes=(784, 1024, 1024, 10))  # ~1.8M params
    grads = OrderedDict(
        (n, jax.device_put(jnp.asarray(v), replicated(mesh)))
        for n, v in params.items())
    dense_bytes = sum(int(np.asarray(v).nbytes) for v in params.values())

    out = {}
    # Chain lengths per codec: rounds are tens of microseconds for
    # identity/blockq (need LONG chains to lift the slope over the launch
    # noise) but milliseconds for topk (short chains carry plenty of
    # signal; long ones would burn minutes).
    lengths = {"identity": (1024, 16384), "blockq": (1024, 16384),
               "topk": (256, 2048), "topk_approx": (256, 2048)}
    reps = 3
    platform = mesh.devices.flat[0].platform
    for name in ("identity", "blockq", "topk", "topk_approx"):
        codec = get_codec(None if name == "identity" else name, platform)
        sync_body = _make_sync_body(codec)
        n_short, n_long = lengths[name]

        def make_chain(n, sync_body=sync_body):
            def chained(g):
                def body(g, _):
                    d = sync_body(g)
                    return jax.tree.map(lambda x: x / world, d), 0.0
                g, _ = lax.scan(body, g, None, length=n)
                return g
            return jax.jit(jax.shard_map(chained, mesh=mesh, in_specs=P(),
                                         out_specs=P(), check_vma=False))

        chains = {}
        for n in (n_short, n_long):
            f = make_chain(n)
            np.asarray(jax.tree.leaves(f(grads))[0].ravel()[0])  # warmup
            chains[n] = f
        best = {n: float("inf") for n in chains}
        for rep in range(reps):
            # rep+1: never value-identical to the warmup input.
            fresh = jax.block_until_ready(jax.tree.map(
                lambda x, r=rep: x * (1.0 + 0.01 * (r + 1)), grads))
            for n, f in chains.items():
                t0 = time.perf_counter()
                jax.block_until_ready(f(fresh))
                best[n] = min(best[n], time.perf_counter() - t0)
        slope = 1e3 * (best[n_long] - best[n_short]) / (n_long - n_short)
        # Noise floor: a sub-resolution slope can come out negative — clamp
        # and flag rather than reporting a nonsensical negative latency.
        sync_ms = max(0.0, slope)
        payload = sum(codec.wire_bytes(v.shape, v.dtype)
                      for v in params.values())
        out[name] = {"sync_ms": round(sync_ms, 3),
                     "below_resolution": bool(slope <= 0.0),
                     "chain_lengths": [n_short, n_long],
                     "payload_bytes": int(payload),
                     "dense_bytes": dense_bytes}
    return {"world": world, "n_params": dense_bytes // 4,
            "scope": "single_chip_kernel_cost",
            "backend": platform,
            "per_codec": out}


def worker_gradsync_virtual() -> dict:
    """Cross-rank grad-sync PATTERN cost on a virtual CPU mesh — real SPMD
    collectives across 4 and 8 simulated devices (the `mpirun -n N` analogue,
    SURVEY §4), same 1.86M-param MLP payload as the measured reference-style
    host baseline (`benchmarks/REFERENCE_BASELINE.json`), so the two numbers
    are same-payload / same-world / both-host-CPU — the apples-to-apples
    comparison VERDICT r2 asked for.  No TPU involved."""
    from collections import OrderedDict

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from pytorch_ps_mpi_tpu.models import init_mlp
    from pytorch_ps_mpi_tpu.ops.codecs import get_codec
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh, replicated

    ref = _load_reference_baseline()
    ref_mlp = (ref or {}).get("payloads", {}).get("mlp_1p8m")

    rng = np.random.RandomState(0)
    params = init_mlp(rng, sizes=(784, 1024, 1024, 10))
    dense_bytes = sum(int(np.asarray(v).nbytes) for v in params.values())

    worlds = {}
    for world in (4, 8):
        if world > len(jax.devices()):
            continue
        mesh = make_ps_mesh(world)
        grads = OrderedDict(
            (n, jax.device_put(jnp.asarray(v), replicated(mesh)))
            for n, v in params.items())
        per_codec = {}
        for name in ("identity", "blockq", "topk"):
            codec = get_codec(None if name == "identity" else name, "cpu")

            def timed(bucket_bytes):
                f = jax.jit(jax.shard_map(
                    _make_sync_body(codec, bucket_bytes), mesh=mesh,
                    in_specs=P(), out_specs=P(), check_vma=False))
                jax.block_until_ready(f(grads))  # compile
                times = []
                for i in range(12):
                    fresh = jax.tree.map(
                        lambda x, k=i: x * (1.0 + 0.01 * k), grads)
                    jax.block_until_ready(fresh)
                    t0 = time.perf_counter()
                    jax.block_until_ready(f(fresh))
                    times.append(time.perf_counter() - t0)
                return 1e3 * float(np.median(times))

            # Before/after the bucketing rework: per-parameter collectives
            # (the reference's per-param loop transliterated) vs the
            # dtype-bucketed flat collectives MPI_PS ships by default.
            # Direction caveat, recorded below: on THIS host-CPU backend
            # the pack/slice memcpy is pure overhead (host collectives
            # have no per-op barrier/launch cost to amortize and thunks
            # run small collectives concurrently), so speedups ~<=1 here
            # are expected; the TPU-side benefit is structural — 130
            # sync all-gathers collapse to 3 + 38 compute-fused chunks in
            # the compiled v5e-8 schedule (OVERLAP_EVIDENCE.json).
            from pytorch_ps_mpi_tpu.parallel.collectives import (
                DEFAULT_BUCKET_BYTES)
            ms_perparam = timed(None)
            ms = timed(DEFAULT_BUCKET_BYTES)
            payload = sum(codec.wire_bytes(v.shape, v.dtype)
                          for v in params.values())
            entry = {"sync_ms_per_step": round(ms, 3),
                     "sync_ms_per_param_collectives": round(ms_perparam, 3),
                     "bucketing_speedup_host_cpu": round(ms_perparam / ms, 2)
                     if ms > 0 else None,
                     "payload_bytes": int(payload)}
            if name == "identity" and ref_mlp and \
                    world == (ref_mlp.get("world") or ref.get("world")):
                entry["reference_hostpath_ms"] = ref_mlp["value"]
                entry["speedup_vs_reference"] = round(ref_mlp["value"] / ms, 1)
            per_codec[name] = entry
        worlds[f"world{world}"] = per_codec
    # igather(root_only=True) vs the SPMD all-gather it exists to undercut
    # (r3 VERDICT weak #5: the host-driven lowering's latency was never
    # measured).  Same payload, world=8: rows sharded over the mesh,
    # gathered to rank 0 only vs materialized on every rank.
    igather_cmp = {}
    try:
        from pytorch_ps_mpi_tpu.parallel import collectives as C
        from pytorch_ps_mpi_tpu.parallel.mesh import batch_sharded

        mesh = make_ps_mesh(8)
        leaf = np.stack([np.full((256, 1024), r, np.float32)
                         for r in range(8)])  # 8 MB stacked payload
        x = jax.device_put(jnp.asarray(leaf), batch_sharded(mesh))
        for name, call in (
                ("iallgather_spmd", lambda: C.iallgather(x, mesh)),
                ("igather_root_only",
                 lambda: C.igather(x, mesh, root=0, root_only=True))):
            call().wait()  # warm (compile / transfer-path setup)
            times = []
            for _ in range(8):
                t0 = time.perf_counter()
                call().wait()
                times.append(time.perf_counter() - t0)
            igather_cmp[name] = {
                "ms": round(1e3 * float(np.median(times)), 3)}
        igather_cmp["payload_bytes"] = int(leaf.nbytes)
        igather_cmp["note"] = ("root_only is host-driven (O(world) "
                               "sequential D2D) by design — the async-PS "
                               "building block; the SPMD all-gather is "
                               "the in-step path")
    except Exception as e:  # never fail the workload over the comparison
        igather_cmp = {"error": repr(e)[:200]}
    return {"platform": "virtual_cpu",
            "n_params": dense_bytes // 4, "dense_bytes": dense_bytes,
            "scope": "cross_rank_pattern_cost",
            "reference": ("benchmarks/REFERENCE_BASELINE.json "
                          "(gloo host pipeline, same payload)"),
            "per_world": worlds,
            "igather_lowering_comparison": igather_cmp}


def worker_async_virtual() -> dict:
    """Device-level AsySG-InCon pattern on the virtual 8-device CPU mesh
    (no TPU): 1 PS device + 7 worker devices, quota swept — the
    single-controller async topology at the reference README's shape
    (`/root/reference/README.md:56-77`), measured: updates/s, staleness
    distribution, convergence.  Complements ``multihost_cpu`` (TCP
    process-level) and ``async_resnet18`` (real-chip rung 3)."""
    import jax
    import numpy as np

    from pytorch_ps_mpi_tpu.async_ps import AsyncSGD, dataset_batch_fn
    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn

    devices = jax.devices()
    rng = np.random.RandomState(7)
    x = rng.randn(2048, 64).astype(np.float32)
    w = rng.randn(64, 10).astype(np.float32)
    y = (x @ w).argmax(1).astype(np.int32)

    sweep = {}
    n_workers = max(1, len(devices) - 1)
    for quota in sorted({1, max(1, n_workers // 2), n_workers}):
        params = init_mlp(np.random.RandomState(0), sizes=(64, 128, 10))
        # Plain SGD: heavy momentum under staleness ~= workers/quota is the
        # classic async divergence; this workload records the staleness
        # pattern, not that pathology (the convergence-under-momentum
        # evidence lives in tests/test_async_ps.py with tuned lr).
        opt = AsyncSGD(list(params.items()), lr=0.05,
                       quota=quota, devices=devices)
        opt.compile_step(mlp_loss_fn)
        batch_fn = dataset_batch_fn(x, y, 256, seed=3)
        opt.run(batch_fn, steps=3)  # warmup: compile both programs
        steps = 40
        t0 = time.perf_counter()
        hist = opt.run(batch_fn, steps=steps)
        wall = time.perf_counter() - t0
        st = np.asarray(hist["staleness"], np.float64)
        losses = hist["losses"]
        k = max(1, len(losses) // 5)
        sweep[f"quota{quota}"] = {
            "updates_per_sec": round(steps / wall, 2),
            "grads_per_sec": round(steps * quota / wall, 2),
            "staleness_mean": round(float(st.mean()), 3),
            "staleness_p90": round(float(np.percentile(st, 90)), 3),
            "loss_first": round(float(np.mean(losses[:k])), 4),
            "loss_last": round(float(np.mean(losses[-k:])), 4),
        }
    return {"workers": n_workers, "topology": "1 PS device + worker devices",
            "model": "mlp 64-128-10", "per_quota": sweep}


def _run_all(names) -> dict:
    """Run workloads one after another in THIS process; returns
    ``{name: result}`` with a failed one recorded as ``{"error": tail of
    the traceback}`` so the rest of the plan still measures — `main` turns
    any such entry into a non-zero exit."""
    import gc
    import traceback

    import jax

    out = {}
    for name in names:
        try:
            out[name] = _WORKERS[name]()
        except Exception:
            out[name] = {"error": traceback.format_exc()[-900:]}
        # One process runs the whole plan: drop dead device buffers and
        # cached executables so an 8-10G workload (lm d1024) isn't
        # squeezed by the previous model's remnants.
        gc.collect()
        jax.clear_caches()
    return out


def worker_cpu_suite() -> dict:
    """All CPU-side workloads, run SEQUENTIALLY in this one process so
    their throughput/latency numbers never contend with each other for
    host cores."""
    return _run_all(("gradsync_virtual", "multihost_cpu", "async_virtual"))


def worker_tpu_plan() -> dict:
    """The whole TPU plan in this one process (one process holds the chip
    at a time)."""
    return _run_all(_TPU_PLAN)


def worker_multihost_cpu() -> dict:
    """Multi-host async PS scale evidence (CPU, no TPU): one TCP PS
    in this process, FOUR real worker processes, quota swept — the
    reference's multi-node AsySG-InCon deployment shape
    (`/root/reference/README.md:66-70`, quota=32 topology) at test scale,
    recorded in the artifact instead of only in pytest logs."""
    import numpy as np

    from pytorch_ps_mpi_tpu.models import init_mlp, mlp_loss_fn
    from pytorch_ps_mpi_tpu.multihost_async import AsyncSGDServer

    worker_code = r"""
import os, sys
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + \
    " --xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
from pytorch_ps_mpi_tpu.async_ps import dataset_batch_fn
from pytorch_ps_mpi_tpu.models import mlp_loss_fn
from pytorch_ps_mpi_tpu.multihost_async import AsyncPSWorker
rng = np.random.RandomState(7)
x = rng.randn(512, 32).astype(np.float32)
w = rng.randn(32, 8).astype(np.float32)
y = (x @ w).argmax(1).astype(np.int32)
worker = AsyncPSWorker("127.0.0.1", int(sys.argv[1]), code=None)
worker.run(mlp_loss_fn, dataset_batch_fn(x, y, 128, seed=3))
"""
    n_workers = 4
    steps = 24
    sweep = {}
    for quota in (1, 2, 4):
        params = init_mlp(np.random.RandomState(0), sizes=(32, 64, 8))
        srv = AsyncSGDServer(list(params.items()), lr=0.05, momentum=0.9,
                             quota=quota)
        srv.compile_step(mlp_loss_fn)
        procs = [subprocess.Popen(
            [sys.executable, "-c", worker_code, str(srv.address[1])],
            stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            cwd=_REPO) for _ in range(n_workers)]
        t0 = time.perf_counter()
        try:
            hist = srv.serve(steps=steps)
        finally:
            for p in procs:
                try:
                    p.communicate(timeout=60)
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.communicate()
            srv.close()
        wall = time.perf_counter() - t0
        st = np.asarray(hist["staleness"], np.float64)
        losses = hist["losses"]
        k = max(1, len(losses) // 5)
        sweep[f"quota{quota}"] = {
            "updates_per_sec": round(steps / wall, 2),
            "grads_per_sec": round(steps * quota / wall, 2),
            "staleness_mean": round(float(st.mean()), 3),
            "staleness_p90": round(float(np.percentile(st, 90)), 3),
            "loss_first": round(float(np.mean(losses[:k])), 4),
            "loss_last": round(float(np.mean(losses[-k:])), 4),
        }
    # Probe failure must not discard the minutes of sweep data above.
    try:
        wire = _wire_economics()
    except Exception as e:  # noqa: BLE001 - record, keep the sweep
        wire = {"error": f"{type(e).__name__}: {e}"[:300]}
    return {"workers": n_workers, "transport": "tcp_localhost",
            "model": "mlp 32-64-8", "per_quota": sweep,
            "wire_economics": wire}


def _wire_economics() -> dict:
    """Transfer economics of the ONE transport whose cost is not compiled
    away: the multihost TCP wire (`multihost_async.py` PARM/GRAD frames),
    measured on a real ResNet-18-sized parameter payload at both wire
    levels.  Answers the r4 review's question: is the PS serialization-
    bound at wire_level 0 vs 1?  (A PARM push and a GRAD push with the
    identity codec carry the same tree, so one payload covers both message
    types.)  The transport leg is LOOPBACK — real cross-host links are
    slower, so the measured serialization_fraction is an upper bound; the
    modeled_10GbE figures recompute the split at a representative
    1.2 GB/s link using the measured blob sizes."""
    import socket
    import threading

    import numpy as np

    from pytorch_ps_mpi_tpu.models import build_model, resnet18
    from pytorch_ps_mpi_tpu.multihost_async import _recv_frame, _send_frame
    from pytorch_ps_mpi_tpu.native import serializer

    model = resnet18(num_classes=10, small_inputs=True)
    params, _ = build_model(model, (1, 32, 32, 3))
    tree = {k: np.asarray(v) for k, v in params.items()}
    payload_bytes = int(sum(a.nbytes for a in tree.values()))

    def best(fn, reps=5):
        b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            b = min(b, time.perf_counter() - t0)
        return b

    # Loopback echo server: RTT/2 approximates the one-way frame time at
    # this blob size (kernel buffering makes sub-ms asymmetry irrelevant).
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)

    def echo():
        while True:
            try:
                conn, _ = srv.accept()
            except OSError:
                return
            with conn:
                try:
                    while True:
                        _send_frame(conn, _recv_frame(conn))
                except (ConnectionError, OSError):
                    pass

    thr = threading.Thread(target=echo, daemon=True)
    thr.start()

    out = {"payload_mb": round(payload_bytes / 2**20, 2),
           "model": "resnet18 (the reference's headline model)",
           "transport": "tcp loopback, length-prefixed frames"}
    try:
        for lvl in (0, 1):
          try:  # a level-1 failure must not discard the level-0 numbers
            # Fresh connection + timeout per level: a mid-frame failure in
            # one level must not leave a stale echo in the stream (frame
            # desync) or block the other level forever.
            cli = socket.socket()
            cli.settimeout(120.0)
            cli.connect(srv.getsockname())
            blob = None

            def ser(lvl=lvl):
                nonlocal blob
                blob = serializer.dumps(tree, level=lvl)
            ser_s = best(ser)
            de_s = best(lambda: serializer.loads(blob))

            def rtt():
                _send_frame(cli, blob)
                _recv_frame(cli)
            rtt_s = best(rtt)
            oneway_s = rtt_s / 2
            total_s = ser_s + oneway_s + de_s
            modeled_wire_s = len(blob) / 1.2e9   # 10 GbE ≈ 1.2 GB/s
            out[f"wire_level{lvl}"] = {
                "blob_mb": round(len(blob) / 2**20, 2),
                "serialize_ms": round(ser_s * 1e3, 2),
                "deserialize_ms": round(de_s * 1e3, 2),
                "tcp_oneway_ms": round(oneway_s * 1e3, 2),
                "tcp_MBps": round(len(blob) / 2**20 / oneway_s, 1),
                "per_message_ms": round(total_s * 1e3, 2),
                "serialization_fraction_loopback":
                    round((ser_s + de_s) / total_s, 3),
                "modeled_10GbE": {
                    "per_message_ms": round(
                        (ser_s + de_s + modeled_wire_s) * 1e3, 2),
                    "serialization_fraction": round(
                        (ser_s + de_s)
                        / (ser_s + de_s + modeled_wire_s), 3),
                },
            }
          except Exception as e:  # noqa: BLE001
            out[f"wire_level{lvl}"] = {
                "error": f"{type(e).__name__}: {e}"[:300]}
          finally:
            try:
                cli.close()
            except OSError:
                pass
    finally:
        srv.close()
    l0, l1 = out["wire_level0"], out["wire_level1"]
    if "error" not in l0 and "error" not in l1:
        lbl = lambda f: "serialization" if f > 0.5 else "transport"
        f0, f1 = (l0["modeled_10GbE"]["serialization_fraction"],
                  l1["modeled_10GbE"]["serialization_fraction"])
        out["summary"] = (
            f"at 10GbE: level0 {lbl(f0)}-bound ({f0:.0%} codec), "
            f"level1 {lbl(f1)}-bound ({f1:.0%} codec, "
            f"{l1['blob_mb']}/{l0['blob_mb']} MB on the wire); "
            f"loopback fractions are upper bounds")
    return out


def _attention_slopes(best: dict, names, n_short: int, n_long: int,
                      gn_short: int, gn_long: int):
    """Chain-minimum seconds → per-call slope report + validity.

    Validity (``bad``) is judged on the UNROUNDED slopes: a real but tiny
    positive slope (say 0.0004 ms) must not be declared invalid because
    the 3-decimal report rounds it to 0.0 — and a tiny NEGATIVE one must
    not round into a clean-looking 0.0.  Rounding happens only in the
    returned report dicts; speedup ratios should divide the unrounded
    values (``fwd_u`` / ``step_u``)."""
    def slope_ms(kind, name, lo, hi):
        return (1e3 * (best[(kind, name, hi)] - best[(kind, name, lo)])
                / (hi - lo))

    fwd_u = {name: slope_ms("fwd", name, n_short, n_long) for name in names}
    step_u = {name: slope_ms("step", name, gn_short, gn_long)
              for name in names}
    bad = {f"{kind}:{k}:{v}"
           for kind, d in (("fwd", fwd_u), ("step", step_u))
           for k, v in d.items() if v <= 0}
    ms = {k: round(v, 3) for k, v in fwd_u.items()}
    step_ms = {k: round(v, 3) for k, v in step_u.items()}
    raw_s = {f"{kind}_{name}_n{n}": round(t, 4)
             for (kind, name, n), t in best.items()}
    return fwd_u, step_u, ms, step_ms, raw_s, bad


def worker_attention() -> dict:
    """Flash-attention Pallas kernel vs XLA dense attention, long context
    (bf16, causal), on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel.ring_attention import dense_attention

    b, s, h, d = 4, 4096, 8, 128
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(
        rng.randn(b, s, h, d).astype(np.float32)).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()

    # Measurement method (per-program launch overhead is large next to one
    # kernel call, and independent same-input calls need not execute
    # sequentially):
    # 1. chain the op inside one jitted lax.scan so call i+1 depends on
    #    call i — n real sequential executions;
    # 2. time two chain lengths and take the SLOPE (T_long - T_short) /
    #    (n_long - n_short) — the fixed launch/fetch overhead cancels;
    # 3. min over interleaved repetitions with fresh inputs — the min is
    #    stable (launch noise is one-sided).
    # Chain lengths sized to FIT THE TIMEOUT (r2's 64->512 x 5 reps timed
    # out twice): at ~4.6 ms/dense call, 48->256 puts ~1 s of slope signal
    # on the dense chain and ~0.3 s on flash — both clear of the ~0.1 s
    # min-level noise — while one full rep costs ~2 s instead of ~15 s.
    n_short, n_long, reps = 48, 256, 4

    def make_chain(fn, n):
        def chained(q, k, v):
            def body(x, _):
                o = fn(x, k, v, causal=True)
                return q + o.astype(q.dtype) * jnp.bfloat16(1e-3), 0.0
            x, _ = jax.lax.scan(body, q, None, length=n)
            return x
        return jax.jit(chained)

    # Train-step direction: fwd + FULL backward (dq, dk, dv — all three
    # combined into the chain update so none is dead code XLA could
    # eliminate).  This is what the Pallas bwd kernels are for; the jnp-scan
    # backward it replaced was never timed on silicon.
    def make_grad_chain(fn, n):
        def chained(q, k, v):
            def loss(qq, kk, vv):
                return jnp.sum(fn(qq, kk, vv, causal=True)
                               .astype(jnp.float32)) * 1e-6
            g = jax.grad(loss, argnums=(0, 1, 2))

            def body(x, _):
                gq, gk, gv = g(x, k, v)
                upd = (gq + gk + gv).astype(x.dtype)
                return x + upd * jnp.bfloat16(1e-3), 0.0
            x, _ = jax.lax.scan(body, q, None, length=n)
            return x
        return jax.jit(chained)

    fns = {"dense_xla": dense_attention, "flash_pallas": flash_attention}
    chains = {}
    # Grad chains cost ~3x the fwd; shorter lengths keep one rep ~the same
    # wall-clock as the fwd pair.
    gn_short, gn_long = 16, 96
    for name, fn in fns.items():
        for n in (n_short, n_long):
            g = make_chain(fn, n)
            np.asarray(g(q, k, v)[0, 0, 0, 0])  # compile + warmup
            chains[("fwd", name, n)] = g
        for n in (gn_short, gn_long):
            g = make_grad_chain(fn, n)
            np.asarray(g(q, k, v)[0, 0, 0, 0])
            chains[("step", name, n)] = g
    def measure(best=None):
        # Starting from a prior run's minimums merges the two runs:
        # launch noise is one-sided, so the elementwise min over more
        # reps is strictly better — a retry must not discard the first
        # run's clean chains along with its noisy ones.
        best = dict(best) if best else {key: float("inf") for key in chains}
        for _ in range(reps):
            # ONE fresh input per rep, shared by all chains, MATERIALIZED
            # before the timers start: `jnp.asarray` of a 67 MB host array
            # dispatches asynchronously, so without the block the timed
            # region swallows the host->device transfer — which swamped the
            # 0.2-1.2 s chain signal into NEGATIVE slopes in the
            # 2026-07-31 12:39 capture.
            q2 = jax.block_until_ready(mk())
            for key, g in chains.items():
                t0 = time.perf_counter()
                # Wait on the output in place — a scalar slice-fetch would
                # dispatch a second tiny program + round trip in the timer.
                jax.block_until_ready(g(q2, k, v))
                best[key] = min(best[key], time.perf_counter() - t0)

        fwd_u, step_u, ms, step_ms, raw_s, bad = _attention_slopes(
            best, list(fns), n_short, n_long, gn_short, gn_long)
        return best, fwd_u, step_u, ms, step_ms, raw_s, bad

    best, fwd_u, step_u, ms, step_ms, raw_s, bad = measure()
    retried = False
    first_raw = None
    if bad:
        # One full re-measurement before declaring the rung invalid: one
        # noisy launch must not cost the attention capture.  Chains stay
        # compiled (retry costs execution time only) and the prior
        # minimums carry over (merged min).
        first_raw = raw_s
        best, fwd_u, step_u, ms, step_ms, raw_s, bad = measure(best)
        retried = True
    if bad:
        # A non-positive slope means the measurement is invalid (overhead
        # noise exceeded the chain signal) — raise instead of recording a
        # nonsense speedup; BOTH runs' raw chain times ride in the error
        # (same noise shape or independent? — the triage question).
        raise RuntimeError(
            f"attention slope invalid twice (non-positive: {sorted(bad)}); "
            f"run-1 raw chain seconds: {first_raw}; "
            f"merged-after-retry: {raw_s}")
    return {"shape": [b, s, h, d], "dtype": "bfloat16", "causal": True,
            "method": f"scan-chain slope {n_short}->{n_long} (fwd), "
                      f"{gn_short}->{gn_long} (grad), min of {reps}, "
                      "inputs materialized pre-timer",
            "ms_per_call": ms, "step_ms_per_call": step_ms,
            "raw_chain_s": raw_s, "retried": retried,
            # Ratios of the UNROUNDED slopes (the report dicts above are
            # rounded for display only).
            "fwd_speedup": round(fwd_u["dense_xla"] / fwd_u["flash_pallas"],
                                 3),
            "step_speedup": round(
                step_u["dense_xla"] / step_u["flash_pallas"], 3),
            "speedup": round(fwd_u["dense_xla"] / fwd_u["flash_pallas"], 3)}


def worker_lm_throughput() -> dict:
    """Transformer-LM training throughput (tokens/sec/chip) + MFU, bf16,
    flash attention — the long-context model family measured end-to-end on
    hardware, same donation-chained honest timing as the ResNet workload
    (step i+1 consumes step i's params, so the final fetch covers all)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_lm
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel.mesh import batch_sharded, make_ps_mesh

    mesh = make_ps_mesh()
    world = mesh.shape["ps"]
    seq = 1024
    # d1024xL12, 219M params, 16/chip: AOT roofline puts this config's MFU
    # ceiling at 67% (AI 161 FLOPs/B) vs 38% for the old d512xL8 b32 —
    # which was vocab-logit-traffic-bound — and b32 at d1024 OOMs 16G HBM
    # on the f32 logits temp.  (benchmarks note, r4 roofline sweep.)
    batch = int(os.environ.get("BENCH_LM_BATCH", "16")) * world

    model = TransformerLM(
        vocab_size=32768, d_model=1024, n_heads=16, n_layers=12, d_ff=4096,
        max_len=seq, dtype=jnp.bfloat16,
        attn=functools.partial(flash_attention, causal=True))
    params = build_lm(model, seq_len=seq)
    n_params = sum(int(np.prod(p.shape)) for p in params.values())

    opt = SGD(list(params.items()), lr=0.01, momentum=0.9, mesh=mesh)
    opt.compile_step(make_lm_loss(model))

    toks = synthetic_lm(batch, seq_len=seq, vocab=32768, seed=0)
    sharding = batch_sharded(mesh)
    b = {k: jax.device_put(v, sharding)
         for k, v in lm_batch(toks).items()}

    for _ in range(3):
        opt.step(b)
    n_steps = 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss, _ = opt.step(b, block=False)
    loss = float(loss)  # host fetch: forces the whole donation chain
    wall = time.perf_counter() - t0

    tok_s_chip = batch * seq * n_steps / wall / world
    res = {"tokens_per_sec_per_chip": round(tok_s_chip, 1),
           "n_params": n_params, "seq_len": seq,
           "batch_per_chip": batch // world, "world": world,
           "attn": "flash_pallas", "dtype": "bfloat16",
           "loss": round(loss, 4)}
    res.update(_mfu_fields(opt._step_fn,
                           (opt.params, opt.state, opt.aux, b),
                           wall_per_step=wall / n_steps))
    if res["flops_per_step_per_chip"]:
        res["kflops_per_token"] = round(
            res["flops_per_step_per_chip"] / (batch // world * seq) / 1e3, 1)
    return res


def worker_probe() -> dict:
    """Runtime health check: just the tiny jit probe (worker_main already
    ran it before dispatching here), for ad-hoc ``--worker probe`` use."""
    return {}


_WORKERS = {
    "probe": worker_probe,
    "throughput": worker_throughput,
    "throughput_blockq": worker_throughput_blockq,
    "lm_throughput": worker_lm_throughput,
    "resnet50": worker_resnet50,
    "async_resnet18": worker_async_resnet18,
    "kernels": worker_kernels,
    "gradsync": worker_gradsync,
    "gradsync_virtual": worker_gradsync_virtual,
    "multihost_cpu": worker_multihost_cpu,
    "async_virtual": worker_async_virtual,
    "cpu_suite": worker_cpu_suite,
    "tpu_plan": worker_tpu_plan,
    "attention": worker_attention,
}

# The TPU plan, in order.  resnet50 runs LAST: its compile is by far the
# largest program in the plan, so a failure there can only cost itself.
# BENCH_TPU_PLAN=a,b,c runs a subset.
_TPU_PLAN = tuple(
    os.environ.get("BENCH_TPU_PLAN", "").split(",")
    if os.environ.get("BENCH_TPU_PLAN") else
    ("attention", "kernels", "throughput_blockq", "gradsync",
     "throughput", "lm_throughput", "async_resnet18", "resnet50"))

# Workers that run on the virtual-CPU platform (they never touch the TPU).
_CPU_WORKERS = {"gradsync_virtual", "multihost_cpu", "async_virtual",
                "cpu_suite"}


def worker_main(name: str) -> None:
    """Run one workload in this process and print its JSON record.  Exit
    code: 0 ok; 4 the platform is not ``tpu`` (a TPU workload never runs
    anywhere else); 5 the workload raised."""
    if name in _CPU_WORKERS:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " --xla_force_host_platform_device_count=8")
        import jax

        jax.config.update("jax_platforms", "cpu")
        probe = {"backend": "cpu_virtual"}
    else:
        probe = _probe()
        if probe["backend"] != "tpu":
            print(json.dumps({"ok": False, "stage": "probe", "probe": probe,
                              "error": "TPU workload, but the platform is "
                                       f"{probe['backend']!r}"}))
            sys.exit(4)
    from pytorch_ps_mpi_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    try:
        res = _WORKERS[name]()
    except Exception:
        import traceback
        print(json.dumps({"ok": False, "stage": name, "probe": probe,
                          "error": traceback.format_exc()[-900:]}))
        sys.exit(5)
    res["ok"] = True
    res["probe"] = probe
    res.setdefault("backend", probe["backend"])
    print(json.dumps(res))


# ---------------------------------------------------------------------------
# Parent orchestration
# ---------------------------------------------------------------------------


def _baseline_fields(img_s_chip: float) -> tuple[float, dict]:
    """Headline ``vs_baseline`` from the MEASURED host-path baseline; the
    legacy estimated-V100 ratio rides along, labeled, never as the headline
    (VERDICT r2 #6: no invented constant in the headline ratio)."""
    ref = _load_reference_baseline()
    info: dict = {
        # r3 advisor: version the ratio semantics explicitly so
        # round-over-round consumers never silently mix denominators
        # (r1-r2 headlined vs the estimated V100; r3+ headline divides by
        # the MEASURED host-path sync-only bound).
        "headline_ratio_semantics": (
            "images/sec/chip ÷ measured reference-style host-path "
            "sync-only bound per rank (schema 2); the legacy estimated-"
            "V100 ratio rides below, labeled"),
        "vs_estimated_v100": round(img_s_chip / REF_IMG_S_PER_GPU_EST, 3),
        "estimated_v100_img_s": REF_IMG_S_PER_GPU_EST,
    }
    r18 = (ref or {}).get("payloads", {}).get("resnet18")
    if r18 and r18.get("value"):
        step_s = r18["value"] / 1e3
        bound = REF_BATCH_PER_RANK / step_s
        info.update({
            "source": "measured_hostpath_sync_bound",
            "ref_ms_per_step": r18["value"],
            "ref_world": r18.get("world"),
            "per_rank_img_s_bound": round(bound, 1),
            "note": ("reference-style pickle+allgather pipeline measured on "
                     "the real ResNet-18 gradient payload "
                     "(benchmarks/reference_baseline.py); the bound counts "
                     "sync cost ONLY (reference compute excluded — strictly "
                     "favorable to the reference architecture), "
                     f"batch {REF_BATCH_PER_RANK}/rank"),
        })
        return round(img_s_chip / bound, 3) if bound else 0.0, info
    info["source"] = "estimated_v100 (measured baseline artifact missing)"
    return round(img_s_chip / REF_IMG_S_PER_GPU_EST, 3), info


HEADLINE_LINE_CAP = 1500  # a ~2000-char tail capture must parse it


def _scalar_summary(d: dict, max_keys: int = 7) -> dict:
    """Depth-1 scalars of a workload result — the compact line carries the
    essential numbers themselves, not only a pointer to the full file."""
    out = {}
    for k, v in d.items():
        if isinstance(v, (bool, int, float)):
            out[k] = v
        elif isinstance(v, str) and len(v) <= 40 and k != "backend":
            out[k] = v
        if len(out) >= max_keys:
            break
    return out


def _best_quota(d: dict) -> dict:
    per = {k: v for k, v in d.get("per_quota", {}).items()
           if k.startswith("quota") and k[5:].isdigit()
           and isinstance(v, dict)}
    if not per:
        return {}
    key = max(per, key=lambda q: int(q[5:]))
    sub = per[key]
    return {key + "_updates_per_sec": sub.get("updates_per_sec"),
            key + "_loss_last": sub.get("loss_last")}


def _gv_pull(d: dict) -> dict:
    w8 = (d.get("per_world") or {}).get("world8")
    ident = (w8 or {}).get("identity") if isinstance(w8, dict) else None
    if not isinstance(ident, dict):
        return {}
    return {"w8_identity_ms": ident.get("sync_ms_per_step"),
            "w8_speedup_vs_reference": ident.get("speedup_vs_reference")}


# Per-workload nested pulls that the depth-1 scalar summary would miss.
_SUMMARY_PULLS = {
    "throughput_blockq": lambda d: {
        "bucketing_speedup_tpu":
            (d.get("bucketing_ab_tpu") or {}).get("bucketing_speedup_tpu")},
    "attention": lambda d: {"ms_per_call": d.get("ms_per_call"),
                            "step_ms_per_call": d.get("step_ms_per_call"),
                            "fwd_speedup": d.get("fwd_speedup"),
                            "step_speedup": d.get("step_speedup")},
    "gradsync": lambda d: {"sync_ms": {
        n: v.get("sync_ms") for n, v in d.get("per_codec", {}).items()
        if isinstance(v, dict)}},
    "gradsync_virtual": lambda d: _gv_pull(d),
    "multihost_cpu": _best_quota,
    "async_virtual": _best_quota,
}

# Drop order under the cap: last entries are dropped first.
_SUMMARY_PRIORITY = (
    "throughput", "throughput_blockq", "lm_throughput", "resnet50",
    "attention", "async_resnet18", "kernels", "gradsync",
    "gradsync_virtual", "multihost_cpu", "async_virtual")


def _compact_line(full: dict, full_paths: list[str]) -> str:
    """The one stdout JSON line, hard-capped at HEADLINE_LINE_CAP chars:
    headline + per-workload key scalars + error counts, with the full
    nested artifact referenced by path.  Progressive pruning guarantees
    the cap (and therefore parseability) regardless of how much landed."""
    extra = full.get("extra", {})
    c: dict = {}
    for k in ("backend", "device_kind", "mfu", "wall_s"):
        if extra.get(k) is not None:
            c[k] = extra[k]
    if full_paths:
        c["full_results"] = full_paths[0]
    for name in _SUMMARY_PRIORITY:
        rec = extra.get(name)
        if not isinstance(rec, dict):
            continue
        s = _scalar_summary(rec)
        pull = _SUMMARY_PULLS.get(name)
        if pull:
            s.update({k: v for k, v in pull(rec).items() if v is not None})
        if s:
            c[name] = s
    errors = extra.get("errors")
    if errors:
        c["errors"] = {k: (f"{len(v)}x: {str(v[0])[:90]}"
                           if isinstance(v, list) and v else str(v)[:90])
                       for k, v in errors.items()}
    payload = {k: full[k] for k in ("metric", "value", "unit", "vs_baseline")}
    payload["extra"] = c
    line = json.dumps(payload)
    if len(line) <= HEADLINE_LINE_CAP:
        return line
    if "errors" in c:  # 1) errors -> counts only
        c["errors"] = {k: int(str(v).split("x:")[0])
                       if isinstance(v, str) and "x:" in v else 1
                       for k, v in c["errors"].items()}
        line = json.dumps(payload)
        if len(line) <= HEADLINE_LINE_CAP:
            return line
    for name in reversed(_SUMMARY_PRIORITY):  # 2) drop summaries, low first
        if name in c:
            del c[name]
            line = json.dumps(payload)
            if len(line) <= HEADLINE_LINE_CAP:
                return line
    payload["extra"] = {k: c[k] for k in ("backend", "device_kind", "mfu",
                                          "wall_s", "full_results")
                        if k in c}  # 3) last resort: headline + pointer
    return json.dumps(payload)


def _run_child(worker: str, timeout: float) -> "tuple[dict | None, str]":
    """``python bench.py --worker NAME`` in its own process group; returns
    ``(its JSON record or None, a word on what went wrong)``.  On timeout
    the whole group is killed and reaped (``multihost_cpu`` spawns TCP
    worker grandchildren): nothing outlives this call."""
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker", worker],
        stdout=subprocess.PIPE, text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout)
        why = f"exit code {proc.returncode}"
    except subprocess.TimeoutExpired:
        out, why = "", f"timeout after {timeout:.0f}s"
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    for line in reversed(out.strip().splitlines()):
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict):
            return rec, why
    return None, why


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", choices=sorted(_WORKERS))
    ap.add_argument("--save", metavar="PATH",
                    help="also write the full nested record to PATH")
    args = ap.parse_args(argv)
    if args.worker:
        worker_main(args.worker)
        return 0

    t_start = time.perf_counter()
    results: dict = {}
    errors: dict = {}
    probe: dict = {}
    # The chip first; without it there is nothing to compose, and the CPU
    # suite's minutes would only delay the error.
    for suite, timeout in (("tpu_plan", TPU_PLAN_TIMEOUT_S),
                           ("cpu_suite", CPU_SUITE_TIMEOUT_S)):
        rec, why = _run_child(suite, timeout)
        if rec is None or not rec.pop("ok", False):
            errors[suite] = [(rec or {}).get("error") or f"no result: {why}"]
            if suite == "tpu_plan":
                break
            continue
        if suite == "tpu_plan":
            probe = rec.get("probe", {})
        for name, res in rec.items():
            if name in _WORKERS and isinstance(res, dict):
                if "error" in res:
                    errors[name] = [res["error"]]
                else:
                    results[name] = res

    primary = results.get("throughput", {})
    img_s_chip = float(primary.get("images_per_sec_per_chip", 0.0))
    vs_baseline, baseline_info = _baseline_fields(img_s_chip)
    extra = {"backend": probe.get("backend"),
             "device_kind": probe.get("device_kind"),
             "device_count": probe.get("device_count"),
             "wall_s": round(time.perf_counter() - t_start, 1),
             "baseline": baseline_info}
    if primary.get("mfu") is not None:
        extra["mfu"] = primary["mfu"]
    extra.update({k: v for k, v in results.items() if k != "throughput"})
    if primary:
        extra["throughput"] = primary
    if errors:
        extra["errors"] = errors
    full = {"metric": "resnet18_cifar10_sync_ps_throughput",
            "value": round(img_s_chip, 1), "unit": "images/sec/chip",
            "vs_baseline": vs_baseline if img_s_chip else 0.0,
            "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            "extra": extra}
    saved = []
    if args.save:
        with open(args.save, "w") as f:
            json.dump(full, f, indent=1)
            f.write("\n")
        saved.append(args.save)
    print(_compact_line(full, saved))
    return 1 if errors or probe.get("backend") != "tpu" else 0


if __name__ == "__main__":
    sys.exit(main())
