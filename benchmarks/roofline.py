"""Roofline analysis of the bench programs from XLA's own cost model.

AOT-compiles the exact `bench.py` training-step programs for a single v5e
core (`jax.experimental.topologies`, compile-only — no chip needed) and
reads the compiled module's FLOP count and HBM bytes-accessed, giving each
program's arithmetic intensity and its MFU *ceiling* on v5e
(peaks: 197 TF/s bf16, 819 GB/s HBM → ridge ≈ 241 FLOPs/byte).

This is the analysis half of the MFU story: the measured half is the
`mfu` field the throughput workloads record on hardware.  A measured MFU
should be read against the ceiling here, not against 100% — ResNet-18 on
CIFAR images is HBM-bound (activation traffic), so e.g. 44% measured MFU
at batch 1024 is ~70% of that program's 63% roofline ceiling.

Usage: ``python benchmarks/roofline.py [--save]`` →
``benchmarks/ROOFLINE.json``.  Compile-heavy (~10 min on this host).
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_HERE = os.path.dirname(os.path.abspath(__file__))

PEAK_FLOPS_BF16 = 197e12  # v5e public spec
PEAK_HBM_BPS = 819e9


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args()

    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from pytorch_ps_mpi_tpu import SGD
    from pytorch_ps_mpi_tpu.data.datasets import synthetic_lm
    from pytorch_ps_mpi_tpu.models import (build_model, make_classifier_loss,
                                           resnet18)
    from pytorch_ps_mpi_tpu.models.transformer import (TransformerLM,
                                                       build_lm, lm_batch,
                                                       make_lm_loss)
    from pytorch_ps_mpi_tpu.ops.flash_attention import flash_attention
    from pytorch_ps_mpi_tpu.parallel.mesh import make_ps_mesh

    # Smallest valid v5e topology is one host's 2x2; a 1-device mesh over
    # it compiles the single-core program the bench runs.
    topo = topologies.get_topology_desc(platform="tpu",
                                       topology_name="v5e:2x2")
    aot_mesh = Mesh(np.array(topo.devices).reshape(-1)[:1], ("ps",))
    cpu_mesh = make_ps_mesh(1, devices=jax.local_devices(backend="cpu")[:1])
    rep = NamedSharding(aot_mesh, P())
    shd = NamedSharding(aot_mesh, P("ps"))
    abstract = lambda t, s: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), t)

    rows = {}

    def report(tag, opt, loss_fn, has_aux, abstract_batch):
        opt.mesh = aot_mesh
        step = opt._make_spmd_step(loss_fn, has_aux)
        c = step.lower(abstract(opt.params, rep), abstract(opt.state, rep),
                       abstract(opt.aux, rep), abstract_batch).compile()
        ca = c.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        flops = float(ca.get("flops", 0.0))
        byts = float(ca.get("bytes accessed", 0.0))
        t_f, t_b = flops / PEAK_FLOPS_BF16, byts / PEAK_HBM_BPS
        rows[tag] = {
            "flops_per_step": flops, "hbm_bytes_per_step": byts,
            "arithmetic_intensity": round(flops / byts, 1) if byts else None,
            "bound": "HBM" if t_b > t_f else "MXU",
            "mfu_ceiling": round(t_f / max(t_f, t_b), 3),
        }
        print(tag, json.dumps(rows[tag]))

    model = resnet18(num_classes=10, small_inputs=True, dtype=jnp.bfloat16)
    params, aux = build_model(model, (1, 32, 32, 3))
    loss_fn, has_aux = make_classifier_loss(model, has_aux=bool(aux))
    opt = SGD(list(params.items()), lr=0.1, momentum=0.9, mesh=cpu_mesh)
    for batch in (1024, 4096):
        ab = {"x": jax.ShapeDtypeStruct((batch, 32, 32, 3), jnp.float32,
                                        sharding=shd),
              "y": jax.ShapeDtypeStruct((batch,), jnp.int32, sharding=shd)}
        report(f"resnet18_cifar_b{batch}", opt, loss_fn, has_aux, ab)

    seq = 1024
    lm = TransformerLM(vocab_size=32768, d_model=1024, n_heads=16,
                       n_layers=12, d_ff=4096, max_len=seq,
                       dtype=jnp.bfloat16,
                       attn=functools.partial(flash_attention, causal=True))
    # Init runs eagerly on the CPU, where the Mosaic flash kernel cannot:
    # the parameter tree does not depend on the attention function, so it
    # comes from the dense-attention twin; only the program lowered for
    # the TPU topology holds the kernel.
    lparams = build_lm(lm.copy(attn=None), seq_len=seq)
    lopt = SGD(list(lparams.items()), lr=0.01, momentum=0.9, mesh=cpu_mesh)
    toks = synthetic_lm(16, seq_len=seq, vocab=32768, seed=0)
    lb = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shd)
          for k, v in lm_batch(toks).items()}
    report("lm_d1024_L12_s1024_b16", lopt, make_lm_loss(lm), False, lb)

    out = {"method": ("XLA compiled-module cost analysis (flops, bytes "
                      "accessed), AOT v5e single core"),
           "peaks": {"bf16_flops": PEAK_FLOPS_BF16,
                     "hbm_bytes_per_s": PEAK_HBM_BPS},
           "programs": rows}
    if args.save:
        with open(os.path.join(_HERE, "ROOFLINE.json"), "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
