"""What the chip's compiler says a sync cell's step needs, without a chip.

    JAX_PLATFORMS=cpu python3 perfbench/step_memory.py --workload <cell> [--rows N ...]

Compiles the cell's training step for a TPU v5e that is described and not
attached (`jax.experimental.topologies`), at the configuration's real sizes,
and prints one JSON line per `--rows` value with `compiled.memory_analysis()`:
arguments (parameters, optimizer state, batch), outputs, what of the
arguments is donated into the outputs, and the program's temporaries.  A
program the chip's compiler refuses for memory is printed as `"fits": false`
with the head of the compiler's own message, which carries its total and its
largest allocations.  Nothing runs: this is a compile, never a measurement,
and no time or rate comes from it.  The temporaries are what the compiler
took with nothing pressing it, not what it needs: on the chip the same step
has run in less (PERF.md), so the refusal is the answer, not the sum.

The step compiled here is a stand-in for `MPI_PS`'s fused step, which places
its own parameters and so cannot be handed a described device: the family's
loss under `jax.value_and_grad`, then `optim.rules`' update leaf by leaf, with
parameters and state donated, as the fused step does on one chip (where its
exchange is a no-op).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
GIB = float(1 << 30)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rows", type=int, nargs="*", default=None,
                    help="rows a chip a step; default: the cell's own")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench.run import find, load_json
    from pytorch_ps_mpi_tpu.optim.rules import RULES

    jax.config.update("jax_enable_compilation_cache", False)
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = find(bench["workloads"], args.workload, "workload")
    cell = load_json(os.path.join(HERE, "workloads", entry["name"] + ".json"))
    config = load_json(os.path.join(
        ROOT, find(bench["configs"], entry["config"], "config")["file"]))
    if cell["mode"] != "sync":
        raise SystemExit("step_memory: only sync cells have one step program")
    family = importlib.import_module(
        f"perfbench.models.{config['family']}").build(
            config, cell, rehearse=False, impl="mosaic")
    loss_fn, has_aux = family.sync_loss()
    init_state, update = RULES[cell["optim"]]
    hyper = cell["hyper"]

    def step(params, state, batch):
        if has_aux:
            (loss, _), grads = jax.value_and_grad(
                lambda p: loss_fn(p, family.aux, batch), has_aux=True)(params)
        else:
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch))(params)
        new = {n: update(params[n], grads[n], state[n], **hyper)
               for n in params}
        return ({n: v[0] for n, v in new.items()},
                {n: v[1] for n, v in new.items()}, loss)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)
    params = jax.eval_shape(family.init_params, 0)
    state = jax.eval_shape(
        lambda p: {n: init_state(v) for n, v in p.items()}, params)
    seq = family.shapes["seq_len"]
    for rows in args.rows or [cell["rows_per_chip"]]:
        ids = jax.ShapeDtypeStruct((rows, seq), jnp.int32)
        batch = {"tokens": ids, "targets": ids, "positions": ids}
        line = {"workload": entry["name"], "rows_per_chip": rows,
                "compiled_for": topo.devices[0].device_kind,
                "measured": False}
        try:
            compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
                on_chip(params), on_chip(state), on_chip(batch)).compile()
        except Exception as exc:    # the compiler's refusal is the answer
            line.update(fits=False, compiler_says=str(exc)[:4000])
        else:
            m = compiled.memory_analysis()
            line.update(
                fits=True,
                arguments_gib=m.argument_size_in_bytes / GIB,
                outputs_gib=m.output_size_in_bytes / GIB,
                donated_gib=m.alias_size_in_bytes / GIB,
                temporaries_gib=m.temp_size_in_bytes / GIB,
                program_gib=m.generated_code_size_in_bytes / GIB)
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
