#!/usr/bin/env python3
"""Compile each cell's step and its reference for a described TPU v5e host
(`v5e:2x2`), on the CPU, and print the compiler's memory per device.

    python3 bench/compile_check.py [cell ...]

Nothing runs: this finds what the chip's compiler refuses, and whether the
step and the reference fit, before any chip time is spent.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TPU_LOG_DIR"] = "disabled"


def main(argv):
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(root / "src"), str(root)]
    import json
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench import program
    from bench.references import load_reference
    from bench.run import load_cell

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    with open(root / "BENCHMARK.json") as f:
        names = argv or [w["name"] for w in json.load(f)["workloads"]]
    gib = lambda b: f"{b / 2 ** 30:.2f} GiB"
    for name in names:
        cell = load_cell(name)
        devices = topo.devices[:cell.chips]
        prog = program.build(cell.config, cell.traffic, devices)
        kd = jax.ShapeDtypeStruct((2,), jnp.uint32)
        p, o = jax.eval_shape(prog.init, kd)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (prog.data.global_batch, prog.data.seq_len), jnp.int32,
            sharding=NamedSharding(prog.mesh, P("data")))}
        ma = prog.step.lower(p, o, batch).compile().memory_analysis()
        print(f"{name} step: args {gib(ma.argument_size_in_bytes)} temp "
              f"{gib(ma.temp_size_in_bytes)} out "
              f"{gib(ma.output_size_in_bytes)} alias "
              f"{gib(ma.alias_size_in_bytes)} -> per device "
              f"{gib(ma.argument_size_in_bytes + ma.temp_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes)}",
              flush=True)
        ref = load_reference(cell.config)
        mesh = Mesh(np.array(devices), ("data",))
        rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
        pr = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                                         sharding=rep), p)
        tok = jax.ShapeDtypeStruct(batch["tokens"].shape, jnp.int32,
                                   sharding=rows)
        vg = jax.jit(jax.value_and_grad(partial(ref.loss,
                                                m=cell.config["model"])),
                     out_shardings=(rep, rep))
        ma = vg.lower(pr, tok).compile().memory_analysis()
        print(f"{name} reference: args {gib(ma.argument_size_in_bytes)} "
              f"temp {gib(ma.temp_size_in_bytes)} out "
              f"{gib(ma.output_size_in_bytes)}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
