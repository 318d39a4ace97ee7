"""Production serving driver: TP-sharded params + batched engine.

Parameter/checkpoint distribution goes through the paper's collective
layer: with model-parallel > 1 the host-initialized parameters are
replicated to every device by the cached single-root broadcast artifact
(`tree_broadcast` under shard_map) before the TP sharding is applied —
serving restarts replay the artifact from the schedule cache instead of
recompiling it.

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b \
        --reduced --host-devices 4 --model-parallel 4
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=2)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU tests only: re-exec on N forced CPU host "
                         "devices (sets JAX_PLATFORMS=cpu)")
    ap.add_argument("--schedule-cache", default="",
                    help="pre-compile the model-axis tree-pipeline collective "
                         "programs into this on-disk artifact cache")
    ap.add_argument("--no-broadcast-params", action="store_true",
                    help="skip the tree-broadcast parameter distribution "
                         "(saves the broadcast schedule compile on boot "
                         "when no cache is warmed)")
    ap.add_argument("--inject-fault", default="",
                    help="'u-v' — fail link u-v on the model axis after the "
                         "broadcast schedule is compiled: the driver repairs "
                         "the program in place (CollectiveContext.hot_swap) "
                         "and distributes parameters over the degraded "
                         "fabric")
    argv = sys.argv[1:] if argv is None else list(argv)
    args = ap.parse_args(argv)

    from .runtime import force_host_devices, use_compile_cache
    if args.host_devices:
        force_host_devices(args.host_devices, "repro.launch.serve", argv)
    use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.serve import Request, ServingEngine
    from .sharding import serving_param_specs, to_named

    mp = args.model_parallel
    devs = jax.devices()[:mp]
    mesh = Mesh(np.array(devs).reshape(1, mp), ("data", "model"))

    broadcast_params = mp > 1 and not args.no_broadcast_params
    ctx = None
    if args.schedule_cache or broadcast_params:
        # Serving restarts are frequent; warm the artifact cache with the
        # model-axis tree-pipeline programs so only the first boot pays for
        # schedule compilation (pipeline-collectives consumers load them;
        # the XLA-collective engine below is unaffected).  With mp > 1 the
        # context also provides the broadcast program used to distribute
        # the parameters below.
        from repro.api import Collectives
        from repro.comms import CollectiveContext
        coll = Collectives(cache=args.schedule_cache or None)
        ctx = CollectiveContext({"data": 1, "model": mp},
                                collectives=coll)
        print(ctx.describe())
        if coll.cache is not None:
            print(coll.cache.describe())
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.float32 if args.reduced else jnp.bfloat16)
    if broadcast_params:
        # Distribute the host-initialized checkpoint through the cached
        # single-root broadcast artifact: every device ends up with the
        # root's bytes (MPI_Bcast semantics) before TP sharding applies.
        from repro.comms import tree_broadcast
        from jax.sharding import PartitionSpec as P

        prog = ctx.broadcast_program("model", root=0)
        if args.inject_fault:
            # a link died between boot and parameter distribution: repair
            # the compiled broadcast (and any other model-axis programs)
            # and carry on over the degraded fabric — no recompile from
            # scratch, no engine restart
            from repro.train import LinkFault
            u_s, v_s = args.inject_fault.split("-", 1)
            fault = LinkFault(int(u_s), int(v_s))
            print(f"[repair] injected {fault}")
            reports = ctx.hot_swap(fault.transform_text)
            for axis, reps in reports.items():
                for r in reps:
                    print(f"[repair] axis {axis} {r.kind}: "
                          f"{r.repair_time_s * 1e3:.1f}ms "
                          f"warm=(solve={r.warm_solve},split={r.warm_split})")
            prog = ctx.broadcast_program("model", root=0)

        def _bcast_tree(tree):
            return jax.tree.map(
                lambda x: tree_broadcast(x, prog, "model"), tree)

        bcast = jax.shard_map(_bcast_tree, mesh=mesh, in_specs=P(),
                              out_specs=P(), check_vma=False)
        t0 = time.perf_counter()
        with mesh:
            params = jax.jit(bcast)(params)
        params = jax.block_until_ready(params)
        print(f"params distributed via tree broadcast "
              f"(root=0, axis=model, {mp} devices) in "
              f"{(time.perf_counter() - t0) * 1e3:.0f} ms")
    if ctx is not None:
        print(ctx.compile_stats_report())
    p_spec = serving_param_specs(jax.eval_shape(lambda: params), mesh)
    with mesh:
        params = jax.device_put(params, to_named(p_spec, mesh))
        engine = ServingEngine(model, params, batch_size=args.batch_size,
                               max_len=args.max_len)
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            plen = int(rng.integers(4, 24))
            engine.submit(Request(
                uid=i,
                prompt=rng.integers(1, cfg.vocab_size, plen, dtype=np.int32),
                max_new_tokens=args.new_tokens))
        # traced under the mesh, so that code which cannot be partitioned
        # (the SSD's Pallas kernels) sees a tensor-parallel step
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            completions = engine.run()
        for c in completions:
            print(f"req {c.uid}: {c.prompt_len} prompt -> "
                  f"{len(c.tokens) - c.prompt_len} new tokens "
                  f"({c.latency_s * 1e3:.0f} ms batch)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
