"""Production training driver: mesh + sharding policy + sharded data +
fault-tolerant supervisor, end to end.

On a TPU host it runs on the chips JAX finds (`chip_smoke.py` drives it
in-process on one chip, and on four with ``--chips 4``).  For tests on the
CPU, --host-devices N re-execs on N forced CPU host devices.  The paper's
collective layer plugs in at two points: the per-axis topology models used
by GSPMD cost analysis, and (collectives=pipeline) the BucketedAllReduce
gradient hook built from tree-pipeline schedules.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \
        --steps 50 --host-devices 8 --data-parallel 8
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import sys


def start_count(text: str) -> tuple[int, int]:
    """'START:COUNT' -> (START, COUNT), START >= 0 and COUNT >= 1."""
    try:
        start, count = (int(v) for v in text.split(":"))
    except ValueError:
        start = count = -1
    if start < 0 or count < 1:
        raise argparse.ArgumentTypeError(
            f"{text!r}: expected START:COUNT, START >= 0 and COUNT >= 1")
    return start, count


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--num-layers", type=int, default=0,
                    help="cut the config's depth to N layers (0 keeps it); "
                         "widths stay the published ones")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="CPU tests only: re-exec on N forced CPU host "
                         "devices (sets JAX_PLATFORMS=cpu)")
    ap.add_argument("--collectives", default="xla",
                    choices=("xla", "pipeline"),
                    help="xla: stock GSPMD all-reduces.  pipeline: gradients "
                         "flow through a BucketedAllReduce built from the "
                         "cached bandwidth-optimal allreduce artifact "
                         "(shard_map data-parallel driver; requires "
                         "--model-parallel 1)")
    ap.add_argument("--schedule-cache", default="",
                    help="pre-compile the per-axis tree-pipeline collective "
                         "programs into this on-disk artifact cache (later "
                         "launches and any pipeline-collectives consumer "
                         "load them instead of compiling)")
    ap.add_argument("--inject-fault", default="",
                    help="'step:u-v' — raise a LinkFault for link u-v at "
                         "that step.  The supervisor's on_link_fault hook "
                         "repairs the affected per-axis schedules in place "
                         "(CollectiveContext.hot_swap) and retries the same "
                         "step without restoring a checkpoint")
    ap.add_argument("--trace-steps", type=start_count, default=None,
                    metavar="START:COUNT",
                    help="write a profiler trace of COUNT steps from step "
                         "START into <ckpt-dir>/trace")
    argv = sys.argv[1:] if argv is None else list(argv)
    args = ap.parse_args(argv)

    from .runtime import force_host_devices, use_compile_cache
    if args.host_devices:
        force_host_devices(args.host_devices, "repro.launch.train", argv)
    use_compile_cache()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_config, reduced_config
    from repro.models import build_model
    from repro.models.common import set_activation_sharding
    from repro.train import (AdamWConfig, FaultInjector, TrainConfig,
                             TrainSupervisor, init_adamw, make_train_step)
    from repro.train.data import DataConfig, make_global_batch
    from .sharding import batch_specs, opt_specs, param_specs, to_named

    dp, mp = args.data_parallel, args.model_parallel
    devs = jax.devices()
    if dp * mp > len(devs):
        raise SystemExit(f"need {dp * mp} devices, have {len(devs)}")
    mesh = Mesh(np.array(devs[:dp * mp]).reshape(dp, mp), ("data", "model"))
    print(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))}")

    ctx = None
    if args.schedule_cache or args.collectives == "pipeline":
        # Warm the on-disk artifact cache with this mesh's per-axis
        # tree-pipeline programs: the first launch compiles and persists,
        # later launches deserialize.  Under --collectives pipeline the
        # BucketedAllReduce gradient hook below replays the cached
        # `repro.allreduce` artifact end-to-end.
        from repro.api import Collectives
        from repro.comms import CollectiveContext
        coll = Collectives(cache=args.schedule_cache or None)
        ctx = CollectiveContext(dict(zip(mesh.axis_names,
                                         mesh.devices.shape)),
                                collectives=coll)
        print(ctx.describe())
        if coll.cache is not None:
            print(coll.cache.describe())
        if args.collectives != "pipeline":
            # pipeline mode prints the report after the allreduce artifact
            # is acquired; here the per-axis AG/RS programs are all there is
            print(ctx.compile_stats_report())

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if args.num_layers:
        cfg = dataclasses.replace(cfg, num_layers=args.num_layers)
    model = build_model(cfg, remat=True)

    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    # pipeline collectives run a replicated-params shard_map DP driver, so
    # FSDP param sharding only applies to the XLA-collectives path
    p_spec = param_specs(jax.eval_shape(lambda: params), mesh,
                         fsdp=args.collectives == "xla")
    o_spec = opt_specs(p_spec)
    with mesh:
        params = jax.device_put(params, to_named(p_spec, mesh))
        opt = jax.device_put(init_adamw(params), to_named(o_spec, mesh))

    tc = TrainConfig(optimizer=AdamWConfig(lr=1e-3, warmup_steps=10,
                                           total_steps=args.steps),
                     microbatches=args.microbatches,
                     compute_dtype=jnp.float32 if args.reduced
                     else jnp.bfloat16)
    dc = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                    global_batch=args.global_batch,
                    num_image_tokens=cfg.num_image_tokens,
                    encoder_seq=cfg.encoder_seq if cfg.is_encoder_decoder
                    else 0, d_model=cfg.d_model)

    batch0 = make_global_batch(dc, 0, mesh, ("data",))
    b_spec = batch_specs(jax.eval_shape(lambda: batch0), mesh)
    if args.collectives == "pipeline" and mp != 1:
        raise SystemExit("--collectives pipeline requires "
                         "--model-parallel 1")

    def build_step_jit():
        """The jitted step — rebuilt after a hot swap so the shard_map
        closure picks up the repaired ppermute programs."""
        if args.collectives == "pipeline":
            # Gradients cross devices through the paper's tree-pipeline
            # allreduce: one cached `repro.allreduce` artifact per axis,
            # lowered to ppermute programs and wrapped as the
            # BucketedAllReduce hook of make_train_step, executed inside
            # shard_map.
            from jax.sharding import PartitionSpec as P

            red = ctx.bucketed_allreduce("data", wire_dtype=None)
            # the cached allreduce artifact is now acquired (compiled or
            # replayed) — log which pipeline stage the time went to
            print(ctx.compile_stats_report())

            def grad_reduce(tree):
                return jax.tree.map(lambda x: x / dp, red(tree))

            base_step = make_train_step(model, tc, grad_reduce=grad_reduce)

            def spmd_step(params, opt_state, batch):
                p, o, m = base_step(params, opt_state, batch)
                # per-device diagnostics must be replicated for out_specs=P()
                m = {k: jax.lax.pmean(v, "data") for k, v in m.items()}
                return p, o, m

            step_sm = jax.shard_map(spmd_step, mesh=mesh,
                                    in_specs=(P(), P(), P("data")),
                                    out_specs=(P(), P(), P()),
                                    check_vma=False)
            with mesh:
                return jax.jit(step_sm, donate_argnums=(0, 1))
        train_step = make_train_step(model, tc)

        @functools.wraps(train_step)
        def gspmd_step(params, opt_state, batch):
            # traced under the mesh GSPMD partitions it over, so that code
            # which cannot be partitioned (the SSD's Pallas kernels) sees it
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return train_step(params, opt_state, batch)

        with mesh:
            return jax.jit(
                gspmd_step,
                in_shardings=(to_named(p_spec, mesh), to_named(o_spec, mesh),
                              to_named(b_spec, mesh)),
                out_shardings=(to_named(p_spec, mesh), to_named(o_spec, mesh),
                               None),
                donate_argnums=(0, 1))

    live = {"step_jit": build_step_jit()}
    injector = (FaultInjector.parse(args.inject_fault)
                if args.inject_fault else None)

    def step_fn(step, state):
        if injector is not None:
            injector.check(step)
        p, o = state
        batch = make_global_batch(dc, step, mesh, ("data",))
        p, o, metrics = live["step_jit"](p, o, batch)
        return (p, o), metrics

    def on_link_fault(fault):
        if ctx is None:
            # no pipeline collective state to repair — XLA collectives
            # re-route on their own; just retry the step
            print(f"[repair] {fault}: no collective context attached, "
                  f"retrying step on XLA collectives")
            return
        reports = ctx.hot_swap(fault.transform_text)
        for axis, reps in reports.items():
            for r in reps:
                print(f"[repair] axis {axis} {r.kind}: "
                      f"{r.repair_time_s * 1000:.1f}ms "
                      f"warm=(solve={r.warm_solve},split={r.warm_split}) "
                      f"cached={r.cached}")
        live["step_jit"] = build_step_jit()

    os.makedirs(args.ckpt_dir, exist_ok=True)
    sup = TrainSupervisor(ckpt_dir=args.ckpt_dir,
                          ckpt_every=args.ckpt_every,
                          on_link_fault=on_link_fault,
                          trace_steps=args.trace_steps)
    state, final = sup.run(state=(params, opt), num_steps=args.steps,
                           step_fn=step_fn, log_every=1)
    print(f"done at step {final}; stragglers: {len(sup.monitor.flagged)}; "
          f"link faults repaired: "
          f"{injector.fired if injector else False}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
