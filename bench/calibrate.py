#!/usr/bin/env python3
"""Readings that set a cell's limits, on the chip at the cell's own size.

    python3 bench/calibrate.py --workload <cell> --seeds <n> [--control-seeds <k>]

In one process: the float32 reference once per seed; the program's
readings on `--seeds` seeds through the harness's own set-up path (the
lower readings); the control — the reference computed in fp8
(bench/check.py's CONTROL), put in the program's place — and each fault of
bench/faults.py that the cell can have, on the first `--control-seeds`
seeds (the upper readings).  `unchanged_state` reads 1 on `update_gap` by
construction and is not run.  One JSON line per reading goes to standard
output, with the verdict of bench/check.py against the cell's limits as
they stand and, for the program and the control, the gradient error split
by leaf and by layer; nothing here runs in a benchmark run.
"""
import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_017)
    ap.add_argument("--faults", default="half_batch,no_exchange",
                    help="comma-separated faults to read (bench/faults.py)")
    ap.add_argument("--compute-dtype", default=None,
                    help="run the program in this dtype instead of the "
                         "cell's (a witness: float32 against the float32 "
                         "reference)")
    args = ap.parse_args(argv)

    from bench import run
    import os
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE)
    os.environ["TPU_LOG_DIR"] = "disabled"
    import jax
    jax.config.update("jax_compilation_cache_dir", str(run.CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench import check, faults, program

    cell = run.load_cell(args.workload)
    devices, _ = run.require_chips(cell.chips)
    tf = cell.traffic
    if args.compute_dtype:
        tf = dict(tf, compute_dtype=args.compute_dtype)
    seeds = [args.first_seed + 7919 * k for k in range(args.seeds)]
    emit = lambda **kw: print(json.dumps(dict(cell=cell.name, **kw)),
                              flush=True)

    scale = 1 / (1 - tf["optimizer"]["b1"])

    def program_readings(prog, seed):
        """The program's readings and the reference's for `seed`."""
        prog.data = dataclasses.replace(prog.data, seed=seed)
        kd = program.seed_key_data(seed)
        p, o, _, got, host = run.checked_steps(prog, run.stepper(prog), kd)
        for x in jax.tree.leaves((p, o)):
            x.delete()
        ref = check.reference_readings(cell.config, tf, seed, kd, devices,
                                       other_grad=host.pop("mu"),
                                       other_scale=scale)
        got["change"] = check.program_change(cell.config, kd, devices,
                                             host.pop("params"))
        return got, ref

    prog = program.build(cell.config, tf, devices)
    for seed in seeds:
        t = time.perf_counter()
        got, ref = program_readings(prog, seed)
        numbers = check.compare(got, ref)
        emit(kind="program", seed=seed, numbers=numbers,
             compute_dtype=tf["compute_dtype"],
             correct=check.verdict(numbers, cell.limits),
             loss=got["loss"], ref_loss=ref["loss"],
             leaves=check.leaf_gaps(cell.config, got, ref),
             seconds=time.perf_counter() - t)
        gc.collect()
    del prog

    for seed in seeds[:args.control_seeds]:
        t = time.perf_counter()
        kd = program.seed_key_data(seed)
        ctrl = check.reference_readings(cell.config, tf, seed, kd, devices,
                                        low=check.CONTROL, keep_grad=True)
        ref = check.reference_readings(cell.config, tf, seed, kd, devices,
                                       other_grad=ctrl.pop("grad_leaves"))
        numbers = check.compare(ctrl, ref)
        emit(kind="control", seed=seed, numbers=numbers,
             correct=check.verdict(numbers, cell.limits), loss=ctrl["loss"],
             leaves=check.leaf_gaps(cell.config, ctrl, ref),
             seconds=time.perf_counter() - t)

    for name in filter(None, args.faults.split(",")):
        if not faults.applies(name, tf):
            continue
        with faults.FAULTS[name]():
            prog = program.build(cell.config, tf, devices)
            for seed in seeds[:args.control_seeds]:
                got, ref = program_readings(prog, seed)
                numbers = check.compare(got, ref)
                emit(kind=name, seed=seed, numbers=numbers,
                     correct=check.verdict(numbers, cell.limits),
                     loss=got["loss"], ref_loss=ref["loss"])
                gc.collect()
            del prog
    return 0


if __name__ == "__main__":
    sys.exit(main())
