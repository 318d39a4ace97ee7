#!/usr/bin/env python3
"""Smoke run of the training entry point and the tree-pipeline collectives
on TPU.  One process holds the chips; it starts no children.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # a four-chip host (v5e:2x2)

One chip: `repro.launch.train.main` at the full mamba2-780m config (48
layers, d_model 1536, vocab 50280) for 3 steps, first with
``--collectives xla`` and then with ``--collectives pipeline`` (gradients
through the tree-pipeline allreduce on a size-1 data axis).

Four chips, and nothing else: the six ``tree_*`` collectives against their
``jax.lax`` counterparts on a 4-device mesh, then the trainer at
``--data-parallel 4``, pipeline against xla.  The replicated-parameter
pipeline step does not fit a 16 GiB chip at 48 layers (compiled for a
described v5e: 17.1 of 15.75 GiB; 40 layers: 16.0), so this phase cuts
depth to 36 layers (14.6 GiB); widths stay the published ones.

Step times printed here are set-up information, not a benchmark.  The last
line of stdout is ``{"ok": true, "device": {...}}``, printed only when every
check passed.  Without a TPU it exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import math
import os
import re
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

#: the step-0 losses of ``xla`` and ``pipeline`` come from identical
#: weights (PRNGKey(0)) and an identical batch, so they differ only by
#: rounding.  Compute is bf16 (8 significant bits, relative rounding
#: 2**-8 ~ 3.9e-3) and the two step programs are compiled separately, so
#: they may round at different points: allow a little over one bf16 ulp.
LOSS_RTOL = 5e-3
STEPS = 3
STEP_RE = re.compile(r"^step (\d+): loss=(\S+) dt=(\S+)s$", re.M)


class _Tee(io.TextIOBase):
    """Echo to the real stdout and keep a copy to parse."""

    def __init__(self, out):
        self.out, self.buf = out, io.StringIO()

    def write(self, s):
        self.out.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self):
        self.out.flush()


def train(mode: str, extra: list[str]) -> list[float]:
    """Run the trainer's own main in-process; return the per-step losses."""
    import jax
    from repro.launch.train import main as train_main

    # the final checkpoint is the full state (~9 GB): keep it out of the
    # checkout and out of anything copied back from the machine
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        argv = ["--arch", "mamba2-780m", "--steps", str(STEPS),
                "--collectives", mode, "--ckpt-dir", ckpt,
                "--ckpt-every", str(STEPS + 1)] + extra
        print(f"== train {mode}: {' '.join(argv)}", flush=True)
        tee = _Tee(sys.stdout)
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(tee):
            rc = train_main(argv)
        wall = time.perf_counter() - t0
    if rc != 0:
        raise SystemExit(f"train {mode}: main returned {rc}")
    steps = STEP_RE.findall(tee.buf.getvalue())
    if [int(s) for s, _, _ in steps] != list(range(STEPS)):
        raise SystemExit(f"train {mode}: expected steps 0..{STEPS - 1}, "
                         f"logged {steps}")
    losses = [float(l) for _, l, _ in steps]
    print(f"train {mode}: losses {losses}; step wall times (s) "
          f"{[float(d) for _, _, d in steps]} (step 0 includes compile); "
          f"run {wall:.1f}s incl. init and checkpoint", flush=True)
    gc.collect()
    live = jax.live_arrays()
    stats = jax.devices()[0].memory_stats() or {}
    print(f"train {mode}: after run {len(live)} live arrays, "
          f"{sum(a.nbytes for a in live)} bytes; device 0 peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use')} of {stats.get('bytes_limit')}",
          flush=True)
    return losses


def compare_losses(runs: dict) -> list[str]:
    errors = []
    for mode, losses in runs.items():
        if not all(math.isfinite(v) for v in losses):
            errors.append(f"{mode}: non-finite loss in {losses}")
    x, p = runs["xla"][0], runs["pipeline"][0]
    rel = abs(x - p) / abs(x)
    print(f"step-0 loss xla {x} pipeline {p}: relative difference {rel:.3e} "
          f"(limit {LOSS_RTOL})", flush=True)
    for i, (a, b) in enumerate(zip(runs["xla"], runs["pipeline"])):
        print(f"  step {i}: xla {a} pipeline {b} diff {b - a:+.3e}")
    if not rel <= LOSS_RTOL:
        errors.append(f"step-0 loss differs: xla {x}, pipeline {p}")
    return errors


def collectives_4() -> list[str]:
    """All six tree_* collectives against jax.lax on a 4-device mesh.
    Inputs are small integers in f32, so every sum is exact whatever the
    summation order, and the results must be bitwise equal."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comms import (CollectiveContext, tree_all_gather,
                             tree_all_reduce, tree_all_to_all,
                             tree_broadcast, tree_reduce,
                             tree_reduce_scatter)

    a = 4
    mesh = Mesh(np.array(jax.devices()[:a]), ("data",))
    coords = [tuple(getattr(d, "coords", ())) for d in mesh.devices.flat]
    print(f"mesh order device coords: {coords}")
    far = [(coords[i], coords[(i + 1) % a]) for i in range(a)
           if sum(abs(u - v) for u, v in
                  zip(coords[i], coords[(i + 1) % a])) != 1]
    print(f"ring neighbours in mesh order that are not ICI neighbours: "
          f"{far or 'none'}")

    ctx = CollectiveContext({"data": a})
    print(ctx.describe())
    ax = ctx.axis("data")
    bc = ctx.broadcast_program("data", root=0)
    rd = ctx.collectives.program(ctx.topology("data"), kind="reduce",
                                 root=0)
    a2a = ctx.alltoall_program("data")

    rows, cols = 256, 1024          # 1 MiB of f32 per block
    cases = {
        # name: (per-device input shape, tree fn, lax fn)
        "allgather": ((rows * a, cols),
                      lambda v: tree_all_gather(v, ax.ag_prog, "data"),
                      lambda v: jax.lax.all_gather(v, "data")),
        "reduce_scatter": ((rows * a, cols),
                           lambda v: tree_reduce_scatter(v, ax.rs_prog,
                                                         "data"),
                           lambda v: jax.lax.psum_scatter(
                               v, "data", scatter_dimension=0, tiled=True)),
        "allreduce": ((rows * a, cols),
                      lambda v: tree_all_reduce(v, ax.rs_prog, ax.ag_prog,
                                                "data"),
                      lambda v: jax.lax.psum(v, "data")),
        "broadcast_root0": ((rows * a, cols),
                            lambda v: tree_broadcast(v, bc, "data"),
                            lambda v: jax.lax.all_gather(v, "data")[0]),
        "reduce_root0": ((rows * a, cols),
                         lambda v: tree_reduce(v, rd, "data"),
                         lambda v: jax.lax.psum(v, "data")),
        "alltoall": ((a, rows, cols),
                     lambda v: tree_all_to_all(v, a2a, "data"),
                     lambda v: jax.lax.all_to_all(v, "data", 0, 0)),
    }
    errors = []
    for i, (name, (shape, tree_fn, lax_fn)) in enumerate(cases.items()):
        x = jax.random.randint(jax.random.PRNGKey(i), (a,) + shape, -8, 9
                               ).astype(jnp.float32)
        x = jax.device_put(x, NamedSharding(mesh, P("data")))

        def run(fn):
            f = jax.jit(jax.shard_map(lambda v: fn(v[0])[None], mesh=mesh,
                                      in_specs=P("data"),
                                      out_specs=P("data"), check_vma=False))
            return np.asarray(jax.block_until_ready(f(x)))

        t0 = time.perf_counter()
        got, want = run(tree_fn), run(lax_fn)
        if name == "reduce_root0":      # MPI_Reduce: defined on the root
            got, want = got[0], want[0]
        ok = got.shape == want.shape and np.array_equal(got, want)
        print(f"collective {name}: per-device input {shape} "
              f"({x.nbytes // a} bytes), output {got.shape}, "
              f"bitwise equal to jax.lax: {ok} "
              f"({time.perf_counter() - t0:.1f}s incl. compile)", flush=True)
        if not ok:
            errors.append(f"tree {name} differs from jax.lax")
    return errors


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {platform!r}); "
              f"this check runs only on a TPU", file=sys.stderr)
        return 2
    if args.chips == 4 and len(devs) != 4:
        print(f"chip_smoke: --chips 4 needs exactly 4 TPU devices, found "
              f"{len(devs)}", file=sys.stderr)
        return 2
    kind = devs[0].device_kind
    print(f"platform: {platform}")
    print(f"device_kind: {kind}")
    print(f"device count: {len(devs)}")
    for d in devs:
        print(f"  device {d.id}: coords {getattr(d, 'coords', None)}")

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.runtime import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)

    errors = []
    if args.chips == 1:
        shape = ["--data-parallel", "1", "--global-batch", "1",
                 "--seq", "2048"]
    else:
        errors += collectives_4()
        shape = ["--data-parallel", "4", "--global-batch", "4",
                 "--seq", "2048", "--num-layers", "36"]
    runs = {mode: train(mode, shape) for mode in ("xla", "pipeline")}
    errors += compare_losses(runs)

    if errors:
        for e in errors:
            print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
