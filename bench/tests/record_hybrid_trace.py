#!/usr/bin/env python3
"""Record the small CPU trace that test_scopes_named.py reads:

    python3 bench/tests/record_hybrid_trace.py

The zamba2-1.2b.dp1 cell's step at the widths of tiny.py but two periods
deep (13 layers: shared-block sites before layers 6 and 12), on one
device: compiled by one step, then three steps traced as the benchmark's
traced segment runs them.  The profiler keeps the step's optimized HLO in
the trace, whose metadata names each operation's scope.  Writes
bench/testdata/cpu1_zamba2_step.xplane.pb."""
import glob
import os
import shutil
import sys
import tempfile
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
OUT = ROOT / "bench" / "testdata" / "cpu1_zamba2_step.xplane.pb"
STEPS, SEED = 3, 7
#: two whole periods of six Mamba2 layers and a shared-block site each
LAYERS = 13


def main() -> int:
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import program
    from bench.run import stepper
    from bench.tests.tiny import TINY_MODEL, tiny_cell

    cell = tiny_cell("zamba2-1.2b.dp1", dict(TINY_MODEL, num_layers=LAYERS))
    prog = program.build(cell.config, cell.traffic, jax.devices()[:1], SEED)
    p, o = prog.init(program.seed_key_data(SEED))
    one_step = stepper(prog)
    p, o, _ = one_step(p, o, 0)
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for i in range(1, 1 + STEPS):
            p, o, _ = one_step(p, o, i)
        jax.profiler.stop_trace()
        (src,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True)
        shutil.copy(src, OUT)
    print(f"wrote {OUT}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
