#!/usr/bin/env python3
"""Record the small CPU trace that test_trace.py reads:

    python3 bench/tests/record_trace.py

Four virtual host devices; three `bench.step` spans, each with a 20 ms
host pause in `bench.batch` (the devices idle), then one jitted program
with two ppermutes and a matrix product under `bench.dispatch` and
`bench.sync`.  Writes bench/testdata/cpu4_ppermute.xplane.pb."""
import glob
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
OUT = Path(__file__).resolve().parents[1] / "testdata" / \
    "cpu4_ppermute.xplane.pb"
STEPS, PAUSE_S, PERMUTES = 3, 0.02, 2


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    ring = [(i, (i + 1) % 4) for i in range(4)]

    def body(x):
        for _ in range(PERMUTES):
            x = jnp.tanh(jax.lax.ppermute(x, "data", ring) @ x.T @ x)
        return x

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data"), check_vma=False))
    x = jnp.ones((4 * 128, 128)) / 128
    f(x).block_until_ready()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(STEPS):
            with TraceAnnotation("bench.step"):
                with TraceAnnotation("bench.batch"):
                    time.sleep(PAUSE_S)
                with TraceAnnotation("bench.dispatch"):
                    y = f(x)
                with TraceAnnotation("bench.sync"):
                    y.block_until_ready()
        jax.profiler.stop_trace()
        (src,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"),
                           recursive=True)
        OUT.parent.mkdir(exist_ok=True)
        shutil.copy(src, OUT)
    print(f"wrote {OUT} ({OUT.stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
