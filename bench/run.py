#!/usr/bin/env python3
"""On-chip benchmark of the training step: one cell of BENCHMARK.json.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (`bench/configs/<config>.json`) under a traffic
file (`bench/workloads/<traffic>.json`), both named in BENCHMARK.json;
`correct` is held to `bench/limits/<cell>.json` and each per-layer metric
is read by `bench/metrics/<metric>.py`.  A run:

1. set-up: builds the program's step, makes the weights from the seed on
   the device, and drives the step through the checked steps, which
   compile it and give the readings `correct` compares (bench/check.py);
2. the window: the closed loop of steps (batch from the program's loader,
   step, `block_until_ready`) for `--seconds`, dispatched the traffic's
   `ahead_s` seconds ahead of the step waited for; all that was sent by
   then is waited for, and counts with its time;
3. with `--trace 1`, a traced segment of the traffic's `trace_steps`
   steps after the window, reduced by bench/trace.py;
4. after the memory peak is read and the program's state is freed, the
   float32 reference over the checked steps, and the comparison.

The last line of standard output is one JSON object; the numbers compared
are the last lines of standard error.  Without a TPU, with fewer chips than
the cell asks for, or on a device kind missing from bench/peaks.json, it
exits with 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: JAX's persistent compilation cache: a fixed directory in the checkout
CACHE = ROOT / ".bench_cache" / "jax"
#: traces of `--trace 1` runs, the newest one per cell
OUT = ROOT / ".bench_out"
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Refused(Exception):
    """The run cannot be made here: it prints no result."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[str]
    per_layer: List[str]
    units: Dict[str, str]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json") -> Cell:
    with open(bench_file) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(ROOT / cfg["file"]) as f:
        config = json.load(f)
    with open(BENCH / "workloads" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    with open(BENCH / "limits" / f"{name}.json") as f:
        limits = json.load(f)["limits"]
    mine = lambda ms: [m["name"] for m in ms
                       if name in m.get("workloads", [name])]
    metrics = bench["end_to_end"] + bench["per_layer"]
    return Cell(name=name, chips=w["chips"], config=config, traffic=traffic,
                limits=limits, end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]),
                units={m["name"]: m["unit"] for m in metrics})


def require_chips(n: int):
    """The first `n` TPU devices and the peaks row of their kind."""
    import jax
    from bench.flops import peaks
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no TPU: JAX platform is {devs[0].platform!r}")
    if len(devs) < n:
        raise Refused(f"the cell asks for {n} chips; JAX finds {len(devs)}")
    try:
        return devs[:n], peaks(devs[0].device_kind)
    except KeyError as e:
        raise Refused(str(e)) from None


@dataclasses.dataclass
class Readings:
    """What the per-layer metric readers see."""
    chips: int
    n_params: int
    peak: dict
    tokens_per_s: Optional[float]
    summary: object          # bench.trace.Summary of the traced segment


def read_metric(name: str, r: Readings) -> Optional[float]:
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(r)


def _finite_or_text(x: float):
    return x if math.isfinite(x) else repr(x)


def _gc_timer(pauses: list):
    """A gc callback that appends each collection's seconds to `pauses`."""
    started = []

    def timer(phase, info):
        if phase == "start":
            started[:] = [time.perf_counter()]
        elif started:
            pauses.append(time.perf_counter() - started[0])
    return timer


def dispatcher(prog):
    """Dispatch one step of the loop and return at once: the program's
    loader builds the batch and the step is enqueued on the device.
    Returns the new state, the metrics and the batch and dispatch seconds."""
    from jax.profiler import TraceAnnotation
    clock = time.perf_counter

    def dispatch(p, o, i):
        t0 = clock()
        with TraceAnnotation("bench.batch"):
            batch = prog.batch(i)
        t1 = clock()
        with TraceAnnotation("bench.dispatch"):
            p, o, m = prog.step(p, o, batch)
        return p, o, m, t1 - t0, clock() - t1
    return dispatch


def stepper(prog, spans: Optional[list] = None):
    """One synchronous step, as set-up and the traced segment run it: the
    step is dispatched and waited for.  With `spans`, each step appends the
    seconds of its batch, dispatch and sync."""
    import jax
    from jax.profiler import TraceAnnotation
    clock = time.perf_counter
    dispatch = dispatcher(prog)

    def one_step(p, o, i):
        with TraceAnnotation("bench.step"):
            p, o, m, batch_s, dispatch_s = dispatch(p, o, i)
            t2 = clock()
            with TraceAnnotation("bench.sync"):
                jax.block_until_ready((p, o, m))
            if spans is not None:
                spans.append((batch_s, dispatch_s, clock() - t2))
        return p, o, m["loss"]
    return one_step


def window(prog, p, o, i: int, seconds: float, ahead_s: float,
           step_s: float, losses: list):
    """The measured window: steps are dispatched up to `ahead_s` seconds
    (of `step_s` each) ahead of the one waited for, so that the device
    keeps working while the host stands still.  Once `seconds` are up
    nothing more is sent, and the window closes when all that was sent
    has finished.  Returns the state, the next step's index, each step's
    seconds between its completion and the previous one's (they add up
    to the window), and the window's seconds."""
    import collections
    import jax
    from jax.profiler import TraceAnnotation
    clock = time.perf_counter
    dispatch = dispatcher(prog)
    depth = max(1, math.ceil(ahead_s / step_s))
    inflight = collections.deque()
    times, waits, host = [], [], []
    start = prev = clock()
    while True:
        if not host or clock() - start < seconds:
            p, o, m, batch_s, dispatch_s = dispatch(p, o, i)
            host.append(batch_s + dispatch_s)
            inflight.append(m["loss"])
            losses.append(m["loss"])
            i += 1
            if len(inflight) < depth:
                continue
        if not inflight:
            break
        t = clock()
        with TraceAnnotation("bench.sync"):
            jax.block_until_ready(inflight.popleft())
        now = clock()
        waits.append(now - t)
        times.append(now - prev)
        prev = now
    jax.block_until_ready((p, o))
    slow = sorted(range(len(times)), key=times.__getitem__)[-3:]
    log(f"window {prev - start:.1f} s, {len(times)} steps, {depth} ahead; "
        f"slowest (step, s, waited s) "
        f"{[(k, round(times[k], 4), round(waits[k], 4)) for k in slow]}; "
        f"longest batch + dispatch {max(host):.4f} s (step "
        f"{host.index(max(host))})")
    return p, o, i, times, prev - start


def checked_steps(prog, one_step, key_data):
    """Make the weights and drive the step through the checked steps.
    Returns the live state, the loss arrays, the program's readings (loss,
    first-gradient norms) and, as host leaves, AdamW's first moment after
    the first step ("mu", (1 - b1) times the gradient) and the weights
    after the checked steps ("params"): holding them on the device would
    raise the memory peak."""
    import jax
    import numpy as np
    from bench import check
    grad_norms = check.grad_norm_probe(prog.traffic)
    p, o = prog.init(key_data)
    losses = []
    for i in range(prog.traffic["checked_steps"]):
        p, o, loss = one_step(p, o, i)
        losses.append(loss)
        if i == 0:
            g_norms = check.to_floats(grad_norms(o))
            mu = [np.asarray(x) for x in jax.tree.leaves(o.mu)]
    host = {"mu": mu,
            "params": [np.asarray(x) for x in jax.tree.leaves(p)]}
    got = {"loss": check.to_floats(losses), "grad": g_norms}
    return p, o, losses, got, host


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, devices,
             peak: dict, t0: float = T0) -> dict:
    import jax
    import numpy as np

    from bench import check, program
    from bench import trace as btrace
    from bench.references import load_reference

    tf = cell.traffic
    prog = program.build(cell.config, tf, devices, seed)
    key_data = program.seed_key_data(seed)
    spans = []
    one_step = stepper(prog, spans)
    # set-up: the checked steps compile the step and give the readings
    p, o, losses, got, host = checked_steps(prog, one_step, key_data)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.1f} s")
    compiled = prog.step._cache_size()
    # what set-up left is kept out of the collector's scans in the window
    gc.collect()
    gc.freeze()
    pauses = []
    gc.callbacks.append(timer := _gc_timer(pauses))

    # the window, dispatched ahead by the last checked step's seconds
    p, o, i, times, window_s = window(
        prog, p, o, tf["checked_steps"], seconds, tf["ahead_s"],
        sum(spans[-1]), losses)
    gc.callbacks.remove(timer)
    log(f"{len(pauses)} collections in the window, longest "
        f"{max(pauses, default=0):.4f} s")
    tokens_per_s = len(times) * prog.tokens_per_step / window_s

    summary = None
    if trace:
        tdir = OUT / "trace" / cell.name
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
        for _ in range(tf["trace_steps"]):
            p, o, loss = one_step(p, o, i)
            losses.append(loss)
            i += 1
        jax.profiler.stop_trace()
        del loss
        t = time.perf_counter()
        summary = btrace.summarize(btrace.load(str(tdir)))
        log(f"trace read in {time.perf_counter() - t:.1f} s")
    if prog.step._cache_size() != compiled:
        log("warning: the step compiled again after set-up")

    stats = [d.memory_stats() or {} for d in devices]
    log(f"memory stats of device 0: {stats[0]}")
    # buffers at their peak plus the region the runtime reserved for the
    # compiled programs' scratch: the step's whole footprint
    peak_bytes = max((s.get("peak_bytes_in_use", 0)
                      + s.get("peak_bytes_reserved", 0) for s in stats),
                     default=0)
    loss_values = check.to_floats(losses)
    failed = sum(not math.isfinite(v) for v in loss_values)
    for x in jax.tree.leaves((p, o)):
        x.delete()
    del p, o, losses

    t = time.perf_counter()
    want = check.reference_readings(
        cell.config, tf, seed, key_data, devices, other_grad=host.pop("mu"),
        other_scale=1 / (1 - tf["optimizer"]["b1"]), log=log)
    got["change"] = check.program_change(cell.config, key_data, devices,
                                         host.pop("params"))
    log(f"reference {time.perf_counter() - t:.1f} s")
    numbers = check.compare(got, want)
    correct = check.verdict(numbers, cell.limits)

    if trace:
        n_params = load_reference(cell.config).param_count(
            cell.config["model"])
        r = Readings(chips=len(devices), n_params=n_params, peak=peak,
                     tokens_per_s=tokens_per_s, summary=summary)
        values = {m: read_metric(m, r) for m in cell.per_layer}
        metrics = {m: {"value": v, "unit": cell.units[m]}
                   for m, v in values.items() if v is not None}
    else:
        e2e = {"tokens_per_s": tokens_per_s,
               "step_ms_p90": 1e3 * float(np.percentile(times, 90)),
               "peak_hbm_gib": peak_bytes / 2 ** 30 if peak_bytes else None,
               "setup_s": setup_s}
        metrics = {m: {"value": e2e[m], "unit": cell.units[m]}
                   for m in cell.end_to_end if e2e.get(m) is not None}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    result = {"correct": correct, "attempted": len(loss_values),
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device.update(busy_s=summary.busy_mean_s, window_s=summary.window_s)
        result["breakdown"] = btrace.breakdown(summary)
    result["window"] = {"steps": len(times), "seconds": window_s}
    result["readings"] = {k: _finite_or_text(v) for k, v in numbers.items()}
    result["check"] = {k: {"value": _finite_or_text(numbers[k]),
                           "limit": limit} for k, limit in cell.limits.items()}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["TPU_LOG_DIR"] = "disabled"     # libtpu logs to /tmp else
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise Refused(f"the program (src/repro) is not in {ROOT}")
        cell = load_cell(args.workload)
        import jax
        jax.config.update("jax_compilation_cache_dir", str(CACHE))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devices, peak = require_chips(cell.chips)
    except Refused as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peak)
    for k, v in result["check"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
