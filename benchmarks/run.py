"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  fig1_optimality      — Fig 1/2: optimum vs ring-unwinding on the paper's
                         switch topology (derived = speedup, expect 4x)
  pipeline_convergence — §1.3: achieved/optimal ratio vs chunk count
  zoo_optimality       — eq (1) + achieved ratio across the topology zoo
  allreduce_rs_ag      — App. B: RS+AG vs RE+BC runtime factors
  broadcast_reduce_family — App. A single-root broadcast + reversed reduce
                         vs the eq (5) bound M/λ(root)
  schedule_gen_scaling — §3: strongly-polynomial generation time vs size
  schedule_sweep       — compile+verify the full topology zoo in parallel,
                         emitting BENCH_schedules.json (see repro.cache.sweep)

Modes: default runs everything; ``--smoke`` runs only the 3-topology sweep
smoke (<60s, CI); ``--sweep`` runs only the full sweep.
"""
from __future__ import annotations

import argparse
import os
import sys
import time
from fractions import Fraction

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.api import Collectives
from repro.core import (allgather_inv_xstar, re_bc_allreduce_runtime,
                        rs_ag_allreduce_runtime, simulate_allgather,
                        simulate_allreduce, simulate_broadcast,
                        simulate_reduce, solve_optimality)
from repro.topo import resolve_topology

#: one uncached facade for the whole battery — every schedule the
#: benchmarks compile goes through the repo's single front door
COLL = Collectives()


def row(name: str, us: float, derived: str) -> None:
    print(f"{name},{us:.1f},{derived}", flush=True)


def timed(fn, *args, repeat=1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    return out, (time.perf_counter() - t0) / repeat * 1e6


# ---------------------------------------------------------------------- #

def fig1_optimality() -> None:
    """Paper Fig 1/2: edge splitting preserves the cluster cut; ring
    unwinding loses 4x."""
    g = resolve_topology("fig1a")
    opt, us = timed(solve_optimality, g)
    ring_inv = allgather_inv_xstar(resolve_topology("fig1d"))
    row("fig1_optimality.ours", us, f"inv_x*={opt.inv_x_star}")
    row("fig1_optimality.ring_unwound", us,
        f"inv_x*={ring_inv};slowdown={ring_inv / opt.inv_x_star}x")


def pipeline_convergence() -> None:
    for p in (1, 2, 4, 8, 16, 32, 64, 128):
        sched, us = timed(COLL.schedule, "fig1a", num_chunks=p)
        rep = simulate_allgather(sched)
        row(f"pipeline_convergence.P{p}", us, f"ratio={float(rep.ratio):.4f}")


def zoo_optimality() -> None:
    zoo = ("fig1a", "ring:8", "bring:8", "torus2d:4x4", "fattree",
           "dragonfly", "dgx:8", "star:8", "multipod:2x4")
    for spec in zoo:
        g = resolve_topology(spec)
        sched, us = timed(COLL.schedule, g, num_chunks=32)
        rep = simulate_allgather(sched)
        row(f"zoo_optimality.{g.name}", us,
            f"inv_x*={sched.opt.inv_x_star};k={sched.opt.k};"
            f"ratio={float(rep.ratio):.4f}")


def allreduce_rs_ag() -> None:
    for spec in ("fig1a", "ring:6", "dragonfly", "dgx:8"):
        g = resolve_topology(spec)
        (rs_ag, us) = timed(rs_ag_allreduce_runtime, g)
        re_bc = re_bc_allreduce_runtime(g)
        ar = COLL.schedule(g, kind="allreduce", num_chunks=32)
        rep = simulate_allreduce(ar)
        row(f"allreduce.{g.name}", us,
            f"rs_ag={rs_ag};re_bc={re_bc};"
            f"re_bc/rs_ag={float(re_bc / rs_ag):.2f};"
            f"achieved_ratio={float(rep.ratio):.3f}")


def broadcast_reduce_family() -> None:
    """Appendix A + dual: single-root broadcast/reduce across topologies,
    converging to the eq (5) bound M/λ(root)."""
    for spec in ("fig1a", "bring:8", "dragonfly", "star:8"):
        g = resolve_topology(spec)
        bc, us = timed(COLL.schedule, g, kind="broadcast", num_chunks=32)
        rep_bc = simulate_broadcast(bc)
        rep_red = simulate_reduce(
            COLL.schedule(g, kind="reduce", num_chunks=32))
        row(f"broadcast_reduce.{g.name}", us,
            f"lambda={bc.k};bc_ratio={float(rep_bc.ratio):.4f};"
            f"red_ratio={float(rep_red.ratio):.4f}")


def schedule_gen_scaling() -> None:
    """§3: runtime vs topology size (strongly polynomial — and capacity-
    independent: scaling all bandwidths 100x must not change the time)."""
    for n in (4, 8, 16, 24):
        _, us = timed(COLL.schedule, f"bring:{n}", num_chunks=8)
        row(f"schedule_gen.bidir_ring{n}", us, f"nodes={n}")
    for n in (4, 8, 12):
        _, us = timed(COLL.schedule, f"two_cluster:{n // 2},10,1",
                      num_chunks=8)
        row(f"schedule_gen.two_cluster{n}", us, f"nodes={n}+3sw")
    _, us1 = timed(COLL.schedule, "two_cluster:4,10,1", num_chunks=8)
    _, us100 = timed(COLL.schedule, "two_cluster:4,1000,100", num_chunks=8)
    row("schedule_gen.capacity_independence", us100,
        f"t(100x_bandwidth)/t(1x)={us100 / max(us1, 1):.2f}")


def schedule_sweep(out_path: str, smoke: bool = False,
                   cache_dir: str | None = None,
                   topologies: list[str] | None = None,
                   full: bool = False, pack_jobs: int = 1) -> None:
    """Parallel zoo sweep; every entry must reproduce its claimed runtime.
    `topologies` specs ride alongside the selected zoo rows (the smoke set
    under --smoke, the whole zoo under --sweep/the full battery), or alone
    when only --topology was given."""
    from repro.cache import (SMOKE_NAMES, claim_mismatches, run_sweep,
                             sweep_registry)
    if smoke:
        names = list(SMOKE_NAMES)
    elif full and topologies:
        names = list(sweep_registry())   # whole zoo + the extra specs
    else:
        names = None                     # run_sweep: zoo, or specs alone
    t0 = time.perf_counter()
    doc = run_sweep(names=names, cache_dir=cache_dir, out_path=out_path,
                    topologies=topologies, pack_jobs=pack_jobs)
    us = (time.perf_counter() - t0) * 1e6
    for e in doc["entries"]:
        row(f"schedule_sweep.{e['name']}", e["compile_time_s"] * 1e6,
            f"inv_x*={e['inv_x_star']};k={e['k']};depth={e['depth']};"
            f"achieved/claimed={e['achieved_over_claimed']};"
            f"achieved/lb={e['achieved_over_lb_float']:.4f}")
    bad = claim_mismatches(doc)
    row("schedule_sweep.total", us,
        f"topologies={doc['num_topologies']};claim_mismatches={len(bad)};"
        f"out={out_path}")
    if bad:
        raise SystemExit(f"schedule sweep claim mismatches: {bad}")


def build_parser() -> argparse.ArgumentParser:
    """The benchmark CLI (exposed separately so tools/check_docs.py can
    assert the documented flags match)."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="3-topology schedule sweep only (<60s, CI)")
    ap.add_argument("--sweep", action="store_true",
                    help="full schedule sweep only")
    ap.add_argument("--out", default=None,
                    help="sweep output path (default: BENCH_schedules.json, "
                         "or BENCH_schedules.smoke.json under --smoke so the "
                         "committed full-sweep scoreboard is never clobbered)")
    ap.add_argument("--cache-dir", default=None,
                    help="schedule artifact cache dir for the sweep")
    ap.add_argument("--topology", nargs="*", default=None, metavar="SPEC",
                    help="sweep these extra TopologySpec strings (full "
                         "grammar incl. transforms): alongside the selected "
                         "zoo rows under --smoke/--sweep, or alone when "
                         "given by themselves — arbitrary non-zoo fabrics "
                         "without a code edit")
    ap.add_argument("--pack-jobs", type=int, default=1,
                    help="process-parallel split+pack within each family "
                         "(engages when topology-level parallelism is "
                         "inactive; schedules stay byte-identical)")
    return ap


def main(argv: list[str] | None = None) -> None:
    args = build_parser().parse_args(argv)
    if args.out is None:
        from repro.cache import default_out_path
        args.out = default_out_path(
            partial=args.smoke or args.topology is not None)

    print("name,us_per_call,derived")
    if args.smoke or args.sweep or args.topology is not None:
        schedule_sweep(args.out, smoke=args.smoke, cache_dir=args.cache_dir,
                       topologies=args.topology, full=args.sweep,
                       pack_jobs=args.pack_jobs)
        return
    fig1_optimality()
    pipeline_convergence()
    zoo_optimality()
    allreduce_rs_ag()
    broadcast_reduce_family()
    schedule_gen_scaling()
    schedule_sweep(args.out, cache_dir=args.cache_dir,
                   pack_jobs=args.pack_jobs)


if __name__ == "__main__":
    main()
