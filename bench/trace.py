"""Reduction of a profiler trace (`.xplane.pb`) to device intervals and host
spans, and the sums the per-layer metrics read.

Device operations are the events of the "XLA Ops" line of each
`/device:TPU:<n>` plane, named by the HLO instruction's text
("%fusion.7 = f32[..] fusion(..)"), kept as its name and opcode.  A trace
without device planes (the CPU backend, used by the tests) has its
operations on host threads instead: the events that carry an `hlo_op`
and a `device_ordinal` stat.  Host spans are the
benchmark's own `jax.profiler.TraceAnnotation`s, whose names start with
"bench.".  All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

SPAN_PREFIX = "bench."
STEP_SPAN = "bench.step"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
PERMUTE_OPS = {"collective-permute", "collective-permute-start",
               "collective-permute-done", "ppermute"}
#: control flow: its interval holds its body's operations
CONTAINERS = {"while", "conditional", "call"}


@dataclasses.dataclass(frozen=True)
class Interval:
    name: str
    start: float
    end: float
    op: str = ""                         # HLO opcode of a device operation


def hlo_name(text: str) -> Tuple[str, str]:
    """(name, opcode) of an operation event: from "%name = shape
    opcode(operands), ..." on TPU, from "name.N" on the CPU."""
    if " = " not in text:
        return text, re.sub(r"\.\d+$", "", text)
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):             # a tuple shape: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:].lstrip()
    else:
        rest = rest.split(" ", 1)[1] if " " in rest else ""
    return name.lstrip("%"), rest.split("(", 1)[0].strip()


def _op(text: str, start: float, duration: float) -> Interval:
    name, op = hlo_name(text)
    return Interval(name, start, start + duration, op)


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Interval]]       # device id -> operations by start
    spans: List[Interval]                # bench.* host spans by start


def _stats(event) -> dict:
    return dict(event.stats)


def from_planes(planes) -> Trace:
    """Build a Trace from `jax.profiler.ProfileData` planes."""
    ops: Dict[int, List[Interval]] = defaultdict(list)
    host_ops: Dict[int, List[Interval]] = defaultdict(list)
    spans: List[Interval] = []
    for plane in planes:
        dev = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if dev:
                if line.name == "XLA Ops":
                    ops[int(dev.group(1))].extend(
                        _op(e.name, e.start_ns, e.duration_ns)
                        for e in line.events)
                continue
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append(Interval(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
                    continue
                st = _stats(e) if plane.name == "/host:CPU" else {}
                if "hlo_op" in st and "device_ordinal" in st:
                    host_ops[int(st["device_ordinal"])].append(
                        _op(str(st["hlo_op"]), e.start_ns, e.duration_ns))
    ops = ops or host_ops
    return Trace(ops={d: sorted(v, key=lambda i: i.start)
                      for d, v in sorted(ops.items())},
                 spans=sorted(spans, key=lambda i: i.start))


def load(path: str) -> Trace:
    """`path` is an `.xplane.pb` file or a directory holding one (the
    profiler writes it under plugins/profile/<time>/)."""
    import jax
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True))
        if len(found) != 1:
            raise FileNotFoundError(f"{len(found)} .xplane.pb files under "
                                    f"{path}")
        path = found[0]
    return from_planes(jax.profiler.ProfileData.from_file(path).planes)


# ---------------------------------------------------------------------- #
# interval arithmetic
# ---------------------------------------------------------------------- #

def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged [start, end) pieces of `intervals`, clipped to [lo, hi)."""
    out: List[List[float]] = []
    for iv in sorted(intervals, key=lambda i: i.start):
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(pieces: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The parts of [lo, hi) that `pieces` (merged, sorted) leave free."""
    out, t = [], lo
    for s, e in pieces:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def inside(iv: Interval, lo: float, hi: float) -> bool:
    return iv.start >= lo and iv.end <= hi


# ---------------------------------------------------------------------- #
# the reduction the metrics read
# ---------------------------------------------------------------------- #

@dataclasses.dataclass
class Summary:
    steps: int                       # bench.step spans in the window
    window_s: float                  # first step start .. last step end
    busy_s: Dict[int, float]         # device -> union of its operations
    permute_s: Dict[int, float]      # device -> collective-permute time
    permute_calls: Dict[int, int]    # device -> permute ops (not "-done")
    op_s: Dict[str, float]           # op name -> s, mean over devices
    #                                  (control flow left out: its body's
    #                                  operations are listed)
    idle_by_span: Dict[str, float]   # host span -> idle s, mean over devices

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s)


def innermost_span(spans: List[Interval], t: float,
                   starts: Optional[List[float]] = None) -> str:
    """Name of the innermost span open at `t` (spans are sorted by start
    and nest, so it is the open one that started last)."""
    starts = starts if starts is not None else [sp.start for sp in spans]
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if spans[i].end > t:
            return spans[i].name
        i -= 1
    return "none"


def attribute(gap: Tuple[float, float], spans: List[Interval],
              starts: List[float], bounds: List[float]):
    """Split an idle gap at span boundaries; yield (span name, ns)."""
    s, e = gap
    cuts = [s] + bounds[bisect.bisect_right(bounds, s):
                        bisect.bisect_left(bounds, e)] + [e]
    for a, b in zip(cuts, cuts[1:]):
        yield innermost_span(spans, (a + b) / 2, starts), b - a


def summarize(tr: Trace) -> Summary:
    steps = [s for s in tr.spans if s.name == STEP_SPAN]
    if not steps:
        raise ValueError(f"no {STEP_SPAN} spans in the trace")
    if not tr.ops:
        raise ValueError("no device operations in the trace")
    lo, hi = steps[0].start, max(s.end for s in steps)
    n = len(tr.ops)
    busy, permute_s, permute_n = {}, {}, {}
    op_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    starts = [sp.start for sp in tr.spans]
    bounds = sorted({t for sp in tr.spans for t in (sp.start, sp.end)})
    for dev, ops in tr.ops.items():
        pieces = union(ops, lo, hi)
        busy[dev] = sum(e - s for s, e in pieces) * 1e-9
        mine = [o for o in ops if inside(o, lo, hi)]
        perm = [o for o in mine if o.op in PERMUTE_OPS]
        permute_s[dev] = sum(o.end - o.start for o in perm) * 1e-9
        permute_n[dev] = sum(not o.op.endswith("-done") for o in perm)
        for o in mine:
            if o.op not in CONTAINERS:
                op_s[o.name] += (o.end - o.start) * 1e-9 / n
        for gap in gaps(pieces, lo, hi):
            for name, ns in attribute(gap, tr.spans, starts, bounds):
                idle[name] += ns * 1e-9 / n
    return Summary(steps=len(steps), window_s=(hi - lo) * 1e-9, busy_s=busy,
                   permute_s=permute_s, permute_calls=permute_n,
                   op_s=dict(op_s), idle_by_span=dict(idle))


def breakdown(summary: Summary, top: int = 10) -> dict:
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(summary.op_s),
            "idle_gaps": rank(summary.idle_by_span)}
