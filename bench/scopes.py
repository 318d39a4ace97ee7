"""The training step's phases in a traced segment, read from the program's
own scopes.

The program names its phases with `jax.named_scope`s, which reach each
compiled operation's metadata as its `op_name` path.  The profiler keeps
the HLO of every program it saw run in the trace itself (the "Hlo Proto"
stats of its `/host:metadata` plane); a device operation in the trace
(named as bench/trace.py parses it) is matched to its path there, in the
programs that carry the `train.forward` scope, and classed by the first
rule that holds:

1. `optimizer`: the path holds `train.adamw`;
2. `remat`: it holds `rematted_computation` (recomputed under
   `jax.checkpoint`);
3. `backward`: it holds `transpose(` (the transposed forward);
4. `forward`: it holds `train.forward`;
5. `unscoped` otherwise (operations with no path among them).

Apart from that, `ssd` is every operation whose path holds `ssm.ssd`: a
part of the other classes.  Control flow is left out as in
`trace.summarize`: its body's operations are counted instead.  A program
without the `train.forward` scope gives no split.

The program's host spans (`data.batch`, `train.*`, `comms.schedule`)
refine the split of idle device time that `trace.summarize` makes by the
benchmark's own spans.

The per-layer readers see only the run's `trace.Summary`: `of_run` finds
the trace that bench/run.py wrote for it, reads it once for all of them,
and logs the split and the idle time by host span to standard error.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from bench import trace

CLASSES = ("forward", "remat", "backward", "optimizer", "unscoped")
FORWARD = "train.forward"
#: the program's host spans (the benchmark's own start with "bench.")
PROGRAM_SPANS = {"train", "train.dispatch", "train.wait", "train.checkpoint",
                 "train.repair", "data.batch", "comms.schedule"}
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"


# ---------------------------------------------------------------------- #
# the programs' HLO in the trace: protobuf wire format, read by hand (the
# profiler's Python planes leave out the metadata plane's stats)
# ---------------------------------------------------------------------- #

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width ones are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _message(buf) -> Dict[int, list]:
    out: Dict[int, list] = defaultdict(list)
    for f, v in _fields(buf):
        out[f].append(v)
    return out


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _instruction_paths(hlo_proto) -> Dict[str, str]:
    """Instruction name -> `op_name` of an `xla.HloProto`: its module (1)
    -> computations (3) -> instructions (2) -> name (1), metadata (7) ->
    op_name (2)."""
    out = {}
    for module in _message(hlo_proto)[1]:
        for comp in _message(module)[3]:
            for ins in _message(comp)[2]:
                m = _message(ins)
                for md in m[7]:
                    name = _message(md)[2]
                    if name and m[1]:
                        out[_text(m[1][0])] = _text(name[0])
    return out


def program_paths(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Program name -> (instruction name -> `op_name` path), for every
    program whose HLO a serialized `XSpace` holds: XSpace.planes (1) ->
    XPlane name (2), event_metadata (4), stat_metadata (5)."""
    out = {}
    for f, plane in _fields(memoryview(xspace)):
        if f != 1:
            continue
        p = _message(plane)
        if not p[2] or _text(p[2][0]) != METADATA_PLANE:
            continue
        stat_names = {}
        for entry in p[5]:                   # map<int64, XStatMetadata>
            e = _message(entry)
            md = _message(e[2][0]) if e[2] else {}
            stat_names[e[1][0] if e[1] else 0] = _text(md[2][0]) \
                if md and md[2] else ""
        for entry in p[4]:                   # map<int64, XEventMetadata>
            e = _message(entry)
            md = _message(e[2][0]) if e[2] else None
            if md is None:
                continue
            for stat in md[5]:               # XStat: metadata_id 1, bytes 6
                st = _message(stat)
                if st[1] and st[6] and \
                        stat_names.get(st[1][0]) == HLO_PROTO_STAT:
                    out[_text(md[2][0]) if md[2] else str(len(out))] = \
                        _instruction_paths(st[6][0])
    return out


def step_paths(programs: Dict[str, Dict[str, str]]) -> Dict[str, str]:
    """The paths of the programs that carry the `train.forward` scope: the
    step's, whichever other programs ran in the trace."""
    out: Dict[str, str] = {}
    for paths in programs.values():
        if any(FORWARD in p for p in paths.values()):
            out.update(paths)
    return out


def phase(path: str) -> str:
    if "train.adamw" in path:
        return "optimizer"
    if "rematted_computation" in path:
        return "remat"
    if "transpose(" in path:
        return "backward"
    if FORWARD in path:
        return "forward"
    return "unscoped"


@dataclasses.dataclass
class Split:
    steps: int                           # bench.step spans in the segment
    seconds: Dict[int, Dict[str, float]]  # device -> class -> s
    ssd_s: Dict[int, float]              # device -> s under ssm.ssd
    unscoped_ops: Dict[str, float]       # op name -> s, mean over devices

    def per_step_ms(self, cls: str) -> float:
        """A class's device time per step, on the device with the most."""
        return 1e3 * max(s[cls] for s in self.seconds.values()) / self.steps


def _window(tr: trace.Trace):
    steps = [s for s in tr.spans if s.name == trace.STEP_SPAN]
    return len(steps), steps[0].start, max(s.end for s in steps)


def split(tr: trace.Trace, paths: Dict[str, str]) -> Optional[Split]:
    """Device seconds of each class over the traced steps; None when the
    program names no phases."""
    if not any(FORWARD in p for p in paths.values()):
        return None
    n_steps, lo, hi = _window(tr)
    seconds: Dict[int, Dict[str, float]] = {}
    ssd: Dict[int, float] = {}
    unscoped: Dict[str, float] = defaultdict(float)
    for dev, ops in tr.ops.items():
        sec = dict.fromkeys(CLASSES, 0.0)
        ssd[dev] = 0.0
        for o in ops:
            if o.op in trace.CONTAINERS or not trace.inside(o, lo, hi):
                continue
            path = paths.get(o.name, "")
            s = (o.end - o.start) * 1e-9
            cls = phase(path)
            sec[cls] += s
            if cls == "unscoped":
                unscoped[o.name] += s / len(tr.ops)
            if "ssm.ssd" in path:
                ssd[dev] += s
        seconds[dev] = sec
    return Split(steps=n_steps, seconds=seconds, ssd_s=ssd,
                 unscoped_ops=dict(unscoped))


def program_spans(planes) -> List[trace.Interval]:
    """The program's host spans in a trace's planes, by start."""
    out = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend(trace.Interval(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                       for e in line.events if e.name in PROGRAM_SPANS)
    return sorted(out, key=lambda i: i.start)


def idle_by_span(tr: trace.Trace, spans: List[trace.Interval]
                 ) -> Dict[str, float]:
    """Idle device seconds in the traced steps by the innermost host span,
    the program's and the benchmark's, mean over devices."""
    spans = sorted(tr.spans + spans, key=lambda i: i.start)
    _, lo, hi = _window(tr)
    starts = [sp.start for sp in spans]
    bounds = sorted({t for sp in spans for t in (sp.start, sp.end)})
    idle: Dict[str, float] = defaultdict(float)
    for ops in tr.ops.values():
        for gap in trace.gaps(trace.union(ops, lo, hi), lo, hi):
            for name, ns in trace.attribute(gap, spans, starts, bounds):
                idle[name] += ns * 1e-9 / len(tr.ops)
    return dict(idle)


def profile_start_ns(planes) -> Optional[int]:
    """When the profiler started, in `time.time_ns` nanoseconds."""
    for plane in planes:
        if plane.name == "Task Environment":
            start = dict(plane.stats).get("profile_start_time")
            return int(start) if start is not None else None
    return None


@dataclasses.dataclass
class Reading:
    """What the readers take from one traced run's trace."""
    split: Optional[Split]
    idle: Dict[str, float]               # host span -> idle s, as idle_by_span
    start_ns: Optional[int]              # the profiler's start (time.time_ns)


def read(xspace: bytes) -> Tuple[trace.Trace, Reading]:
    """The trace of a serialized `XSpace`, and its reading."""
    import jax
    planes = list(jax.profiler.ProfileData.from_serialized_xspace(
        xspace).planes)
    tr = trace.from_planes(planes)
    return tr, Reading(split=split(tr, step_paths(program_paths(xspace))),
                       idle=idle_by_span(tr, program_spans(planes)),
                       start_ns=profile_start_ns(planes))


_READ: Dict[Tuple[str, float], Optional[Reading]] = {}


def of_run(summary: Optional[trace.Summary]) -> Optional[Reading]:
    """The reading of the traced run that `summary` reduces: the newest
    trace bench/run.py wrote (it writes each traced run's anew), if
    `trace.summarize` reduces it to `summary`.  Read once for all readers,
    and logged to standard error then."""
    from bench import run
    if summary is None:
        return None
    found = glob.glob(str(run.OUT / "trace" / "**" / "*.xplane.pb"),
                      recursive=True)
    if not found:
        return None
    path = max(found, key=os.path.getmtime)
    key = (path, os.path.getmtime(path))
    if key not in _READ:
        with open(path, "rb") as f:
            tr, got = read(f.read())
        if trace.summarize(tr) != summary:   # another run's trace
            got = None
        else:
            for line in report(got.split, got.idle, summary):
                print(line, file=sys.stderr, flush=True)
        _READ[key] = got
    return _READ[key]


def report(sp: Optional[Split], idle: Dict[str, float],
           summary: trace.Summary, top: int = 5) -> List[str]:
    """The two stderr lines of a traced run: the class split per step on
    the busiest device, with the four phases' share of its busy time and
    the largest unscoped operations; idle time per step by host span."""
    n = summary.steps
    lines = ["scopes: the program names no phases"]
    if sp is not None:
        dev = max(summary.busy_s, key=summary.busy_s.get)
        sec = sp.seconds[dev]
        phases = sum(sec[c] for c in CLASSES if c != "unscoped")
        ops = sorted(sp.unscoped_ops.items(), key=lambda kv: -kv[1])[:top]
        lines[0] = (
            f"scopes (ms a step, device {dev}): "
            + ", ".join(f"{c} {1e3 * sec[c] / n:.3f}" for c in CLASSES)
            + f", ssd {1e3 * sp.ssd_s[dev] / n:.3f}; phases "
            f"{100 * phases / summary.busy_s[dev]:.2f}% of busy; largest "
            f"unscoped {[(k, round(1e3 * v / n, 4)) for k, v in ops]}")
    rank = sorted(idle.items(), key=lambda kv: -kv[1])
    lines.append("idle by host span (ms a step): "
                 + str([(k, round(1e3 * v / n, 4)) for k, v in rank]))
    return lines
