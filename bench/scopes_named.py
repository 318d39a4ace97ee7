"""Device time under the program's own named scopes in a traced run, read
beside bench/scopes.py's phase split and built from its public functions.

A device operation of the traced steps (control flow left out, its body
counted instead, as in `trace.summarize`) counts under a scope when the
`op_name` path that the step's HLO gives it (`scopes.program_paths`,
`scopes.step_paths`) holds the scope's name, in forward, recompute and
backward alike.  `of_run` finds the traced run's trace by the rule of
`scopes.of_run` (the newest trace bench/run.py wrote, if it reduces to the
run's summary), reads it once for all readers, and logs the times to
standard error.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import sys
from typing import Dict, Optional, Sequence, Tuple

from bench import scopes, trace

#: the hybrid family's shared block, and inside it the attention's
#: scores, mask, softmax and PV (the names `repro.obs` gives them)
SHARED = "hybrid.shared"
SHARED_ATTN = "hybrid.shared.attn"
NAMES = (SHARED, SHARED_ATTN)


@dataclasses.dataclass
class Named:
    steps: int                             # bench.step spans in the segment
    seconds: Dict[str, Dict[int, float]]   # scope -> device -> s

    def per_step_ms(self, scope: str) -> float:
        """A scope's device time per step, on the device with the most."""
        return 1e3 * max(self.seconds[scope].values()) / self.steps


def named(tr: trace.Trace, paths: Dict[str, str],
          names: Sequence[str] = NAMES) -> Optional[Named]:
    """Device seconds under each scope of `names` over the traced steps;
    None when the program names no phases (no step to read)."""
    if not any(scopes.FORWARD in p for p in paths.values()):
        return None
    steps = [s for s in tr.spans if s.name == trace.STEP_SPAN]
    lo, hi = steps[0].start, max(s.end for s in steps)
    seconds = {n: {} for n in names}
    for dev, ops in tr.ops.items():
        for n in names:
            seconds[n][dev] = 0.0
        for o in ops:
            if o.op in trace.CONTAINERS or not trace.inside(o, lo, hi):
                continue
            path = paths.get(o.name, "")
            for n in names:
                if n in path:
                    seconds[n][dev] += (o.end - o.start) * 1e-9
    return Named(steps=len(steps), seconds=seconds)


def read(xspace: bytes) -> Tuple[trace.Trace, Optional[Named]]:
    """The trace of a serialized `XSpace`, and its times by scope."""
    import jax
    planes = list(jax.profiler.ProfileData.from_serialized_xspace(
        xspace).planes)
    tr = trace.from_planes(planes)
    return tr, named(tr, scopes.step_paths(scopes.program_paths(xspace)))


_READ: Dict[Tuple[str, float], Optional[Named]] = {}


def of_run(summary: Optional[trace.Summary]) -> Optional[Named]:
    """The times by scope of the traced run that `summary` reduces, or
    None where there is no such trace."""
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
        elif got is not None:
            dev = max(summary.busy_s, key=summary.busy_s.get)
            print(f"named scopes (ms a step, device {dev}): "
                  + ", ".join(f"{n} {1e3 * got.seconds[n][dev] / got.steps:.3f}"
                              for n in NAMES)
                  + f"; busy {1e3 * summary.busy_s[dev] / got.steps:.3f}",
                  file=sys.stderr, flush=True)
        _READ[key] = got
    return _READ[key]


def shared_sites() -> Optional[int]:
    """Shared-block applications the program traced in this process, by
    its counter; None for a program without the counter."""
    try:
        from repro import obs
        return sum(obs.shared_sites().values())
    except (ImportError, AttributeError):
        return None


def shared_ms(summary: Optional[trace.Summary], scope: str
              ) -> Optional[float]:
    """A shared-block scope's device time per traced step.  No time under
    it is a measured 0 where the counter says the program applied no
    shared block; where it applied one, or has no counter to say, no time
    means the scope cannot be read (None), as does a trace that cannot."""
    got = of_run(summary)
    if got is None:
        return None
    ms = got.per_step_ms(scope)
    if ms > 0:
        return ms
    return 0.0 if shared_sites() == 0 else None
