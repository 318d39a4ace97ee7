"""The program's own tracing: every scope and span name, and the compile
counter.

* Device scopes are `jax.named_scope`s.  They live in each HLO
  operation's metadata (`op_name`), so they change no program: a profiler
  trace of the device, read against the compiled step's metadata, splits
  device time by scope.  Under `jax.value_and_grad` the backward ops of a
  scope carry ``transpose(jvp(<scope>))`` and the ops a `jax.checkpoint`
  recomputes carry ``rematted_computation``, so one forward scope names
  three phases.
* Host spans are `jax.profiler.TraceAnnotation`s (and the per-step
  `StepTraceAnnotation`).  They write into the profiler's own trace, on the
  device ops' clock, and cost next to nothing when no trace is being
  taken.
* The compile counter listens to JAX's own compile events
  (`jax.monitoring`), from import on, and `compile_totals` reads it, in
  all or up to a moment on the wall clock (`time.time_ns`, the clock of a
  profiler trace's start time).
* The SSD path counter counts, when a program is traced, each SSD scan by
  the path it took (`count_ssd`: the Pallas kernels or XLA);
  `ssd_paths` reads it.
* The shared-block counter counts, when a program is traced, each
  application of the hybrid family's shared transformer block, by site
  (`count_shared`); `shared_sites` reads it.

README.md ("Tracing a training run") says what each name covers.
"""
from __future__ import annotations

import threading
import time
from typing import Dict, NamedTuple, Optional

import jax

# device scopes (jax.named_scope)
FORWARD = "train.forward"
GRAD_REDUCE = "train.grad_reduce"
ADAMW = "train.adamw"
SSD = "ssm.ssd"
SSD_KERNEL = "ssm.ssd.kernel"
SHARED = "hybrid.shared"
SHARED_ATTN = "hybrid.shared.attn"
COMMS_GATHER = "comms.gather"
COMMS_PERMUTE = "comms.permute"
COMMS_SCATTER = "comms.scatter"
COMMS_STAGE = "comms.stage"
COMMS_BUCKET = "comms.bucket"

# host spans (jax.profiler.TraceAnnotation / StepTraceAnnotation)
STEP = "train"
DISPATCH = "train.dispatch"
WAIT = "train.wait"
CHECKPOINT = "train.checkpoint"
REPAIR = "train.repair"
DATA_BATCH = "data.batch"
SCHEDULE = "comms.schedule"

#: JAX's compile events, by phase.  A persistent-cache read happens inside
#: the backend compile, so its seconds are also part of "compile".
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}


class Totals(NamedTuple):
    count: int
    seconds: float


def _record(event: str, duration: float, **_) -> None:
    phase = COMPILE_EVENTS.get(event)
    if phase is not None:
        with _LOCK:
            n, s = _TOTALS[phase]
            _TOTALS[phase] = Totals(n + 1, s + duration)
            _EVENTS.append((time.time_ns(), phase, duration))


def compile_totals(before_ns: Optional[int] = None) -> Dict[str, Totals]:
    """Count and seconds of each compile phase since this module was first
    imported, of the events that ended before `before_ns` (`time.time_ns`)
    if it is given: {"trace", "lower", "compile", "cache_read"} -> Totals."""
    with _LOCK:
        if before_ns is None:
            return dict(_TOTALS)
        events = list(_EVENTS)
    count = dict.fromkeys(_TOTALS, 0)
    seconds = dict.fromkeys(_TOTALS, 0.0)
    for end_ns, phase, s in events:
        if end_ns < before_ns:
            count[phase] += 1
            seconds[phase] += s
    return {p: Totals(count[p], seconds[p]) for p in count}


def count_ssd(path: str) -> None:
    """Count one SSD scan traced on `path` ("kernel" or "xla")."""
    with _LOCK:
        _SSD_PATHS[path] = _SSD_PATHS.get(path, 0) + 1


def ssd_paths() -> Dict[str, int]:
    """SSD scans traced since this module was first imported, by path."""
    with _LOCK:
        return dict(_SSD_PATHS)


def count_shared(site: int) -> None:
    """Count one application of the shared block traced at `site`."""
    with _LOCK:
        _SHARED_SITES[site] = _SHARED_SITES.get(site, 0) + 1


def shared_sites() -> Dict[int, int]:
    """Shared-block applications traced since this module was first
    imported, by site."""
    with _LOCK:
        return dict(_SHARED_SITES)


def compile_seconds(totals: Dict[str, Totals]) -> float:
    """Wall seconds spent compiling: tracing, lowering and the backend
    compile (which holds any persistent-cache read)."""
    return sum(totals[p].seconds for p in ("trace", "lower", "compile"))


# Registered once per process: a reload of this module keeps the listener
# and the events it has counted.  One entry per compile event: a process
# compiles a bounded set of programs.
if "_EVENTS" not in globals():
    _LOCK = threading.Lock()
    _TOTALS = {p: Totals(0, 0.0) for p in COMPILE_EVENTS.values()}
    _EVENTS: list = []       # (time.time_ns() at its end, phase, seconds)
    _SSD_PATHS: Dict[str, int] = {}
    _SHARED_SITES: Dict[int, int] = {}
    jax.monitoring.register_event_duration_secs_listener(_record)
