"""The split of device time by the program's scopes (bench/scopes.py) and
its readers, on a hand-made trace and on a small trace recorded on the CPU
(bench/testdata, made by `record_scoped_trace.py`: the dp1 step at tiny
widths, 3 traced steps, with the step's optimized HLO in the trace)."""
import time
from pathlib import Path

import pytest

from bench import run, scopes, trace
from bench.run import Readings, read_metric

DATA = Path(__file__).resolve().parents[1] / "testdata"
RECORDED = DATA / "cpu1_mamba2_step.xplane.pb"
PHASES = ("forward", "remat", "backward", "optimizer")
READERS = [f"{c}_ms_per_step" for c in PHASES] + ["ssd_ms_per_step",
                                                  "setup_compile_s"]


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields) -> bytes:
    """A protobuf message of (field number, int | str | bytes) pairs."""
    out = b""
    for f, v in fields:
        if isinstance(v, int):
            out += _varint(f << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(f << 3 | 2) + _varint(len(v)) + v
    return out


def test_program_paths_from_a_hand_made_trace():
    instr = [_msg((1, "fusion.3"), (2, "fusion"),
                  (7, _msg((1, "mul"), (2, "jit(step)/train.adamw/mul")))),
             _msg((1, "copy.1"), (2, "copy")),
             _msg((1, "add.2"), (2, "add"), (7, _msg((4, 12))))]
    hlo = _msg((1, _msg((1, "jit_step"), (3, _msg(
        (1, "main"), *[(2, i) for i in instr])))))
    stat_md = [_msg((1, 7), (2, _msg((1, 7), (2, "Hlo Proto")))),
               _msg((1, 8), (2, _msg((1, 8), (2, "other"))))]
    event_md = _msg((1, 1), (2, _msg((1, 1), (2, "jit_step(1)"),
                                     (5, _msg((1, 8), (6, b"x"))),
                                     (5, _msg((1, 7), (6, hlo))))))
    xspace = _msg((1, _msg((1, 2), (2, "/host:CPU"))),
                  (1, _msg((1, 1), (2, "/host:metadata"), (4, event_md),
                           *[(5, m) for m in stat_md])),
                  (3, "host0"))
    assert scopes.program_paths(xspace) == {
        "jit_step(1)": {"fusion.3": "jit(step)/train.adamw/mul"}}
    # only a program with the forward scope names the step's operations
    assert scopes.step_paths(scopes.program_paths(xspace)) == {}


@pytest.mark.parametrize("path, cls", [
    ("jit(step)/train.adamw/transpose(x)/mul", "optimizer"),
    ("jit(step)/transpose(jvp(train.forward))/while/body/checkpoint/"
     "rematted_computation/ssm.ssd/dot", "remat"),
    ("jit(step)/transpose(jvp(train.forward))/while/body/ssm.ssd/dot",
     "backward"),
    ("jit(step)/jvp(train.forward)/while/body/ssm.ssd/exp", "forward"),
    ("jit(step)/ssm.ssd/jit(tril)/iota", "unscoped"),
    ("", "unscoped"),
])
def test_phase_rules_apply_in_order(path, cls):
    assert scopes.phase(path) == cls


@pytest.fixture(scope="module")
def recorded():
    import jax
    raw = RECORDED.read_bytes()
    planes = list(jax.profiler.ProfileData.from_serialized_xspace(raw).planes)
    tr = trace.from_planes(planes)
    paths = scopes.step_paths(scopes.program_paths(raw))
    return tr, paths, scopes.split(tr, paths), planes


@pytest.fixture
def traced_run(monkeypatch, tmp_path):
    """The recorded trace where bench/run.py leaves a traced run's."""
    monkeypatch.setattr(run, "OUT", tmp_path)
    where = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(RECORDED.read_bytes())
    return where / "host.xplane.pb"


def test_the_trace_holds_the_steps_paths(recorded):
    tr, paths, _, _ = recorded
    ops = {o.name for v in tr.ops.values() for o in v}
    assert any(scopes.FORWARD in p for p in paths.values())
    assert any("train.adamw" in p for p in paths.values())
    # most of the traced operations are named; the rest carry no metadata
    assert len(ops & set(paths)) > len(ops) / 2


def test_classes_cover_every_non_container_op(recorded):
    tr, paths, sp, _ = recorded
    n, lo, hi = scopes._window(tr)
    assert sp.steps == n == 3
    for dev, ops in tr.ops.items():
        mine = [o for o in ops if o.op not in trace.CONTAINERS
                and trace.inside(o, lo, hi)]
        assert sum(sp.seconds[dev].values()) == pytest.approx(
            sum(o.end - o.start for o in mine) * 1e-9)
        # every phase of the step ran, and most of it is named
        assert all(sp.seconds[dev][c] > 0 for c in PHASES)
        assert any(o.name in paths for o in mine)


def test_ssd_is_a_part_of_the_other_classes(recorded):
    tr, paths, sp, _ = recorded
    for dev, ops in tr.ops.items():
        ssd_ops = [o for o in ops if "ssm.ssd" in paths.get(o.name, "")
                   and o.op not in trace.CONTAINERS]
        assert ssd_ops and {scopes.phase(paths[o.name]) for o in ssd_ops} \
            <= {"forward", "remat", "backward", "unscoped"}
        assert 0 < sp.ssd_s[dev] <= sum(sp.seconds[dev].values())


def test_a_program_without_scopes_gives_no_split(recorded):
    tr, paths, _, _ = recorded
    bare = {k: v.replace("train.forward", "f").replace("train.adamw", "a")
            for k, v in paths.items()}
    assert scopes.split(tr, bare) is None


def test_readers_on_the_recorded_trace(recorded, traced_run):
    tr, _, sp, _ = recorded
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=trace.summarize(tr))
    for c in PHASES:
        assert read_metric(f"{c}_ms_per_step", r) == pytest.approx(
            1e3 * sp.seconds[0][c] / 3)
    assert read_metric("ssd_ms_per_step", r) == pytest.approx(
        1e3 * sp.ssd_s[0] / 3)
    # recorded by another process: nothing here compiled before it began
    assert read_metric("setup_compile_s", r) == 0.0


def test_setup_compile_s_counts_what_compiled_before_the_trace(
        recorded, traced_run, monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro import obs
    jax.jit(lambda x: x * 2.0 + 7.0)(jnp.ones(13)).block_until_ready()
    start = time.time_ns()
    monkeypatch.setattr(scopes, "profile_start_ns", lambda planes: start)
    jax.jit(lambda x: x * 3.0 - 7.0)(jnp.ones(13)).block_until_ready()
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=trace.summarize(recorded[0]))
    got = read_metric("setup_compile_s", r)
    assert got > 0
    assert got == obs.compile_seconds(obs.compile_totals(before_ns=start))
    assert got < obs.compile_seconds(obs.compile_totals())


def test_readers_find_nothing_without_a_trace(tmp_path, monkeypatch):
    monkeypatch.setattr(run, "OUT", tmp_path)
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=None)
    for name in READERS:
        assert read_metric(name, r) is None
    # a summary, but no trace where a traced run leaves it
    r.summary = trace.summarize(trace.load(str(DATA / "cpu4_ppermute"
                                               ".xplane.pb")))
    for name in READERS:
        assert read_metric(name, r) is None


def test_readers_leave_another_runs_trace_alone(traced_run):
    # the newest trace is not the one the run reduced: no reading
    other = trace.summarize(trace.load(str(DATA / "cpu4_ppermute.xplane.pb")))
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=other)
    for name in READERS:
        assert read_metric(name, r) is None


def test_idle_time_split_by_the_programs_host_spans(recorded):
    tr, _, sp, planes = recorded
    spans = scopes.program_spans(planes)
    assert [s.name for s in spans].count("data.batch") >= 3
    idle = scopes.idle_by_span(tr, spans)
    summary = trace.summarize(tr)
    # the same idle time as the benchmark's split, more finely named
    assert sum(idle.values()) == pytest.approx(
        sum(summary.idle_by_span.values()))
    assert idle.get("data.batch", 0) > 0
    lines = scopes.report(sp, idle, summary)
    assert len(lines) == 2 and lines[0].startswith("scopes (ms a step")
    assert "data.batch" in lines[1]
    assert scopes.report(None, idle, summary)[0] == \
        "scopes: the program names no phases"
