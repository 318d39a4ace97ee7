"""The trace reduction (bench/trace.py) and the per-layer readers, on
synthetic intervals and on a small trace recorded on the CPU
(bench/testdata, made by record_trace.py: 4 devices, 3 steps, each a
20 ms host pause in bench.batch and one program with 2 ppermutes)."""
from pathlib import Path

import pytest

from bench import trace
from bench.run import Readings, read_metric

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "cpu4_ppermute.xplane.pb"
I = trace.Interval


def test_union_merges_overlaps_and_clips_to_window():
    ops = [I("a", 0, 10), I("b", 5, 20), I("c", 30, 40), I("d", 38, 60),
           I("e", 70, 80)]
    assert trace.union(ops, 2, 75) == [(2, 20), (30, 60), (70, 75)]
    assert trace.gaps(trace.union(ops, 2, 75), 2, 75) == [(20, 30),
                                                          (60, 70)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


def test_innermost_span_names_what_the_host_was_doing():
    spans = [I("bench.step", 0, 100), I("bench.batch", 0, 40),
             I("bench.sync", 60, 100)]
    assert trace.innermost_span(spans, 10) == "bench.batch"
    assert trace.innermost_span(spans, 50) == "bench.step"
    assert trace.innermost_span(spans, 150) == "none"


def test_hlo_names_from_tpu_event_text():
    assert trace.hlo_name(
        "%fusion.714 = f32[48]{0:T(128)S(1)} fusion(f32[4,1,48]{2,1,0} "
        "%get-tuple-element.1993), kind=kLoop") == ("fusion.714", "fusion")
    assert trace.hlo_name(
        "%collective-permute-start.3 = (f32[8]{0:T(128)}, f32[8]{0}, "
        "u32[]{:S(2)}) collective-permute-start(f32[8]{0} %p), "
        "source_target_pairs={{0,1}}") == ("collective-permute-start.3",
                                           "collective-permute-start")
    assert trace.hlo_name(
        "%while.147 = (s32[]{:T(128)}, bf16[2]{0}) while((s32[], bf16[2]) "
        "%tuple.368), condition=%c") == ("while.147", "while")
    assert trace.hlo_name("ppermute.3") == ("ppermute.3", "ppermute")


def test_summary_busy_is_the_union_and_steps_split_per_step():
    cp = "collective-permute"
    tr = trace.Trace(
        ops={0: [I("fusion.1", 10, 30, "fusion"),
                 I(f"{cp}-start.2", 20, 40, f"{cp}-start"),
                 I(f"{cp}-done.2", 40, 45, f"{cp}-done"),
                 I("fusion.1", 110, 150, "fusion"),
                 I("while.3", 110, 150, "while")],
             1: [I("fusion.1", 10, 90, "fusion"),
                 I("fusion.1", 100, 190, "fusion")]},
        spans=[I("bench.step", 0, 100), I("bench.batch", 0, 10),
               I("bench.step", 100, 200), I("bench.batch", 100, 110)])
    s = trace.summarize(tr)
    assert s.steps == 2 and s.window_s == pytest.approx(200e-9)
    assert s.busy_s[0] == pytest.approx(75e-9)     # 10-45 and 110-150
    assert s.busy_s[1] == pytest.approx(170e-9)
    assert s.permute_calls == {0: 1, 1: 0}         # "-done" not counted
    assert s.permute_s[0] == pytest.approx(25e-9)
    # device 0 idles 0-10 and 100-110 in bench.batch, 45-100 and 150-200
    # in bench.step; device 1 0-10 (batch), 90-100 and 190-200 (step)
    assert s.idle_by_span["bench.batch"] == pytest.approx(15e-9)
    assert s.idle_by_span["bench.step"] == pytest.approx(62.5e-9)
    assert s.op_s["fusion.1"] == pytest.approx((60 + 170) / 2 * 1e-9)
    assert "while.3" not in s.op_s           # its body is listed instead


@pytest.fixture(scope="module")
def recorded():
    return trace.summarize(trace.load(str(RECORDED)))


def test_recorded_trace_steps_devices_and_permutes(recorded):
    assert recorded.steps == 3
    assert sorted(recorded.busy_s) == [0, 1, 2, 3]
    # 2 ppermutes in the program, once per step, on every device
    assert recorded.permute_calls == {d: 6 for d in range(4)}
    assert all(t > 0 for t in recorded.permute_s.values())
    assert all(0 < b < recorded.window_s for b in recorded.busy_s.values())


def test_recorded_trace_idle_time_sits_in_the_host_pause(recorded):
    # 3 steps x 20 ms in bench.batch, when no program runs
    assert 0.06 <= recorded.idle_by_span["bench.batch"] < recorded.window_s
    assert max(recorded.idle_by_span, key=recorded.idle_by_span.get) == \
        "bench.batch"
    bd = trace.breakdown(recorded)
    assert bd["idle_gaps"][0][0] == "bench.batch"
    assert len(bd["device_ops"]) <= 10 and bd["device_ops"][0][1] > 0


def test_readers_on_the_recorded_trace(recorded):
    r = Readings(chips=4, n_params=1000, peak={"bf16_flops_per_s": 1e12},
                 tokens_per_s=5e6, summary=recorded)
    idle = read_metric("device_idle_share", r)
    assert idle == pytest.approx(
        100 * (1 - recorded.busy_mean_s / recorded.window_s))
    assert 90 < idle < 100                 # the 20 ms pauses dominate
    assert read_metric("ppermute_calls_per_step", r) == 2
    assert read_metric("ppermute_ms_per_step", r) == pytest.approx(
        1e3 * max(recorded.permute_s.values()) / 3)
    assert read_metric("step_mfu", r) == pytest.approx(
        100 * 6 * 1000 * 5e6 / (4 * 1e12))


def test_readers_find_nothing_without_a_trace_or_permutes(recorded):
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=None)
    for name in ("step_mfu", "device_idle_share", "ppermute_ms_per_step",
                 "ppermute_calls_per_step"):
        assert read_metric(name, r) is None
    recorded_no_permutes = trace.Summary(
        steps=1, window_s=1.0, busy_s={0: 0.5}, permute_s={0: 0.0},
        permute_calls={0: 0}, op_s={}, idle_by_span={})
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=recorded_no_permutes)
    assert read_metric("ppermute_calls_per_step", r) is None
    assert read_metric("device_idle_share", r) == pytest.approx(50.0)
