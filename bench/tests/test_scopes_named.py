"""Device time under the shared block's scopes (bench/scopes_named.py) and
its readers, on a small trace recorded on the CPU (bench/testdata, made by
`record_hybrid_trace.py`: the zamba2-1.2b.dp1 step at tiny widths, two
periods deep, 3 traced steps), and the shared-block counter the readers
consult."""
from pathlib import Path

import jax
import pytest

from bench import run, scopes, scopes_named, trace
from bench.run import Readings, read_metric
from bench.tests.tiny import CPU_PEAK, TINY_MODEL, tiny_cell

DATA = Path(__file__).resolve().parents[1] / "testdata"
RECORDED = DATA / "cpu1_zamba2_step.xplane.pb"
READERS = ("shared_block_ms_per_step", "shared_attn_core_ms_per_step")
CELL = "zamba2-1.2b.dp1"


@pytest.fixture
def fresh_counter(monkeypatch):
    """The program's shared-block counter, empty for this test."""
    from repro import obs
    monkeypatch.setattr(obs, "_SHARED_SITES", {})
    return obs


@pytest.fixture
def traced_run(monkeypatch, tmp_path):
    """The recorded trace where bench/run.py leaves a traced run's, and its
    summary."""
    monkeypatch.setattr(run, "OUT", tmp_path)
    where = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(RECORDED.read_bytes())
    return trace.summarize(trace.load(str(where)))


def test_the_names_are_the_programs(fresh_counter):
    assert (scopes_named.SHARED, scopes_named.SHARED_ATTN) == (
        fresh_counter.SHARED, fresh_counter.SHARED_ATTN)


def test_readers_on_the_recorded_trace(traced_run):
    r = Readings(chips=1, n_params=1, peak={}, tokens_per_s=None,
                 summary=traced_run)
    block, attn = (read_metric(name, r) for name in READERS)
    _, got = scopes_named.read(RECORDED.read_bytes())
    assert block == pytest.approx(got.per_step_ms(scopes_named.SHARED))
    assert attn == pytest.approx(got.per_step_ms(scopes_named.SHARED_ATTN))
    # both positive, the core a part of the block, the two together no
    # more than the step's busy time
    busy_ms = 1e3 * max(traced_run.busy_s.values()) / traced_run.steps
    assert 0 < attn < block and block + attn <= busy_ms


def test_the_scopes_cover_every_pass():
    raw = RECORDED.read_bytes()
    paths = scopes.step_paths(scopes.program_paths(raw))
    block = [p for p in paths.values() if scopes_named.SHARED in p]
    attn = [p for p in block if scopes_named.SHARED_ATTN in p]
    for names in (block, attn):
        assert {scopes.phase(p) for p in names} >= {"forward", "remat",
                                                    "backward"}
    # the core is nested in the block's scope
    assert all(f"{scopes_named.SHARED}/" in p for p in attn)


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


def test_counter_reads_the_sites_the_config_implies(fresh_counter):
    from bench import program
    for layers, sites in ((13, 2), (8, 1), (2, 0)):
        fresh_counter._SHARED_SITES.clear()
        cell = tiny_cell(CELL, dict(TINY_MODEL, num_layers=layers))
        prog = program.build(cell.config, cell.traffic, jax.devices()[:1])
        p, o = jax.eval_shape(prog.init, program.seed_key_data(1))
        prog.step.lower(p, o, prog.batch(0))
        # the step is traced once: each site's block once
        assert fresh_counter.shared_sites() == {k: 1 for k in range(sites)}


def test_two_layer_cut_reads_a_measured_zero(fresh_counter):
    """tiny.py's two layers hold no hybrid layer: the traced run's shared
    block time is 0, not missing."""
    cell = tiny_cell(CELL)
    r = run.run_cell(cell, seed=2 ** 33 + 5, seconds=0.3, trace=True,
                     devices=jax.devices()[:1], peak=CPU_PEAK)
    assert fresh_counter.shared_sites() == {}
    for name in READERS:
        assert r["metrics"][name]["value"] == 0.0
    assert r["metrics"]["hybrid_step_mfu"]["value"] > 0
