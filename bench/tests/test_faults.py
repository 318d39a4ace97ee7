"""`correct` comes out false when the timed path is broken underneath
(bench/faults.py), for each fault a cell can have, at reduced widths on
the CPU; the harness's look for a chip is skipped, the rest of a run is
driven as it stands."""
import json
from pathlib import Path

import jax
import pytest

from bench import faults, run
from bench.tests.tiny import CPU_PEAK, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
CASES = [(c, f) for c in CELLS for f in faults.FAULTS
         if faults.applies(f, tiny_cell(c).traffic)]


@pytest.mark.parametrize("name,fault", CASES)
def test_fault_is_not_correct(name, fault):
    cell = tiny_cell(name)
    with faults.FAULTS[fault]():
        r = run.run_cell(cell, seed=2 ** 32 + 99, seconds=0.2, trace=False,
                         devices=jax.devices()[:cell.chips], peak=CPU_PEAK)
    assert not r["correct"], r["check"]


def test_unchanged_state_reads_one_on_update_gap():
    cell = tiny_cell(CELLS[0])
    with faults.unchanged_state():
        r = run.run_cell(cell, seed=5, seconds=0.2, trace=False,
                         devices=jax.devices()[:1], peak=CPU_PEAK)
    assert r["check"]["update_gap"]["value"] == pytest.approx(1.0)
