"""The control of `correct`: the reference itself, computed with every
matrix product in fp8 (float8_e4m3fn operands, float8_e5m2 gradients, one
scale per tensor), put in the program's place, has to come out not
correct against each cell's limits.
On the chip it is read at the cells' own sizes by bench/calibrate.py; here
at reduced widths on the CPU (tiny.CONTROL_MODEL)."""
import json
from pathlib import Path

import jax
import pytest

from bench import check, program
from bench.tests.tiny import CONTROL_MODEL, CONTROL_TRAFFIC, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    cell = tiny_cell(name, CONTROL_MODEL, CONTROL_TRAFFIC)
    devices = jax.devices()[:cell.chips]
    seed = 2 ** 31 + 11
    kd = program.seed_key_data(seed)
    ctrl = check.reference_readings(cell.config, cell.traffic, seed, kd,
                                    devices, low=check.CONTROL,
                                    keep_grad=True)
    want = check.reference_readings(cell.config, cell.traffic, seed, kd,
                                    devices,
                                    other_grad=ctrl.pop("grad_leaves"))
    numbers = check.compare(ctrl, want)
    assert not check.verdict(numbers, cell.limits), numbers


def test_reference_against_itself_reads_zero():
    cell = tiny_cell(CELLS[0])
    kd = program.seed_key_data(3)
    a = check.reference_readings(cell.config, cell.traffic, 3, kd,
                                 jax.devices()[:1], keep_grad=True)
    b = check.reference_readings(cell.config, cell.traffic, 3, kd,
                                 jax.devices()[:1],
                                 other_grad=a.pop("grad_leaves"))
    assert check.compare(a, b) == {k: 0.0 for k in check.NUMBERS}


def test_gradient_error_split_adds_up():
    """The split of `grad_err` by leaf and layer (bench/check.py's
    leaf_gaps) sums back to the whole."""
    import math
    cell = tiny_cell(CELLS[0])
    kd = program.seed_key_data(17)
    ctrl = check.reference_readings(cell.config, cell.traffic, 17, kd,
                                    jax.devices()[:1], low=check.CONTROL,
                                    keep_grad=True)
    want = check.reference_readings(cell.config, cell.traffic, 17, kd,
                                    jax.devices()[:1],
                                    other_grad=ctrl.pop("grad_leaves"))
    split = check.leaf_gaps(cell.config, ctrl, want)
    shares = [share for _, share in split["grad_err"].values()]
    assert math.isclose(sum(shares), 1.0, rel_tol=1e-9)
    assert len(split["grad_err_by_layer"]) == cell.config["model"][
        "num_layers"]
    err, norm = want["grad_err_parts"]
    assert math.isclose(want["grad_err"], math.sqrt(
        sum(e.sum() for e in err) / sum(n.sum() for n in norm)))
    assert 0 < want["grad_err"] < 1
