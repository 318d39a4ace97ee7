"""CPU rehearsal of each cell through the harness's own functions, at
reduced widths (tiny.py) on four virtual host devices: set-up, window,
traced segment, reference and comparison, and the result line's shape."""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from bench import run
from bench.tests.tiny import CPU_PEAK, tiny_cell

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_runs_and_is_correct(name, traced):
    cell = tiny_cell(name)
    r = run.run_cell(cell, seed=2 ** 33 + 12345, seconds=0.5, trace=traced,
                     devices=jax.devices()[:cell.chips], peak=CPU_PEAK)
    assert r["correct"], r["check"]
    assert list(r)[-1] == "check" and r["failed"] == 0
    assert r["attempted"] >= cell.traffic["checked_steps"] + 1
    assert r["device"]["count"] == cell.chips
    want = set(cell.per_layer if traced else cell.end_to_end)
    # the CPU reports no memory peak, so peak_hbm_gib is left out here
    assert set(r["metrics"]) == want - {"peak_hbm_gib"}
    if traced:
        assert 0 < r["device"]["busy_s"] <= r["device"]["window_s"]
        assert r["breakdown"]["device_ops"]
        if cell.traffic["collectives"] == "pipeline":
            assert r["metrics"]["ppermute_calls_per_step"]["value"] > 0
    json.dumps(r, allow_nan=False)


def test_same_seed_same_readings():
    cell = tiny_cell(CELLS[0])
    runs = [run.run_cell(cell, seed=7, seconds=0.2, trace=False,
                         devices=jax.devices()[:1], peak=CPU_PEAK)
            for _ in range(2)]
    assert runs[0]["check"] == runs[1]["check"]


def _bench(args, cwd, env_extra):
    env = dict(os.environ, **env_extra)
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


ARGS = ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
        "--trace", "0"]


def test_refuses_without_a_tpu():
    p = _bench(ARGS, ROOT, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 2 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_an_unknown_device_kind():
    from bench.flops import peaks
    with pytest.raises(KeyError, match="not in"):
        peaks("cpu")
    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    import shutil
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _bench(ARGS, tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0 and p.stdout == ""
