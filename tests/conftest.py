"""Shared fixtures.  NOTE: no global XLA_FLAGS here — smoke tests must see
one device; multi-device collective tests spawn subprocesses that set
--xla_force_host_platform_device_count themselves."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)

import numpy as np
import pytest


def run_snippet(code: str) -> str:
    """Run `code` in a fresh interpreter that sees 8 host devices; return
    its stdout (the run must succeed)."""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, f"stderr:\n{out.stderr}\nstdout:\n{out.stdout}"
    return out.stdout


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
