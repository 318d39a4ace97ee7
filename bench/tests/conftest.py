"""The benchmark's own tests run on the CPU, by hand, from the checkout:

    PYTHONPATH=src python -m pytest -p no:cacheprovider bench/tests -q

Four virtual host devices stand in for the four-chip host; nothing here
loads the TPU library.  The variables are set before JAX is imported."""
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = " ".join(
    [f for f in os.environ.get("XLA_FLAGS", "").split()
     if not f.startswith("--xla_force_host_platform_device_count")]
    + ["--xla_force_host_platform_device_count=4"])
ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
