"""Process set-up shared by the launch drivers and `chip_smoke.py`.

* `use_compile_cache` — where JAX's persistent compilation cache lives.
  A `JAX_COMPILATION_CACHE_DIR` set from outside wins (JAX reads it
  itself); otherwise the cache is the fixed `.jax_cache/` at the checkout
  root.  The directory is part of the cache key, so it is never built from
  a temp dir, a pid or the time.
* `force_host_devices` — the CPU-only test mode behind ``--host-devices N``:
  re-exec the driver on N forced CPU host devices.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path
from typing import List

#: checkout root: src/repro/launch/runtime.py -> three directories up
CHECKOUT = Path(__file__).resolve().parents[3]

_HOST_COUNT_FLAG = "--xla_force_host_platform_device_count"


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def force_host_devices(n: int, module: str, argv: List[str]) -> None:
    """Re-exec ``python -m module argv`` on the CPU with `n` host devices.

    Sets ``JAX_PLATFORMS=cpu`` and appends the device-count flag to any
    existing ``XLA_FLAGS``.  Returns without re-exec when this process
    already runs in that mode (the re-exec'd child)."""
    flag = f"{_HOST_COUNT_FLAG}={n}"
    flags = os.environ.get("XLA_FLAGS", "").split()
    if os.environ.get("JAX_PLATFORMS") == "cpu" and flag in flags:
        return
    flags = [f for f in flags if not f.startswith(_HOST_COUNT_FLAG + "=")]
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = " ".join(flags + [flag])
    os.execv(sys.executable, [sys.executable, "-m", module] + list(argv))
