"""Checkpointing: atomic, sharded, resumable, optionally async.

Layout:
    <dir>/step_000123/
        manifest.json        # tree structure, shapes, dtypes, step
        arrays.npz           # flattened leaves (addressable data)
    <dir>/LATEST             # atomic pointer file

Writes go to a tmp dir + os.replace (atomic on POSIX), so a crash mid-save
never corrupts the latest checkpoint — the fault-tolerance loop relies on
this.  `save_async` runs the serialisation on a background thread with the
arrays already fetched to host (so the train loop only blocks for the
device->host copy).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

Params = Any


def _flatten_with_paths(tree: Params) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        out.append((key, leaf))
    return out


def save(ckpt_dir: str, step: int, tree: Params) -> str:
    """Synchronous atomic save; returns the checkpoint path."""
    leaves = _flatten_with_paths(tree)
    host = {k: np.asarray(v) for k, v in leaves}
    return _write(ckpt_dir, step, tree, host)


_pending: List[threading.Thread] = []


def save_async(ckpt_dir: str, step: int, tree: Params) -> threading.Thread:
    """Fetch to host synchronously, serialise on a background thread.
    Writes land in call order, so LATEST never moves back to an older step
    when an earlier write is still running."""
    leaves = _flatten_with_paths(tree)
    host = {k: np.asarray(v) for k, v in leaves}   # device->host blocks here
    prev = _pending[-1] if _pending else None

    def write():
        if prev is not None:
            prev.join()
        _write(ckpt_dir, step, tree, host)

    t = threading.Thread(target=write, daemon=True)
    t.start()
    _pending.append(t)
    return t


def wait_pending() -> None:
    while _pending:
        _pending.pop().join()


def _write(ckpt_dir: str, step: int, tree: Params,
           host: Dict[str, np.ndarray]) -> str:
    name = f"step_{step:09d}"
    final = os.path.join(ckpt_dir, name)
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    manifest = {
        "step": step,
        "leaves": {k: {"shape": list(v.shape), "dtype": str(v.dtype)}
                   for k, v in host.items()},
    }
    np.savez(os.path.join(tmp, "arrays.npz"), **host)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    # atomic LATEST pointer
    ptr_tmp = os.path.join(ckpt_dir, ".LATEST.tmp")
    with open(ptr_tmp, "w") as f:
        f.write(name)
    os.replace(ptr_tmp, os.path.join(ckpt_dir, "LATEST"))
    return final


def latest_step(ckpt_dir: str) -> Optional[int]:
    ptr = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(ptr):
        return None
    with open(ptr) as f:
        name = f.read().strip()
    path = os.path.join(ckpt_dir, name)
    if not os.path.isdir(path):
        return None
    return int(name.split("_")[1])


def restore(ckpt_dir: str, template: Params,
            step: Optional[int] = None) -> Tuple[Params, int]:
    """Restore into the structure of `template` (shapes are validated).
    Re-sharding happens on the caller side by device_put with the desired
    sharding — elastic restarts restore on a different mesh this way."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:09d}")
    arrays = np.load(os.path.join(path, "arrays.npz"))
    leaves = _flatten_with_paths(template)
    restored = []
    for key, leaf in leaves:
        if key not in arrays:
            raise KeyError(f"checkpoint missing leaf {key}")
        arr = arrays[key]
        want = tuple(getattr(leaf, "shape", ()))
        if tuple(arr.shape) != want:
            raise ValueError(
                f"leaf {key}: checkpoint shape {arr.shape} != {want}")
        restored.append(arr)
    treedef = jax.tree_util.tree_structure(template)
    return jax.tree_util.tree_unflatten(treedef, restored), step


def all_steps(ckpt_dir: str) -> List[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                  if d.startswith("step_") and not d.endswith(".tmp"))


def gc_old(ckpt_dir: str, keep: int = 3) -> None:
    steps = all_steps(ckpt_dir)
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:09d}"),
                      ignore_errors=True)
