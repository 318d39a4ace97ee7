"""Deterministic synthetic data pipeline with host-side sharding.

Production shape: each host materialises only its slice of the global batch
(`host_batch_slice`), and `make_global_batch` assembles a sharded
jax.Array via `jax.make_array_from_callback` — the same call pattern a real
multi-host loader uses, so swapping in a tokenised dataset changes one
function.  Batches are a pure function of (seed, step): restart-safe and
bitwise reproducible across checkpoint resume (the fault-tolerance tests
rely on this).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import obs


@dataclasses.dataclass(frozen=True)
class DataConfig:
    vocab_size: int
    seq_len: int
    global_batch: int
    seed: int = 0
    # frontend stubs (vlm / audio)
    num_image_tokens: int = 0
    encoder_seq: int = 0
    d_model: int = 0


def _rng_for(cfg: DataConfig, step: int, name: str) -> np.random.Generator:
    return np.random.default_rng(
        np.random.SeedSequence([cfg.seed, step, hash(name) & 0x7FFFFFFF]))


def host_batch_slice(cfg: DataConfig, step: int, lo: int, hi: int
                     ) -> Dict[str, np.ndarray]:
    """Rows [lo, hi) of the global batch for `step` — what one host loads.
    Generated row-wise so any slicing of the global batch is consistent."""
    out: Dict[str, np.ndarray] = {}
    rows = []
    for r in range(lo, hi):
        rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed, step, r]))
        rows.append(rng.integers(0, cfg.vocab_size, cfg.seq_len,
                                 dtype=np.int32))
    out["tokens"] = np.stack(rows) if rows else \
        np.zeros((0, cfg.seq_len), np.int32)
    if cfg.num_image_tokens:
        rng = _rng_for(cfg, step, "patch")
        out["patch_embed"] = rng.standard_normal(
            (hi - lo, cfg.num_image_tokens, cfg.d_model),
            dtype=np.float32) * 0.02
    if cfg.encoder_seq:
        rng = _rng_for(cfg, step, "audio")
        out["audio_embed"] = rng.standard_normal(
            (hi - lo, cfg.encoder_seq, cfg.d_model),
            dtype=np.float32) * 0.02
    return out


def make_global_batch(cfg: DataConfig, step: int, mesh: Mesh,
                      batch_axes: Tuple[str, ...] = ("data",)
                      ) -> Dict[str, jax.Array]:
    """Assemble the sharded global batch; each addressable shard is
    materialised independently (multi-host safe)."""
    with TraceAnnotation(obs.DATA_BATCH):
        specs = {"tokens": PartitionSpec(batch_axes)}
        shapes = {"tokens": (cfg.global_batch, cfg.seq_len)}
        if cfg.num_image_tokens:
            specs["patch_embed"] = PartitionSpec(batch_axes)
            shapes["patch_embed"] = (cfg.global_batch, cfg.num_image_tokens,
                                     cfg.d_model)
        if cfg.encoder_seq:
            specs["audio_embed"] = PartitionSpec(batch_axes)
            shapes["audio_embed"] = (cfg.global_batch, cfg.encoder_seq,
                                     cfg.d_model)

        out = {}
        for name, spec in specs.items():
            sharding = NamedSharding(mesh, spec)
            shape = shapes[name]

            def cb(index, name=name, shape=shape):
                rows = index[0]
                lo = rows.start or 0
                hi = rows.stop if rows.stop is not None else shape[0]
                data = host_batch_slice(cfg, step, lo, hi)[name]
                rest = index[1:]
                return data[(slice(None),) + tuple(rest)]

            out[name] = jax.make_array_from_callback(shape, sharding, cb)
        return out
