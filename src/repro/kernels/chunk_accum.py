"""Pallas chunk-accumulate: the reduce-scatter arithmetic hot spot.

Every pipeline round of the paper's reduce-scatter schedule lands incoming
partial-sum chunks that must be added into the local fp32 accumulator:

    acc[slot] += incoming.astype(f32)

Off the shelf this is a bf16->f32 upcast + add + writeback through HBM per
round.  The kernel tiles both operands into VMEM ([block_n, block_c] tiles,
lane-aligned multiples of 128) and fuses upcast+add in-register, so the
accumulator row is read and written exactly once per round.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _accum_kernel(acc_ref, upd_ref, out_ref):
    out_ref[...] = acc_ref[...] + upd_ref[...].astype(acc_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_n", "block_c", "interpret"))
def chunk_accum(acc: jax.Array, update: jax.Array, *,
                block_n: int = 8, block_c: int = 512,
                interpret: bool = False) -> jax.Array:
    """acc: [N, C] float32; update: [N, C] (bf16/f16/f32) -> acc + update."""
    n, c = acc.shape
    bn = min(block_n, n)
    bc = min(block_c, c)
    if n % bn or c % bc:
        raise ValueError(f"shape ({n},{c}) must divide blocks ({bn},{bc})")
    grid = (n // bn, c // bc)
    return pl.pallas_call(
        _accum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bc), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bn, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n, c), acc.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(acc, update)
