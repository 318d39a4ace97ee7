"""Pallas TPU kernel for the Mamba2 SSD intra-chunk block.

The §Perf loop (EXPERIMENTS.md, cell C) showed the chunked SSD's HBM
traffic is dominated by the inter-chunk state and the intra-chunk decay
matrices round-tripping HBM between XLA kernels.  This kernel fuses one
chunk's whole intra-chunk computation in VMEM:

    L[i,j]   = exp(cum[i] - cum[j])   (i >= j, else 0)     [Q, Q]
    y[i]     = sum_j (C[i]·B[j]) * L[i,j] * xdt[j]         [Q, P]
    state    = sum_j exp(cum[Q-1] - cum[j]) * xdt[j] ⊗ B[j]  [P, N]

Grid: (batch*heads, num_chunks); block = one (head, chunk).  VMEM per step:
Q·(P+2N+2) + Q² + P·N floats — Q=256, P=64, N=128: ~0.6 MB.  The decay
matrix L never leaves VMEM, which is exactly the traffic the XLA fallback
pays for.  The inter-chunk recurrence (S/Q steps) stays in XLA — it is
O(S/Q) tiny ops once the intra-chunk work is fused.

Validated in interpret mode against `ref.ssd_chunk_reference`
(tests/test_kernels.py sweeps shapes and dtypes).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref,
                      y_ref, state_ref, *, chunk: int):
    x = x_ref[0].astype(jnp.float32)          # [Q, P]
    dt = dt_ref[0].astype(jnp.float32)        # [Q, 1]
    a = a_ref[pl.program_id(0)]               # scalar (this head's A)
    b = b_ref[0].astype(jnp.float32)          # [Q, N]
    c = c_ref[0].astype(jnp.float32)          # [Q, N]

    iq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jq = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = iq >= jq
    da = dt * a                               # [Q, 1]
    # inclusive prefix sum as a masked row reduction (Mosaic has no cumsum)
    cum = jnp.sum(jnp.where(causal, da.T, 0.0), axis=1,
                  keepdims=True)              # [Q, 1]
    diff = cum - cum.T                        # [Q, Q]
    ll = jnp.where(causal, jnp.exp(diff), 0.0)

    xdt = x * dt                              # [Q, P]
    scores = jax.lax.dot_general(             # C·B^T  [Q, Q]
        c, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    y = jax.lax.dot_general(                  # (scores*L) @ xdt  [Q, P]
        scores * ll, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    decay_state = jnp.exp(cum[-1:] - cum)     # [Q, 1]
    state = jax.lax.dot_general(              # xdt^T @ (decay*B)  [P, N]
        xdt, b * decay_state, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state_ref[0, 0] = state.astype(state_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("chunk", "interpret"))
def ssd_chunk_intra(x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array, *, chunk: int,
                    interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array]:
    """Intra-chunk SSD for all (batch, head, chunk) blocks.

    x: [BH, S, P] (batch*heads flattened), dt: [BH, S], a: [BH],
    b, c: [BH, S, N] (per-head replicated upstream).
    Returns (y_diag [BH, S, P], states [BH, S//chunk, P, N])."""
    bh, s, p = x.shape
    n = b.shape[-1]
    if s % chunk:
        raise ValueError(f"seq {s} must divide chunk {chunk}")
    l = s // chunk
    grid = (bh, l)
    return pl.pallas_call(
        functools.partial(_ssd_chunk_kernel, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda i, j: (i, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, 1, p, n), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, l, p, n), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(x, dt.reshape(bh, s, 1), a.astype(jnp.float32), b, c)
