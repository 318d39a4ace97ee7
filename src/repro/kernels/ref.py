"""Pure-jnp oracle for the SSD Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ssd_chunk_reference(x: jax.Array, dt: jax.Array, a: jax.Array,
                        b: jax.Array, c: jax.Array) -> jax.Array:
    """Single-chunk SSD intra-chunk output (no inter-chunk state).
    x: [Q,H,P], dt: [Q,H], a: [H], b,c: [Q,N] -> y [Q,H,P]."""
    q = x.shape[0]
    da = dt * a[None, :]                                  # [Q,H]
    cs = jnp.cumsum(da, axis=0)
    diff = cs[:, None, :] - cs[None, :, :]                # [i,j,H]
    mask = jnp.tril(jnp.ones((q, q), bool))
    l = jnp.where(mask[..., None], jnp.exp(diff), 0.0)    # [i,j,H]
    scores = (c @ b.T)                                    # [i,j]
    xdt = x * dt[..., None]
    return jnp.einsum("ij,ijh,jhp->ihp", scores, l, xdt)
