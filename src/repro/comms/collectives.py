"""Tree-pipeline collectives executed with `lax.ppermute` under shard_map.

These are drop-in replacements for `lax.all_gather` / `psum_scatter` / `psum`
whose communication pattern is the paper's bandwidth-optimal pipeline
schedule instead of XLA's built-in algorithm.  They must be called INSIDE a
`shard_map` over the mesh axis the program was compiled for.

Data layout: the per-device shard is flattened and padded to
`slots_per_shard` equal chunks; the working buffer is
[axis_size * slots_per_shard + 1, chunk_elems] (last row = trash for
non-receivers).  Each `PermuteCall` is 3 ops: gather chunk(s), ppermute,
scatter (set for allgather, add for reduce-scatter).

On TPU the scatter-add of reduce-scatter is the arithmetic hot spot; the
Pallas `chunk_accum` kernel (src/repro/kernels) fuses it in VMEM.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from .executor import PermuteCall, PermuteProgram


def _me(axis_name: str) -> jax.Array:
    return jax.lax.axis_index(axis_name)


def _run_call(buf: jax.Array, call: PermuteCall, axis_name: str,
              me: jax.Array, mode: str) -> jax.Array:
    send_idx = jnp.asarray(call.send_slots)[me]      # [width]
    recv_idx = jnp.asarray(call.recv_slots)[me]      # [width]
    with jax.named_scope(obs.COMMS_GATHER):
        payload = jnp.take(buf, send_idx, axis=0)    # [width, chunk]
    with jax.named_scope(obs.COMMS_PERMUTE):
        got = jax.lax.ppermute(payload, axis_name, list(call.perm))
    with jax.named_scope(obs.COMMS_SCATTER):
        if mode == "set":
            # non-receivers target the trash row; receivers get exactly
            # one write
            return buf.at[recv_idx].set(got, mode="promise_in_bounds")
        # reduce-scatter: accumulate the incoming partial into our partial
        return buf.at[recv_idx].add(got, mode="promise_in_bounds")


def _run_program(buf: jax.Array, prog: PermuteProgram, axis_name: str,
                 mode: str) -> jax.Array:
    me = _me(axis_name)
    for rnd in prog.rounds:
        for call in rnd:
            buf = _run_call(buf, call, axis_name, me, mode)
    return buf


def _chunk_elems(shard_elems: int, slots: int) -> int:
    return -(-shard_elems // slots)  # ceil


# ---------------------------------------------------------------------- #
# allgather
# ---------------------------------------------------------------------- #

def tree_all_gather(x: jax.Array, prog: PermuteProgram, axis_name: str,
                    *, tiled: bool = False) -> jax.Array:
    """Bandwidth-optimal pipelined allgather of the local shard `x`.

    Returns [A, *x.shape] (or concatenated along axis 0 when tiled=True),
    matching `lax.all_gather` semantics."""
    if prog.kind != "allgather":
        raise ValueError(f"program kind {prog.kind} != allgather")
    a, s = prog.axis_size, prog.slots_per_shard
    shard_elems = int(np.prod(x.shape)) if x.ndim else 1
    ce = _chunk_elems(shard_elems, s)
    with jax.named_scope(obs.COMMS_STAGE):
        flat = jnp.ravel(x)
        flat = jnp.pad(flat, (0, s * ce - shard_elems))
        me = _me(axis_name)
        buf = jnp.zeros((a * s + 1, ce), dtype=x.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, flat.reshape(s, ce), me * s, axis=0)
    buf = _run_program(buf, prog, axis_name, mode="set")
    with jax.named_scope(obs.COMMS_STAGE):
        out = buf[:a * s].reshape(a, s * ce)[:, :shard_elems]
        out = out.reshape((a,) + x.shape)
        if tiled and x.ndim:
            out = out.reshape((a * x.shape[0],) + x.shape[1:])
    return out


# ---------------------------------------------------------------------- #
# reduce-scatter
# ---------------------------------------------------------------------- #

def tree_reduce_scatter(x: jax.Array, prog: PermuteProgram, axis_name: str,
                        *, accum_dtype: Optional[jnp.dtype] = None
                        ) -> jax.Array:
    """Bandwidth-optimal pipelined reduce-scatter.

    `x` has leading dim A*<shard>; returns this device's reduced shard
    (shape [shard, ...]), matching `lax.psum_scatter(tiled=True)`."""
    if prog.kind != "reduce_scatter":
        raise ValueError(f"program kind {prog.kind} != reduce_scatter")
    a, s = prog.axis_size, prog.slots_per_shard
    if x.shape[0] % a:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by {a}")
    shard_rows = x.shape[0] // a
    shard_shape = (shard_rows,) + x.shape[1:]
    shard_elems = int(np.prod(shard_shape))
    ce = _chunk_elems(shard_elems, s)
    compute_dtype = accum_dtype or (
        jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype)
    with jax.named_scope(obs.COMMS_STAGE):
        flat = x.reshape(a, shard_elems).astype(compute_dtype)
        flat = jnp.pad(flat, ((0, 0), (0, s * ce - shard_elems)))
        buf = jnp.concatenate(
            [flat.reshape(a * s, ce),
             jnp.zeros((1, ce), dtype=compute_dtype)], axis=0)
    buf = _run_program(buf, prog, axis_name, mode="add")
    me = _me(axis_name)
    with jax.named_scope(obs.COMMS_STAGE):
        mine = jax.lax.dynamic_slice_in_dim(buf, me * s, s, axis=0)
        out = mine.reshape(s * ce)[:shard_elems].reshape(shard_shape)
        return out.astype(x.dtype)


# ---------------------------------------------------------------------- #
# alltoall (per-source pruned scatter over the packed spanning trees)
# ---------------------------------------------------------------------- #

def tree_all_to_all(x: jax.Array, prog: PermuteProgram, axis_name: str
                    ) -> jax.Array:
    """Bandwidth-optimal pipelined all-to-all of the destination blocks `x`.

    `x` is [A, *block]: ``x[w]`` is this device's block for destination
    ``w``.  Returns [A, *block] with ``out[r]`` = source r's block for this
    device, matching ``jax.lax.all_to_all(x, axis_name, 0, 0)``.

    Alltoall programs fold the destination into the slot index
    (slots_per_shard = A·k·P; slot = dest·k·P + subslot), so each source's
    whole send buffer is staged contiguously at rows [me·S, (me+1)·S) in
    destination-major order.  The diagonal block is never on the wire (the
    schedule prunes it); it stays where this device staged it, and the
    gather below reads it back from our own rows.  Transit chunks a device
    forwards for others land at rows whose dest index differs from ours,
    so they never clobber an output row."""
    if prog.kind != "alltoall":
        raise ValueError(f"program kind {prog.kind} != alltoall")
    a, s = prog.axis_size, prog.slots_per_shard
    if x.shape[0] != a:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {a}")
    kp = s // a                       # subslots per destination block (k·P)
    block_shape = x.shape[1:]
    block_elems = int(np.prod(block_shape)) if len(block_shape) else 1
    ce = _chunk_elems(block_elems, kp)
    me = _me(axis_name)
    with jax.named_scope(obs.COMMS_STAGE):
        flat = x.reshape(a, block_elems)
        flat = jnp.pad(flat, ((0, 0), (0, kp * ce - block_elems)))
        buf = jnp.zeros((a * s + 1, ce), dtype=x.dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, flat.reshape(s, ce), me * s, axis=0)
    buf = _run_program(buf, prog, axis_name, mode="set")
    with jax.named_scope(obs.COMMS_STAGE):
        # source r's block for us sits at rows r*S + me*kp + t
        rows = ((jnp.arange(a) * s)[:, None] + me * kp
                + jnp.arange(kp)[None, :])
        out = jnp.take(buf, rows.reshape(-1), axis=0)
        out = out.reshape(a, kp * ce)[:, :block_elems]
        return out.reshape((a,) + block_shape)


# ---------------------------------------------------------------------- #
# broadcast / reduce (paper Appendix A and its edge-reversed dual)
# ---------------------------------------------------------------------- #

def tree_broadcast(x: jax.Array, prog: PermuteProgram, axis_name: str
                   ) -> jax.Array:
    """Bandwidth-optimal pipelined broadcast of the root's buffer `x`.

    Every device passes an `x` of the same shape (non-root values are
    ignored, matching MPI_Bcast); every device returns the root's `x`.
    The schedule's store-and-forward discipline guarantees non-root data
    never propagates: a device only ever sends chunks it received."""
    if prog.kind != "broadcast":
        raise ValueError(f"program kind {prog.kind} != broadcast")
    a, s = prog.axis_size, prog.slots_per_shard
    root = prog.root
    shard_elems = int(np.prod(x.shape)) if x.ndim else 1
    ce = _chunk_elems(shard_elems, s)
    with jax.named_scope(obs.COMMS_STAGE):
        flat = jnp.ravel(x)
        flat = jnp.pad(flat, (0, s * ce - shard_elems))
        buf = jnp.zeros((a * s + 1, ce), dtype=x.dtype)
        # slot layout matches the executor: the root's chunks live at
        # [root*s, (root+1)*s); every device stages its own copy there
        # (only the root's is ever forwarded)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, flat.reshape(s, ce), root * s, axis=0)
    buf = _run_program(buf, prog, axis_name, mode="set")
    with jax.named_scope(obs.COMMS_STAGE):
        out = jax.lax.dynamic_slice_in_dim(buf, root * s, s, axis=0)
        return out.reshape(s * ce)[:shard_elems].reshape(x.shape)


def tree_reduce(x: jax.Array, prog: PermuteProgram, axis_name: str,
                *, accum_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Bandwidth-optimal pipelined reduce (sum) of `x` to the root.

    Every device contributes its `x`; the return value equals Σ_devices x on
    the root device and an intermediate partial elsewhere (MPI_Reduce
    semantics).  Accumulation happens at every tree hop (op fusion): each
    device forwards one partial per chunk slot, never raw operands."""
    if prog.kind != "reduce":
        raise ValueError(f"program kind {prog.kind} != reduce")
    a, s = prog.axis_size, prog.slots_per_shard
    root = prog.root
    shard_elems = int(np.prod(x.shape)) if x.ndim else 1
    ce = _chunk_elems(shard_elems, s)
    compute_dtype = accum_dtype or (
        jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else x.dtype)
    with jax.named_scope(obs.COMMS_STAGE):
        flat = jnp.ravel(x).astype(compute_dtype)
        flat = jnp.pad(flat, (0, s * ce - shard_elems))
        buf = jnp.zeros((a * s + 1, ce), dtype=compute_dtype)
        buf = jax.lax.dynamic_update_slice_in_dim(
            buf, flat.reshape(s, ce), root * s, axis=0)
    buf = _run_program(buf, prog, axis_name, mode="add")
    with jax.named_scope(obs.COMMS_STAGE):
        out = jax.lax.dynamic_slice_in_dim(buf, root * s, s, axis=0)
        return out.reshape(s * ce)[:shard_elems].reshape(
            x.shape).astype(x.dtype)


# ---------------------------------------------------------------------- #
# allreduce = RS + AG (paper Appendix B)
# ---------------------------------------------------------------------- #

def tree_all_reduce(x: jax.Array, rs_prog: PermuteProgram,
                    ag_prog: PermuteProgram, axis_name: str,
                    *, accum_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Bandwidth-optimal allreduce: reduce-scatter then allgather.
    Matches `lax.psum` semantics for arbitrary-shaped x."""
    a = rs_prog.axis_size
    orig_shape = x.shape
    elems = int(np.prod(orig_shape)) if x.ndim else 1
    pad = (-elems) % a
    with jax.named_scope(obs.COMMS_STAGE):
        flat = jnp.ravel(x)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        flat = flat.reshape(a, (elems + pad) // a)
    shard = tree_reduce_scatter(flat, rs_prog, axis_name,
                                accum_dtype=accum_dtype)
    full = tree_all_gather(shard, ag_prog, axis_name)
    with jax.named_scope(obs.COMMS_STAGE):
        return full.reshape(-1)[:elems].reshape(orig_shape)


# ---------------------------------------------------------------------- #
# multi-axis composition (hierarchical: RS in, AG out)
# ---------------------------------------------------------------------- #

def tree_all_reduce_multi(x: jax.Array, progs: Sequence[tuple],
                          *, accum_dtype: Optional[jnp.dtype] = None
                          ) -> jax.Array:
    """Allreduce over several mesh axes: reduce-scatter innermost-out, then
    allgather in reverse — the standard hierarchical composition, with each
    stage's schedule bandwidth-optimal for its own axis topology.

    progs: sequence of (axis_name, rs_prog, ag_prog)."""
    if not progs:
        return x
    (axis, rs_p, ag_p), *rest = progs
    a = rs_p.axis_size
    orig_shape = x.shape
    elems = int(np.prod(orig_shape)) if x.ndim else 1
    pad = (-elems) % a
    with jax.named_scope(obs.COMMS_STAGE):
        flat = jnp.ravel(x)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        flat = flat.reshape(a, (elems + pad) // a)
    shard = tree_reduce_scatter(flat, rs_p, axis,
                                accum_dtype=accum_dtype)
    shard = tree_all_reduce_multi(shard, rest, accum_dtype=accum_dtype)
    full = tree_all_gather(shard, ag_p, axis)
    with jax.named_scope(obs.COMMS_STAGE):
        return full.reshape(-1)[:elems].reshape(orig_shape)
