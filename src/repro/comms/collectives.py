"""Tree-pipeline collectives executed with `lax.ppermute` under shard_map.

These are drop-in replacements for `lax.all_gather` / `psum_scatter` / `psum`
/ `all_to_all` (and MPI-style broadcast and reduce) whose communication
pattern is the paper's bandwidth-optimal pipeline schedule instead of XLA's
built-in algorithm.  They must be called INSIDE a `shard_map` over the mesh
axis the program was compiled for.

All six kinds share one staging path (`_tree`): the input, padded to equal
chunks, is staged in a [axis_size * slots_per_shard + 1, chunk_elems] buffer
(last row = trash for non-receivers).  Each `PermuteCall` is 3 ops: gather
chunk(s), ppermute, scatter (set, or add for the reducing kinds).  The
scatter-add runs in XLA over the staged buffer's full width, in f32 for
bf16/f16 inputs unless `accum_dtype` says otherwise.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from .executor import PermuteCall, PermuteProgram


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
    me = jax.lax.axis_index(axis_name)
    for rnd in prog.rounds:
        for call in rnd:
            buf = _run_call(buf, call, axis_name, me, mode)
    return buf


def _check(prog: PermuteProgram, kind: str) -> None:
    if prog.kind != kind:
        raise ValueError(f"program kind {prog.kind} != {kind}")


def _tree(x: jax.Array, prog: PermuteProgram, axis_name: str, shape, *,
          lead=(), chunks: Optional[int] = None, at=None, read=None,
          out_lead=(), add: bool = False,
          accum_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Stage `x` as `lead` rows of `chunks` (S unless given) chunks each,
    in device `at`'s slots (every device's when None); run `prog`, adding
    if `add`; read back `out_lead` rows: device `read`'s slots if
    `out_lead` is (), else every device's (`read` None) or device `read`'s
    block in each device's slots (all-to-all).  Returns `shape`, x.dtype."""
    a, s = prog.axis_size, prog.slots_per_shard
    chunks = chunks or s
    elems = x.size // (lead[0] if lead else 1)
    ce = -(-elems // chunks)
    half = x.dtype in (jnp.bfloat16, jnp.float16)
    dtype = x.dtype if not add else accum_dtype or (
        jnp.float32 if half else x.dtype)
    with jax.named_scope(obs.COMMS_STAGE):
        flat = x.reshape(lead + (elems,)).astype(dtype)
        flat = jnp.pad(flat, ((0, 0),) * len(lead)
                       + ((0, chunks * ce - elems),)).reshape(-1, ce)
        if at is None:
            buf = jnp.concatenate([flat, jnp.zeros((1, ce), dtype)], axis=0)
        else:
            buf = jax.lax.dynamic_update_slice_in_dim(
                jnp.zeros((a * s + 1, ce), dtype), flat, at * s, axis=0)
    buf = _run_program(buf, prog, axis_name, mode="add" if add else "set")
    with jax.named_scope(obs.COMMS_STAGE):
        if not out_lead:
            out = jax.lax.dynamic_slice_in_dim(buf, read * chunks, chunks,
                                               axis=0)
        elif read is None:
            out = buf[:a * s]
        else:
            # device r's block for `read` sits at rows r*S + read*chunks + t
            rows = ((jnp.arange(a) * s)[:, None] + read * chunks
                    + jnp.arange(chunks)[None, :])
            out = jnp.take(buf, rows.reshape(-1), axis=0)
        out = out.reshape(out_lead + (chunks * ce,))[..., :elems]
        return out.reshape(shape).astype(x.dtype)


def tree_all_gather(x: jax.Array, prog: PermuteProgram, axis_name: str,
                    *, tiled: bool = False) -> jax.Array:
    """Bandwidth-optimal pipelined allgather of the local shard `x`: returns
    [A, *x.shape] (or concatenated along axis 0 when tiled=True), matching
    `lax.all_gather` semantics."""
    _check(prog, "allgather")
    a = prog.axis_size
    shape = ((a * x.shape[0],) + x.shape[1:] if tiled and x.ndim
             else (a,) + x.shape)
    return _tree(x, prog, axis_name, shape,
                 at=jax.lax.axis_index(axis_name), out_lead=(a,))


def tree_reduce_scatter(x: jax.Array, prog: PermuteProgram, axis_name: str,
                        *, accum_dtype: Optional[jnp.dtype] = None
                        ) -> jax.Array:
    """Bandwidth-optimal pipelined reduce-scatter: `x` has leading dim
    A*<shard>; returns this device's reduced shard (shape [shard, ...]),
    matching `lax.psum_scatter(tiled=True)`."""
    _check(prog, "reduce_scatter")
    a = prog.axis_size
    if x.shape[0] % a:
        raise ValueError(f"leading dim {x.shape[0]} not divisible by {a}")
    return _tree(x, prog, axis_name, (x.shape[0] // a,) + x.shape[1:],
                 lead=(a,), read=jax.lax.axis_index(axis_name), add=True,
                 accum_dtype=accum_dtype)


def tree_all_to_all(x: jax.Array, prog: PermuteProgram, axis_name: str
                    ) -> jax.Array:
    """Bandwidth-optimal pipelined all-to-all: `x` is [A, *block], ``x[w]``
    this device's block for destination ``w``; returns [A, *block] with
    ``out[r]`` = source r's block for this device, matching
    ``jax.lax.all_to_all(x, axis_name, 0, 0)``.

    The slot index folds in the destination (S = A·k·P; slot = dest·k·P +
    subslot), so each source stages its send buffer at its own rows in
    destination-major order.  The diagonal block never leaves them (the
    schedule prunes it); transit chunks land at rows of other
    destinations, so they never clobber an output row."""
    _check(prog, "alltoall")
    a = prog.axis_size
    if x.shape[0] != a:
        raise ValueError(f"leading dim {x.shape[0]} != axis size {a}")
    me = jax.lax.axis_index(axis_name)
    return _tree(x, prog, axis_name, x.shape, lead=(a,),
                 chunks=prog.slots_per_shard // a, at=me, read=me,
                 out_lead=(a,))


def tree_broadcast(x: jax.Array, prog: PermuteProgram, axis_name: str
                   ) -> jax.Array:
    """Bandwidth-optimal pipelined broadcast of the root's buffer `x`: every
    device passes an `x` of the same shape (non-root values are ignored, as
    in MPI_Bcast) and returns the root's.  Each stages its copy at the root's
    slots; store-and-forward means only the root's is ever sent."""
    _check(prog, "broadcast")
    return _tree(x, prog, axis_name, x.shape, at=prog.root, read=prog.root)


def tree_reduce(x: jax.Array, prog: PermuteProgram, axis_name: str,
                *, accum_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Bandwidth-optimal pipelined reduce (sum) of `x` to the root: Σ_devices
    x on the root, a partial elsewhere (MPI_Reduce).  Each hop adds before
    forwarding: one partial per chunk slot."""
    _check(prog, "reduce")
    return _tree(x, prog, axis_name, x.shape, at=prog.root, read=prog.root,
                 add=True, accum_dtype=accum_dtype)


def tree_all_reduce(x: jax.Array, rs_prog: PermuteProgram,
                    ag_prog: PermuteProgram, axis_name: str,
                    *, accum_dtype: Optional[jnp.dtype] = None) -> jax.Array:
    """Bandwidth-optimal allreduce: reduce-scatter then allgather (paper
    Appendix B).  Matches `lax.psum` semantics for arbitrary-shaped x."""
    return tree_all_reduce_multi(x, [(axis_name, rs_prog, ag_prog)],
                                 accum_dtype=accum_dtype)


def tree_all_reduce_multi(x: jax.Array, progs: Sequence[tuple],
                          *, accum_dtype: Optional[jnp.dtype] = None
                          ) -> jax.Array:
    """Allreduce over the axes of `progs`, a sequence of (axis_name,
    rs_prog, ag_prog): reduce-scatter innermost-out, then allgather in
    reverse, each stage's schedule bandwidth-optimal for its own axis."""
    if not progs:
        return x
    (axis, rs_p, ag_p), *rest = progs
    a = rs_p.axis_size
    elems = x.size
    pad = (-elems) % a
    with jax.named_scope(obs.COMMS_STAGE):
        flat = jnp.ravel(x)
        if pad:
            flat = jnp.pad(flat, (0, pad))
        flat = flat.reshape(a, (elems + pad) // a)
    shard = tree_reduce_scatter(flat, rs_p, axis, accum_dtype=accum_dtype)
    shard = tree_all_reduce_multi(shard, rest, accum_dtype=accum_dtype)
    full = tree_all_gather(shard, ag_p, axis)
    with jax.named_scope(obs.COMMS_STAGE):
        return full.reshape(-1)[:elems].reshape(x.shape)
