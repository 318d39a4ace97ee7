"""Beyond-paper distributed-optimization layers on top of the tree
collectives: bucketed gradient reduction (overlap hooks) and wire
compression.

* `BucketedAllReduce` — partitions the gradient pytree into ~equal-byte
  buckets; each bucket is reduced independently, so on hardware the bucket
  i+1 reduction overlaps the bucket i optimizer math (and, launched from
  the backward, overlaps backprop compute — the classic DDP trick).  The
  bucket schedule also keeps each tree-pipeline transfer long enough to
  amortise the (P+depth)/P pipeline fill of the paper's schedules.
* `compressed_all_reduce` — casts the wire payload (bf16 by default) while
  accumulating in f32 via the tree reduce-scatter's accumulator; the paper
  optimises bytes-on-the-wire, compression multiplies that directly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from .collectives import tree_all_reduce
from .executor import PermuteProgram


def partition_buckets(tree: Any, bucket_bytes: int = 64 << 20
                      ) -> List[List[int]]:
    """Greedy partition of flattened leaf indices into ~bucket_bytes groups
    (in reverse order — gradients become ready output-to-input)."""
    leaves = jax.tree_util.tree_leaves(tree)
    buckets: List[List[int]] = [[]]
    size = 0
    for idx in reversed(range(len(leaves))):
        nbytes = int(np.prod(leaves[idx].shape)) * leaves[idx].dtype.itemsize
        if size and size + nbytes > bucket_bytes:
            buckets.append([])
            size = 0
        buckets[-1].append(idx)
        size += nbytes
    return buckets


@dataclasses.dataclass
class BucketedAllReduce:
    rs_prog: PermuteProgram
    ag_prog: PermuteProgram
    axis_name: str
    bucket_bytes: int = 64 << 20
    wire_dtype: Optional[Any] = jnp.bfloat16

    @classmethod
    def from_schedule(cls, ar: Any, axis_name: str,
                      bucket_bytes: int = 64 << 20,
                      wire_dtype: Optional[Any] = jnp.bfloat16
                      ) -> "BucketedAllReduce":
        """Build the gradient hook from ONE `AllReduceSchedule` artifact —
        typically `repro.api.Collectives.schedule(..., kind="allreduce")`
        (cache-backed), so the RS and AG halves replay from a single cached
        `repro.allreduce` entry."""
        from .executor import compile_program
        return cls(rs_prog=compile_program(ar.rs),
                   ag_prog=compile_program(ar.ag), axis_name=axis_name,
                   bucket_bytes=bucket_bytes, wire_dtype=wire_dtype)

    def __call__(self, grads: Any) -> Any:
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        buckets = partition_buckets(grads, self.bucket_bytes)
        out = list(leaves)
        for bucket in buckets:
            with jax.named_scope(obs.COMMS_BUCKET):
                flat = jnp.concatenate(
                    [jnp.ravel(leaves[i]) for i in bucket]) \
                    if len(bucket) > 1 else jnp.ravel(leaves[bucket[0]])
                if self.wire_dtype is not None:
                    flat = flat.astype(self.wire_dtype)
            red = tree_all_reduce(flat, self.rs_prog, self.ag_prog,
                                  self.axis_name,
                                  accum_dtype=jnp.float32)
            with jax.named_scope(obs.COMMS_BUCKET):
                off = 0
                for i in bucket:
                    n = int(np.prod(leaves[i].shape))
                    out[i] = red[off:off + n].reshape(
                        leaves[i].shape).astype(leaves[i].dtype)
                    off += n
        return jax.tree_util.tree_unflatten(treedef, out)


def compressed_all_reduce(x: jax.Array, rs_prog: PermuteProgram,
                          ag_prog: PermuteProgram, axis_name: str,
                          wire_dtype=jnp.bfloat16) -> jax.Array:
    """All-reduce with bf16 (or fp8) wire payload and f32 accumulation."""
    return tree_all_reduce(x.astype(wire_dtype), rs_prog, ag_prog,
                           axis_name,
                           accum_dtype=jnp.float32).astype(x.dtype)
