"""The staging matrix: every tree collective kind, in f32 and bf16, on an
even shape and on a ragged one that must be padded to whole chunks, and on
0-d inputs, each against its `jax.lax` oracle on 8 host devices.

One subprocess compiles each kind's program once on `bidir_ring(8)`, runs
every case and prints one JSON line per case; each parametrised case
asserts its own line."""
import json

import pytest

from conftest import run_snippet

KINDS = ("allgather", "reduce_scatter", "allreduce", "broadcast", "reduce",
         "alltoall")
DTYPES = ("float32", "bfloat16")
SCALAR_KINDS = ("allgather", "broadcast", "reduce", "allreduce")
CASES = ([f"{k}-{d}-{s}" for k in KINDS for d in DTYPES
          for s in ("even", "ragged")]
         + [f"{k}-scalar" for k in SCALAR_KINDS])

MATRIX = """
    import json, zlib
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.api import Collectives
    from repro.comms import (tree_all_gather, tree_all_reduce,
                             tree_all_to_all, tree_broadcast, tree_reduce,
                             tree_reduce_scatter)
    from repro.topo import bidir_ring

    A, BC_ROOT, RED_ROOT = 8, 3, 5
    mesh = Mesh(np.array(jax.devices()), ('x',))
    cc = Collectives(num_chunks=4)      # S = 4k: no odd size divides
    topo = bidir_ring(A)
    prog = {k: cc.program(topo, kind=k) for k in
            ('allgather', 'reduce_scatter', 'alltoall')}
    prog['broadcast'] = cc.program(topo, kind='broadcast', root=BC_ROOT)
    prog['reduce'] = cc.program(topo, kind='reduce', root=RED_ROOT)
    s = prog['allgather'].slots_per_shard
    kp = prog['alltoall'].slots_per_shard // A
    ceil = lambda n, d: -(-n // d)
    # per kind: (even shape, ragged shape) of one device's input, and the
    # elements its rows pad to whole chunks
    shapes = {
        'allgather': ((s, 2), (5, 7)),
        'broadcast': ((s, 2), (5, 7)),
        'reduce': ((s, 2), (5, 7)),
        'reduce_scatter': ((2 * A, s), (5 * A, 7)),
        'allreduce': ((A, 2 * s), (5, 7)),
        'alltoall': ((A, kp, 2), (A, 5, 7)),
    }
    def pad_of(kind, shape):
        n = int(np.prod(shape))
        if kind == 'reduce_scatter':
            return (-(n // A)) % s
        if kind == 'allreduce':
            return (-n) % A + (-ceil(n, A)) % s
        if kind == 'alltoall':
            return (-(n // A)) % kp
        return (-n) % s

    tree = {
        'allgather': lambda v: tree_all_gather(v, prog['allgather'], 'x'),
        'reduce_scatter': lambda v: tree_reduce_scatter(
            v, prog['reduce_scatter'], 'x'),
        'allreduce': lambda v: tree_all_reduce(
            v, prog['reduce_scatter'], prog['allgather'], 'x'),
        'broadcast': lambda v: tree_broadcast(v, prog['broadcast'], 'x'),
        'reduce': lambda v: tree_reduce(v, prog['reduce'], 'x'),
        'alltoall': lambda v: tree_all_to_all(v, prog['alltoall'], 'x'),
    }
    # the oracles; the reductions' run on the f32 upcast
    f32 = lambda v: v.astype(jnp.float32)
    oracle = {
        'allgather': lambda v: jax.lax.all_gather(v, 'x'),
        'reduce_scatter': lambda v: jax.lax.psum_scatter(
            f32(v), 'x', tiled=True),
        'allreduce': lambda v: jax.lax.psum(f32(v), 'x'),
        'broadcast': lambda v: jax.lax.all_gather(v, 'x')[BC_ROOT],
        'reduce': lambda v: jax.lax.psum(f32(v), 'x'),
        'alltoall': lambda v: jax.lax.all_to_all(v, 'x', 0, 0),
    }
    reducing = ('reduce_scatter', 'allreduce', 'reduce')

    def run(case, kind, dtype, shape):
        x = jax.random.normal(jax.random.PRNGKey(zlib.crc32(case.encode())),
                              (A,) + shape).astype(dtype)
        f = jax.jit(jax.shard_map(
            lambda v: (tree[kind](v[0])[None], oracle[kind](v[0])[None],
                       jax.lax.psum(jnp.abs(f32(v[0])), 'x')[None]),
            mesh=mesh, in_specs=P('x'), out_specs=P('x'), check_vma=False))
        outs = f(x)
        got, want, absum = (np.asarray(a, np.float32) for a in outs)
        if kind == 'reduce':        # defined on the root only
            got, want, absum = got[RED_ROOT], want[RED_ROOT], absum[RED_ROOT]
        if kind == 'reduce_scatter':
            # each device's shard of the per-element |x| sums
            absum = absum.reshape(A, A, -1)[np.arange(A), np.arange(A)]
            absum = absum.reshape(want.shape)
        out = {'case': case, 'pad': pad_of(kind, shape),
               'dtype': str(outs[0].dtype), 'shape': list(got.shape),
               'want_shape': list(want.shape)}
        if got.shape != want.shape:
            out['err'] = None
        elif kind in reducing:
            # f32 summation order, plus one bf16 rounding of the f32 sum
            half_ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 0.0
            tol = half_ulp * np.abs(want) + 2.0 ** -20 * absum + 1e-30
            out['err'] = float(np.max(np.abs(got - want) / tol))
        else:
            out['err'] = float(np.max(np.abs(got - want)))
        print(json.dumps(out), flush=True)

    for kind, (even, ragged) in shapes.items():
        for dtype in (jnp.float32, jnp.bfloat16):
            name = jnp.dtype(dtype).name
            run(f'{kind}-{name}-even', kind, dtype, even)
            run(f'{kind}-{name}-ragged', kind, dtype, ragged)
    for kind in ('allgather', 'broadcast', 'reduce', 'allreduce'):
        run(f'{kind}-scalar', kind, jnp.float32, ())
"""


@pytest.fixture(scope="module")
def matrix():
    rows = [json.loads(line) for line in run_snippet(MATRIX).splitlines()
            if line.startswith("{")]
    return {r["case"]: r for r in rows}


@pytest.mark.parametrize("case", CASES)
def test_staging_matches_lax(matrix, case):
    r = matrix[case]
    assert r["shape"] == r["want_shape"], r
    kind = case.split("-")[0]
    if kind in ("reduce_scatter", "allreduce", "reduce"):
        assert r["err"] <= 1.0, r       # within the rounding bound
    else:
        assert r["err"] == 0.0, r       # data movement is exact
    if case.endswith("-even"):
        assert r["pad"] == 0, r
    elif case.endswith("-ragged"):
        assert r["pad"] > 0, r
    # the result comes back in the input's dtype
    assert r["dtype"] == ("bfloat16" if "bfloat16" in case else "float32")
