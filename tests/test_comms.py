"""Multi-device execution of the tree-pipeline collectives vs JAX oracles.

Each test spawns a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 (the main pytest process must keep seeing ONE device)."""
from conftest import run_snippet


def test_tree_collectives_match_references():
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.topo import bidir_ring, fig1a, ring
        from repro.core.schedule import compile_allgather, compile_reduce_scatter
        from repro.comms import compile_program, tree_all_gather, \\
            tree_reduce_scatter, tree_all_reduce

        mesh = Mesh(np.array(jax.devices()), ('x',))
        for topo in (bidir_ring(8), fig1a(), ring(8)):
            ag = compile_program(compile_allgather(topo, num_chunks=4))
            rs = compile_program(compile_reduce_scatter(topo, num_chunks=4))
            x = jax.random.normal(jax.random.PRNGKey(0), (8, 13))
            f = jax.jit(shard_map(lambda v: tree_all_gather(v[0], ag, 'x'),
                                  mesh=mesh, in_specs=P('x'), out_specs=P('x')))
            got = f(x).reshape(8, 8, 13)
            assert np.allclose(got, np.broadcast_to(x[None], (8, 8, 13)),
                               atol=1e-5), topo.name
            h = jax.jit(shard_map(
                lambda v: tree_all_reduce(v[0], rs, ag, 'x')[None],
                mesh=mesh, in_specs=P('x'), out_specs=P('x')))
            got = h(x)
            assert np.allclose(got, np.broadcast_to(x.sum(0), (8, 13)),
                               atol=1e-4), topo.name
            y = jnp.arange(8 * 8 * 4, dtype=jnp.float32).reshape(8, 32)
            g = jax.jit(shard_map(
                lambda v: tree_reduce_scatter(v[0].reshape(8, 4), rs, 'x'),
                mesh=mesh, in_specs=P('x'), out_specs=P('x')))
            assert np.allclose(g(y), y.sum(0).reshape(8, 4)), topo.name
            print('OK', topo.name)
    """))


def test_tree_broadcast_and_reduce_match_references():
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.topo import bidir_ring, fig1a
        from repro.core.schedule import compile_broadcast, compile_reduce
        from repro.comms import compile_program, tree_broadcast, tree_reduce

        mesh = Mesh(np.array(jax.devices()), ('x',))
        for topo in (bidir_ring(8), fig1a()):   # incl. a switched topology
            for root in (0, 3):
                bc = compile_program(compile_broadcast(topo, root=root,
                                                       num_chunks=4))
                rd = compile_program(compile_reduce(topo, root=root,
                                                    num_chunks=4))
                assert bc.root == root and rd.root == root
                x = jax.random.normal(jax.random.PRNGKey(root), (8, 13))
                f = jax.jit(shard_map(
                    lambda v: tree_broadcast(v[0], bc, 'x')[None],
                    mesh=mesh, in_specs=P('x'), out_specs=P('x')))
                got = f(x)
                assert np.allclose(got, np.broadcast_to(x[root], (8, 13)),
                                   atol=1e-5), (topo.name, root)
                g = jax.jit(shard_map(
                    lambda v: tree_reduce(v[0], rd, 'x')[None],
                    mesh=mesh, in_specs=P('x'), out_specs=P('x')))
                # MPI_Reduce semantics: the result is defined on the root
                assert np.allclose(g(x)[root], x.sum(0), atol=1e-4), \\
                    (topo.name, root)
                print('OK bc/red', topo.name, 'root', root)
    """))


def test_tree_all_to_all_matches_reference():
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.api import Collectives
        from repro.topo import bidir_ring, fig1a
        from repro.comms import tree_all_to_all

        mesh = Mesh(np.array(jax.devices()), ('x',))
        cc = Collectives(num_chunks=1)
        for topo in (bidir_ring(8), fig1a()):
            prog = cc.program(topo, kind='alltoall')
            for shape in ((64, 3, 5), (64, 7)):
                x = jax.random.normal(jax.random.PRNGKey(0), shape)
                f = jax.jit(shard_map(
                    lambda v: tree_all_to_all(v, prog, 'x'),
                    mesh=mesh, in_specs=P('x'), out_specs=P('x')))
                g = jax.jit(shard_map(
                    lambda v: jax.lax.all_to_all(v, 'x', 0, 0),
                    mesh=mesh, in_specs=P('x'), out_specs=P('x')))
                assert np.array_equal(np.asarray(f(x)), np.asarray(g(x))), \\
                    (topo.name, shape)
                print('OK a2a', topo.name, shape)
    """))


def test_moe_forward_alltoall_transport_parity():
    """Expert-parallel MoE under shard_map: the compiled tree_all_to_all
    transport must reproduce the jax.lax.all_to_all transport exactly
    (only the wire schedule differs), and both must match the local
    dense-dispatch moe_forward."""
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.api import Collectives
        from repro.topo import bidir_ring
        from repro.comms import tree_all_to_all
        from repro.models.common import ModelConfig
        from repro.models.moe import (init_moe, moe_forward,
                                      moe_forward_alltoall)

        cfg = ModelConfig(name='t', family='moe', num_layers=1, d_model=16,
                          num_heads=2, num_kv_heads=2, d_ff=32,
                          vocab_size=64, num_experts=8,
                          num_experts_per_tok=2, moe_d_ff=24,
                          capacity_factor=2.0)
        p = init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8 * 2, 6, 16))
        mesh = Mesh(np.array(jax.devices()), ('x',))
        prog = Collectives(num_chunks=1).program(bidir_ring(8),
                                                 kind='alltoall')

        def run(fwd):
            def body(v):
                y, aux = fwd(v)
                return y, jax.lax.pmean(aux, 'x')
            return jax.jit(shard_map(body, mesh=mesh, in_specs=P('x'),
                                     out_specs=(P('x'), P())))

        y_lax, a_lax = run(
            lambda v: moe_forward_alltoall(p, cfg, v, 'x'))(x)
        y_tree, a_tree = run(
            lambda v: moe_forward_alltoall(
                p, cfg, v, 'x',
                all_to_all=lambda u: tree_all_to_all(u, prog, 'x')))(x)
        assert np.array_equal(np.asarray(y_lax), np.asarray(y_tree))
        assert np.array_equal(np.asarray(a_lax), np.asarray(a_tree))
        # tokens stay data-parallel, experts see every shard: per-shard
        # routing/capacity is identical to a local dense dispatch
        y_loc, _ = run(lambda v: moe_forward(p, cfg, v))(x)
        assert np.allclose(np.asarray(y_lax), np.asarray(y_loc),
                           atol=1e-5)
        print('OK moe alltoall transport parity')
    """))


def test_bucketed_allreduce_from_cached_artifact():
    print(run_snippet("""
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.api import Collectives
        from repro.cache import ScheduleCache
        from repro.comms import BucketedAllReduce

        mesh = Mesh(np.array(jax.devices()), ('x',))
        cache_dir = tempfile.mkdtemp()
        ar = Collectives(cache=cache_dir, num_chunks=4).schedule(
            'bring:8', kind='allreduce')
        # replay the single artifact from a fresh cache (no recompilation)
        cache = ScheduleCache(cache_dir)
        ar2 = Collectives(cache=cache, num_chunks=4).schedule(
            'bring:8', kind='allreduce')
        assert cache.stats.hits == 1 and cache.stats.misses == 0
        assert ar2.claimed_runtime == ar.claimed_runtime
        red = BucketedAllReduce.from_schedule(ar2, axis_name='x',
                                              wire_dtype=None)
        x = jax.random.normal(jax.random.PRNGKey(9), (8, 40))
        h = jax.jit(shard_map(lambda v: red({'g': v[0]})['g'][None],
                              mesh=mesh, in_specs=P('x'), out_specs=P('x')))
        assert np.allclose(h(x)[0], x.sum(0), atol=1e-4)
        print('OK bucketed allreduce from one cached artifact')
    """))


def test_multi_axis_hierarchical_allreduce():
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.comms.mesh_axes import CollectiveContext
        from repro.comms.collectives import tree_all_reduce_multi

        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ('pod', 'data'))
        ctx = CollectiveContext({'pod': 2, 'data': 4}, num_chunks=4)
        progs = ctx.allreduce_programs(('pod', 'data'))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 11))
        f = jax.jit(shard_map(
            lambda v: tree_all_reduce_multi(v[0], progs)[None],
            mesh=mesh, in_specs=P(('pod', 'data')),
            out_specs=P(('pod', 'data'))))
        got = f(x)
        assert np.allclose(got, np.broadcast_to(x.sum(0), (8, 11)), atol=1e-4)
        print('OK multi-axis', ctx.describe())
    """))


def test_bf16_reduce_scatter_f32_accumulation():
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.topo import bidir_ring
        from repro.core.schedule import compile_reduce_scatter
        from repro.comms import compile_program, tree_reduce_scatter

        mesh = Mesh(np.array(jax.devices()), ('x',))
        rs = compile_program(compile_reduce_scatter(bidir_ring(8),
                                                    num_chunks=4))
        y = (jax.random.normal(jax.random.PRNGKey(2), (8, 8, 16)) * 100
             ).astype(jnp.bfloat16)
        g = jax.jit(shard_map(
            lambda v: tree_reduce_scatter(v[0], rs, 'x'),
            mesh=mesh, in_specs=P('x'), out_specs=P('x')))
        got = g(y.reshape(8, -1)).reshape(8, 16)
        ref = y.astype(jnp.float32).sum(0).reshape(8, 16)
        err = np.abs(np.asarray(got, np.float32) - np.asarray(ref)).max()
        rel = err / np.abs(np.asarray(ref)).max()
        assert rel < 2e-2, rel   # f32 accumulation keeps bf16 inputs sane
        print('OK bf16 accum, rel err', rel)
    """))


def test_bucketed_overlap_allreduce():
    print(run_snippet("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.topo import bidir_ring
        from repro.core.schedule import compile_allgather, \\
            compile_reduce_scatter
        from repro.comms import compile_program
        from repro.comms.overlap import BucketedAllReduce, partition_buckets

        mesh = Mesh(np.array(jax.devices()), ('x',))
        topo = bidir_ring(8)
        red = BucketedAllReduce(
            rs_prog=compile_program(compile_reduce_scatter(topo, num_chunks=4)),
            ag_prog=compile_program(compile_allgather(topo, num_chunks=4)),
            axis_name='x', bucket_bytes=1 << 10)
        grads = {'a': jax.random.normal(jax.random.PRNGKey(0), (8, 64)),
                 'b': jax.random.normal(jax.random.PRNGKey(1), (128,)),
                 'c': jax.random.normal(jax.random.PRNGKey(2), (4, 4))}
        assert len(partition_buckets(grads, 1 << 10)) >= 2
        def f(g):
            g = jax.tree.map(lambda x: x[0], g)
            return jax.tree.map(lambda x: x[None], red(g))
        per_dev = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (8,) + x.shape), grads)
        got = jax.jit(shard_map(f, mesh=mesh,
                                in_specs=P('x'), out_specs=P('x')))(per_dev)
        for k in grads:
            want = grads[k] * 8
            err = np.abs(np.asarray(got[k][0]) - np.asarray(want)).max()
            assert err < np.abs(np.asarray(want)).max() * 2e-2, (k, err)
        print('OK bucketed overlap allreduce')
    """))
