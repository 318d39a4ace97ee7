"""Compile-only rehearsals for TPU v5e, without a chip.

The TPU compiler compiles for a described `v5e:2x2` host that is not
attached, and refuses what the chip would refuse: Pallas blocks that are not
tile-aligned, kernels over the VMEM budget, programs that cannot be
partitioned.  Nothing here runs; these tests only guard that the kernels (at
the widths the models use) and the tree-pipeline collectives (under
`jax.shard_map` over four chips) still compile.

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers all import this
file.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("data",))


def _shape(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# ---------------------------------------------------------------------- #
# Pallas kernels on one described chip (interpret=False)
# ---------------------------------------------------------------------- #

def test_chunk_accum_compiles(one_chip):
    from repro.kernels.chunk_accum import chunk_accum
    acc = _shape((256, 4096), jnp.float32, one_chip)
    upd = _shape((256, 4096), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda a, u: chunk_accum(a, u, interpret=False)).lower(
            acc, upd).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_compiles_head_dim_128_seq_2048(one_chip):
    from repro.kernels.flash_attention import flash_attention
    q = _shape((1, 8, 2048, 128), jnp.bfloat16, one_chip)
    kv = _shape((1, 2, 2048, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False)).lower(
            q, kv, kv).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _ssd_shapes(one_chip, bs, s, h, p, n):
    return (_shape((bs, s, h * p), jnp.bfloat16, one_chip),
            _shape((bs, s, h), jnp.float32, one_chip),
            _shape((h,), jnp.float32, one_chip),
            _shape((bs, s, n), jnp.bfloat16, one_chip),
            _shape((bs, s, n), jnp.bfloat16, one_chip))


def test_ssd_chunk_intra_compiles_at_mamba2_widths(one_chip):
    """mamba2-780m: 48 heads of dim 64, state 128, chunk 512."""
    from repro.kernels.ssd_scan import ssd_chunk_intra
    compiled = jax.jit(
        lambda x, dt, a, b, c: ssd_chunk_intra(
            x, dt, a, b, c, chunk=512, interpret=False)).lower(
        *_ssd_shapes(one_chip, 1, 2048, 48, 64, 128)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("widths", [
    (48, 64, 128, 512),     # mamba2-780m: heads, head_dim, state, chunk
    (64, 64, 64, 256),      # zamba2-1.2b's Mamba2 layers
])
def test_ssd_kernels_compile_forward_and_backward(one_chip, widths):
    """Both SSD kernels, as one layer's gradient runs them at S 2048: the
    forward and the backward fit v5e's VMEM and tiling."""
    from repro.kernels.ssd_scan import kernel_fits, ssd_chunk_intra
    h, p, n, q = widths
    assert kernel_fits(h, p, n, q)

    def loss(x, dt, a, b, c):
        y, states, cum = ssd_chunk_intra(x, dt, a, b, c, chunk=q)
        return (jnp.sum(y.astype(jnp.float32)) + jnp.sum(states)
                + jnp.sum(cum))

    step = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(step).lower(
        *_ssd_shapes(one_chip, 1, 2048, h, p, n)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2


# ---------------------------------------------------------------------- #
# tree-pipeline collectives under jax.shard_map over 4 described chips
# ---------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def ctx4():
    from repro.comms import CollectiveContext
    return CollectiveContext({"data": 4})


def _compile_on_mesh(fn, mesh, shape):
    sm = jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))
    arg = _shape(shape, jnp.float32, NamedSharding(mesh, P("data")))
    return jax.jit(sm).lower(arg).compile().as_text()


def test_tree_all_reduce_compiles_on_4_chips(mesh4, ctx4):
    from repro.comms import tree_all_reduce
    ax = ctx4.axis("data")
    hlo = _compile_on_mesh(
        lambda v: tree_all_reduce(v[0], ax.rs_prog, ax.ag_prog,
                                  "data")[None],
        mesh4, (4, 1 << 20))
    assert "collective-permute" in hlo and "all-reduce" not in hlo


def test_tree_all_gather_compiles_on_4_chips(mesh4, ctx4):
    from repro.comms import tree_all_gather
    prog = ctx4.axis("data").ag_prog
    hlo = _compile_on_mesh(
        lambda v: tree_all_gather(v[0], prog, "data")[None],
        mesh4, (4, 1 << 18))
    assert "collective-permute" in hlo and "all-gather" not in hlo


def test_tree_all_to_all_compiles_on_4_chips(mesh4, ctx4):
    from repro.comms import tree_all_to_all
    prog = ctx4.alltoall_program("data")
    hlo = _compile_on_mesh(
        lambda v: tree_all_to_all(v, prog, "data"),
        mesh4, (16, 1 << 16))
    assert "collective-permute" in hlo and "all-to-all" not in hlo
