"""Pallas kernel validation in interpret mode: shape/dtype sweeps against
the pure-jnp oracles in repro.kernels.ref."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_accum import chunk_accum
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ref import chunk_accum_reference, mha_reference

KEY = jax.random.PRNGKey(7)


def qkv(b, h, hkv, s, d, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, h, s, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, hkv, s, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, hkv, s, d)).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("shape", [
    (1, 2, 2, 128, 16),    # MHA
    (2, 4, 2, 256, 32),    # GQA
    (1, 4, 1, 128, 64),    # MQA
    (2, 2, 2, 512, 16),    # longer seq
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_shapes_dtypes(shape, dtype):
    b, h, hkv, s, d = shape
    q, k, v = qkv(b, h, hkv, s, d, dtype)
    got = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True)
    ref = mha_reference(q, k, v)
    atol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=atol)


@pytest.mark.parametrize("kwargs", [
    dict(causal=False),
    dict(causal=True, window=64),
    dict(causal=True, prefix_len=32),
    dict(causal=True, logit_cap=50.0),
    dict(causal=True, window=96, logit_cap=30.0),
])
def test_flash_attention_mask_variants(kwargs):
    q, k, v = qkv(2, 4, 2, 256, 32, jnp.float32)
    got = flash_attention(q, k, v, block_q=64, block_kv=64, interpret=True,
                          **kwargs)
    ref = mha_reference(q, k, v, **kwargs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)


def test_flash_attention_block_invariance():
    q, k, v = qkv(1, 2, 2, 256, 32, jnp.float32)
    a = flash_attention(q, k, v, block_q=32, block_kv=64, interpret=True)
    b = flash_attention(q, k, v, block_q=128, block_kv=128, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("shape", [(8, 512), (16, 1024), (32, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.float16])
def test_chunk_accum_sweep(shape, dtype):
    n, c = shape
    acc = jax.random.normal(KEY, (n, c), jnp.float32)
    upd = jax.random.normal(jax.random.PRNGKey(3), (n, c)).astype(dtype)
    got = chunk_accum(acc, upd, interpret=True)
    ref = chunk_accum_reference(acc, upd)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-6)


def test_flash_hook_in_models():
    """The kernel can be registered as the models' attention impl and
    produces the same result as the jnp path."""
    from repro.kernels.ops import enable_flash_in_models, \
        disable_flash_in_models
    from repro.models.attention import attend, MaskSpec
    b, s, h, hkv, d = 1, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    pos = jnp.arange(s)
    base = attend(q, k, v, pos, pos, MaskSpec(causal=True))
    enable_flash_in_models()
    try:
        got = attend(q, k, v, pos, pos, MaskSpec(causal=True))
    finally:
        disable_flash_in_models()
    np.testing.assert_allclose(np.asarray(got), np.asarray(base), atol=1e-5)


# ---------------------------------------------------------------------- #
# SSD intra-chunk kernel
# ---------------------------------------------------------------------- #

@pytest.mark.parametrize("shape", [(2, 64, 8, 16, 32), (3, 128, 16, 32, 32),
                                   (1, 256, 32, 16, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_chunk_kernel(shape, dtype):
    """(heads, seq, head_dim, state, chunk): every chunk's y, terminal state
    and cumulative sum against the single-chunk oracle; B and C are one
    group shared by the heads."""
    from repro.kernels.ssd_scan import ssd_chunk_intra
    from repro.kernels.ref import ssd_chunk_reference
    h, s, p, n, q = shape
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (1, s, h, p)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (1, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    b = jax.random.normal(ks[3], (1, s, n)).astype(dtype)
    c = jax.random.normal(ks[4], (1, s, n)).astype(dtype)
    y, states, cum = ssd_chunk_intra(x.reshape(1, s, h * p), dt, a, b, c,
                                     chunk=q, interpret=True)
    y = y.reshape(x.shape)
    assert states.shape == (1, s // q, h, p, n) and cum.shape == (1, h, s)
    atol = 1e-4 if dtype == jnp.float32 else 0.35
    for j in range(s // q):
        sl = slice(j * q, (j + 1) * q)
        xj, dtj = x[0, sl].astype(jnp.float32), dt[0, sl]
        bj, cj = b[0, sl].astype(jnp.float32), c[0, sl].astype(jnp.float32)
        ref = ssd_chunk_reference(xj, dtj, a, bj, cj)
        np.testing.assert_allclose(
            np.asarray(y[0, sl], np.float32), np.asarray(ref),
            atol=atol, rtol=0.1)
        cs = jnp.cumsum(dtj * a, axis=0)                       # [Q, H]
        np.testing.assert_allclose(np.asarray(cum[0, :, sl]),
                                   np.asarray(cs.T), rtol=1e-5, atol=1e-5)
        ref_st = jnp.einsum("qh,qhp,qn->hpn", jnp.exp(cs[-1] - cs) * dtj,
                            xj, bj)
        np.testing.assert_allclose(np.asarray(states[0, j]),
                                   np.asarray(ref_st), atol=atol, rtol=0.1)


def _ssd_inputs(bs, s, h, p, n, seed=11):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(ks[0], (bs, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (bs, s, h)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (h,)))
    b = 0.5 * jax.random.normal(ks[3], (bs, s, n))
    c = 0.5 * jax.random.normal(ks[4], (bs, s, n))
    h0 = 0.3 * jax.random.normal(ks[5], (bs, h, p, n))
    wy = jax.random.normal(ks[6], (bs, s, h, p))
    return (x, dt, a, b, c), h0, wy


def _ssd_outputs_and_grads(fn, args, h0, wy, dtype):
    """(y, final state) and the gradient of a weighted sum of both with
    respect to x, dt, A, B and C, all in f32."""
    def outputs(x, dt, a, b, c):
        return fn(x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype),
                  h0)

    def loss(*args):
        y, fin = outputs(*args)
        return (jnp.sum(y.astype(jnp.float32) * wy)
                + jnp.sum(fin * jnp.cos(fin.shape[-1] * h0)))
    grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)
    return [np.asarray(o, np.float32) for o in (*outputs(*args), *grads)]


def _rel(u, v):
    return float(np.linalg.norm(u - v) / np.linalg.norm(v))


SSD_NAMES = ("y", "final", "dx", "ddt", "dA", "dB", "dC")


@pytest.mark.parametrize("case", [
    # bs, s, h, p, n, chunk, tile, lanes: 4 chunks of 4 tiles, 4 heads in
    # one 64-lane block, N 64, an entering state
    (2, 128, 4, 16, 64, 32, 8, 512),
    # two 128-lane blocks of two heads (dB and dC summed across them),
    # N 128, 2 chunks of 4 tiles
    (1, 256, 4, 64, 128, 128, 32, 128),
    # one block of two 128-lane groups of two heads
    (1, 128, 4, 64, 64, 64, 16, 256),
])
def test_ssd_kernel_path_matches_xla_and_reference(case, monkeypatch):
    from repro.kernels import ssd_scan
    from repro.models.ssm import ssd_chunked, ssd_reference
    bs, s, h, p, n, q, tile, lanes = case
    monkeypatch.setattr(ssd_scan, "TILE", tile)
    monkeypatch.setattr(ssd_scan, "LANES", lanes)
    args, h0, wy = _ssd_inputs(bs, s, h, p, n)

    def path(impl):
        return lambda x, dt, a, b, c, h0: ssd_chunked(
            x, dt, a, b, c, q, h0, impl=impl)

    got = _ssd_outputs_and_grads(path("interpret"), args, h0, wy,
                                 jnp.float32)
    xla = _ssd_outputs_and_grads(path("xla"), args, h0, wy, jnp.float32)
    ref = _ssd_outputs_and_grads(ssd_reference, args, h0, wy, jnp.float32)
    for name, g, x, r in zip(SSD_NAMES, got, xla, ref):
        assert g.shape == x.shape == r.shape, name
        assert _rel(g, x) < 1e-4, name
        assert _rel(g, r) < 2e-4, name


def test_ssd_kernel_path_bf16_as_close_as_xla(monkeypatch):
    """bf16 compute: the kernels' error against the f32 oracle stays near
    the XLA path's (same precision policy: bf16 MXU operands, f32 decay)."""
    from repro.kernels import ssd_scan
    from repro.models.ssm import ssd_chunked, ssd_reference
    monkeypatch.setattr(ssd_scan, "TILE", 16)
    args, h0, wy = _ssd_inputs(1, 128, 4, 32, 64, seed=5)

    def path(impl):
        return lambda x, dt, a, b, c, h0: ssd_chunked(
            x, dt, a, b, c, 64, h0, impl=impl)

    got = _ssd_outputs_and_grads(path("interpret"), args, h0, wy,
                                 jnp.bfloat16)
    xla = _ssd_outputs_and_grads(path("xla"), args, h0, wy, jnp.bfloat16)
    ref = _ssd_outputs_and_grads(ssd_reference, args, h0, wy, jnp.float32)
    # dA sums every step's share, which cancel: its rounding error swings
    # from seed to seed on both paths (0.001-0.06 here), so within twice the
    # XLA path's or 2%
    for name, g, x, r in zip(SSD_NAMES, got, xla, ref):
        assert _rel(g, r) < max(2 * _rel(x, r), 0.02), name


def test_ssd_path_follows_placement():
    """On a host of four devices (the backend check made to say TPU, and
    nothing lowered): a jit placed on one of them, a mesh of one device and
    a `shard_map` body take the kernels; a program traced under a mesh
    that GSPMD partitions over four keeps XLA."""
    import os
    import subprocess
    import sys
    import textwrap
    from pathlib import Path
    code = """
        import json
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax.sharding import SingleDeviceSharding
        from repro import obs
        from repro.models import ssm

        jax.default_backend = lambda: "tpu"
        bs, s, h, p, n, q = 4, 512, 2, 64, 16, 512
        args = (jnp.zeros((bs, s, h, p), jnp.bfloat16),
                jnp.ones((bs, s, h)), -jnp.ones((h,)),
                jnp.zeros((bs, s, n), jnp.bfloat16),
                jnp.zeros((bs, s, n), jnp.bfloat16))
        four = Mesh(np.array(jax.devices()), ("data",))
        one = Mesh(np.array(jax.devices()[:1]), ("data",))

        def scan(*a):
            return ssm.ssd_chunked(*a, q)[0]

        def under(mesh):
            def f(*a):
                with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                    return scan(*a)
            return f

        def path(fn):
            before = obs.ssd_paths()
            fn.trace(*args)
            return [k for k, v in obs.ssd_paths().items()
                    if v != before.get(k, 0)]

        seq = P("data")
        print(json.dumps({
            "one device of four": path(jax.jit(
                lambda *a: scan(*a),
                in_shardings=SingleDeviceSharding(jax.devices()[1]))),
            "mesh of one device": path(jax.jit(under(one))),
            "shard_map over four": path(jax.jit(jax.shard_map(
                lambda *a: scan(*a), mesh=four,
                in_specs=(seq, seq, P(), seq, seq), out_specs=seq,
                check_vma=False))),
            "GSPMD over four": path(jax.jit(under(four))),
        }))
    """
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(Path(__file__).resolve().parents[1] / "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    assert json.loads(out.stdout.strip().splitlines()[-1]) == {
        "one device of four": ["kernel"],
        "mesh of one device": ["kernel"],
        "shard_map over four": ["kernel"],
        "GSPMD over four": ["xla"],
    }


@pytest.mark.parametrize("case", [
    # S, heads, head_dim, state, chunk -> path on a TPU, one device
    (2048, 48, 64, 128, 512, "kernel"),   # mamba2-780m
    (2048, 64, 64, 64, 256, "xla"),       # zamba2-1.2b: XLA is faster
    (1536, 48, 64, 128, 1024, "xla"),     # not whole chunks
    (2048, 6, 48, 128, 512, "xla"),       # heads fill no 128-lane group
])
def test_ssd_path_follows_shape(case, monkeypatch):
    from repro.models import ssm
    s, h, p, n, q, path = case
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    x = jax.ShapeDtypeStruct((1, s, h, p), jnp.bfloat16)
    b = jax.ShapeDtypeStruct((1, s, n), jnp.bfloat16)
    assert ssm.ssd_kernel_fits(x, b, q) == (path == "kernel")
