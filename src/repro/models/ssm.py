"""Mamba2 (state-space duality / SSD) — chunked parallel scan + O(1) decode.

The SSD recurrence per head (state [P, N], input x_t [P], B_t, C_t [N]):

    h_t = exp(Δ_t A) · h_{t-1} + Δ_t · x_t ⊗ B_t
    y_t = h_t @ C_t + D · x_t

Training/prefill uses the chunked block decomposition from the Mamba2 paper
(intra-chunk quadratic attention-like term + inter-chunk state recurrence,
`lax.scan` over chunks), giving O(S·Q) work and exact equality with the
naive recurrence (tested).  Decode keeps (conv_state, ssm_state) per layer —
constant memory in sequence length, which is why mamba2 runs the
long_500k cell.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ssd_scan
from .common import ModelConfig, Params, dense_init, rms_norm


def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(d_inner, num_heads, head_dim, state_dim)."""
    din = cfg.ssm_expand * cfg.d_model
    p = cfg.ssm_head_dim
    h = cfg.ssm_num_heads or din // p
    return din, h, p, cfg.ssm_state_dim


def init_mamba2(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    din, h, p, n = ssm_dims(cfg)
    d = cfg.d_model
    conv_dim = din + 2 * n                       # x, B, C share the conv
    ks = jax.random.split(key, 6)
    return {
        "in_proj": dense_init(ks[0], (d, 2 * din + 2 * n + h), dtype),
        "conv_w": dense_init(ks[1], (cfg.ssm_conv_width, conv_dim), dtype,
                             scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.zeros((h,), dtype),         # A = -exp(A_log) = -1 init
        "D": jnp.ones((h,), dtype),
        "dt_bias": jnp.zeros((h,), dtype),
        "norm_w": jnp.zeros((din,), dtype),
        "out_proj": dense_init(ks[2], (din, d), dtype),
    }


def _segsum(x: jax.Array) -> jax.Array:
    """x: [..., Q, H] -> [..., H, Q, Q] lower-triangular pairwise sums:
    out[i, j] = sum_{j < t <= i} x[t]  (i >= j), -inf above diagonal."""
    q = x.shape[-2]
    cs = jnp.cumsum(x, axis=-2)                               # [..., Q, H]
    diff = cs[..., :, None, :] - cs[..., None, :, :]          # [..., i, j, H]
    diff = jnp.moveaxis(diff, -1, -3)                         # [..., H, i, j]
    mask = jnp.tril(jnp.ones((q, q), bool))
    return jnp.where(mask, diff, -jnp.inf)


def ssd_kernel_fits(x: jax.Array, b: jax.Array, chunk: int) -> bool:
    """Whether `ssd_chunked` runs the Pallas kernels here: a TPU, whole
    chunks of at least `ssd_scan.MIN_CHUNK` steps (shorter ones keep XLA,
    which is faster there), widths the kernels tile, and a computation
    that is not partitioned across devices (a Pallas call cannot be).
    Placement is read from the mesh the scan is traced under: none (a jit
    placed by its arguments alone), a mesh of one device, or a `shard_map`
    body over every axis.  A program GSPMD partitions over several devices
    is traced under its mesh (`jax.sharding.use_abstract_mesh`, as
    `repro.launch.train` does) and keeps XLA."""
    if (jax.default_backend() != "tpu" or x.shape[1] % chunk
            or chunk < ssd_scan.MIN_CHUNK):
        return False
    mesh = jax.sharding.get_abstract_mesh()
    if not (mesh.empty or mesh.size == 1 or mesh.are_all_axes_manual):
        return False
    _, _, h, p = x.shape
    return ssd_scan.kernel_fits(h, p, b.shape[-1], chunk)


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int,
                init_state: Optional[jax.Array] = None,
                impl: Optional[str] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Chunked SSD.
    x: [B,S,H,P], dt: [B,S,H] (>0), a: [H] (<0), b,c: [B,S,N].
    Returns (y [B,S,H,P], final_state [B,H,P,N] float32).

    The intra-chunk block runs as the Pallas kernels of
    `repro.kernels.ssd_scan` where `ssd_kernel_fits`, else in XLA.  `impl`
    forces a path: "xla", "kernel", or "interpret" (the kernels under the
    Pallas interpreter, for tests off the chip).  Each call is counted by
    path at trace time (`obs.count_ssd`).

    Mixed precision: decay terms (exp/cumsum) and the inter-chunk state
    CARRY stay float32; the large intra-chunk products run in the input
    dtype (bf16 in training) with f32 accumulation.  The XLA path also
    emits the per-chunk states in the input dtype; the kernels emit them
    in f32."""
    if impl is None:
        impl = "kernel" if ssd_kernel_fits(x, b, chunk) else "xla"
    obs.count_ssd("xla" if impl == "xla" else "kernel")
    if impl != "xla":
        return _ssd_chunked_kernel(x, dt, a, b, c, chunk, init_state,
                                   interpret=impl == "interpret")
    return _ssd_chunked_xla(x, dt, a, b, c, chunk, init_state)


def _inter_chunk(states, chunk_decay, init_state, cdt):
    """Inter-chunk recurrence: states [B,L,H,P,N], chunk_decay [B,L,H] f32.
    Returns (final f32, entering [B,L,H,P,N] in cdt: each chunk's state
    before it)."""
    bs, _, h, p, n = states.shape
    h0 = init_state.astype(jnp.float32) if init_state is not None else \
        jnp.zeros((bs, h, p, n), jnp.float32)

    def step(carry, inp):
        st, dec = inp                                        # [B,H,P,N],[B,H]
        new = carry * dec[..., None, None] + st.astype(jnp.float32)
        return new, carry.astype(cdt)                        # emit entering

    final, entering = jax.lax.scan(
        step, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    return final, jnp.moveaxis(entering, 0, 1)


def _ssd_chunked_kernel(x, dt, a, b, c, chunk, init_state, interpret):
    bs, s, h, p = x.shape
    n, w = b.shape[-1], h * p
    cdt = x.dtype
    l = s // chunk
    c = c.astype(cdt)
    # heads side by side in the lanes, as the projection made them: no
    # relayout of x, y or their gradients around the kernels
    with jax.named_scope(obs.SSD_KERNEL):
        y_diag, states, cum = ssd_scan.ssd_chunk_intra(
            x.reshape(bs, s, w), dt.astype(jnp.float32),
            a.astype(jnp.float32), b.astype(cdt), c, chunk=chunk,
            interpret=interpret)
    cum = cum.reshape(bs, h, l, chunk)                        # f32
    final, entering = _inter_chunk(
        states, jnp.moveaxis(jnp.exp(cum[..., -1]), 1, 2), init_state, cdt)
    # off-diagonal: prior state read out through intra-chunk decay, in the
    # lanes' layout (an exact 0/1 product spreads each head's decay over
    # its lanes: a broadcast there would be relaid out)
    y_off = jnp.einsum("blqn,blnw->blqw", c.reshape(bs, l, chunk, n),
                       jnp.swapaxes(entering.reshape(bs, l, w, n), 2, 3),
                       preferred_element_type=jnp.float32)
    spread = (jnp.arange(w)[None, :] // p == jnp.arange(h)[:, None])
    decay = jnp.einsum("blqh,hw->blqw",
                       jnp.exp(jnp.moveaxis(cum, 1, 3)).astype(cdt),
                       spread.astype(cdt),
                       precision=jax.lax.Precision.HIGHEST)
    y = y_diag + (y_off.astype(cdt) * decay).reshape(bs, s, w)
    return y.reshape(bs, s, h, p), final


def _ssd_chunked_xla(x, dt, a, b, c, chunk, init_state):
    bs, s, h, p = x.shape
    n = b.shape[-1]
    cdt = x.dtype                                             # compute dtype
    assert s % chunk == 0, f"seq {s} not divisible by chunk {chunk}"
    l = s // chunk
    dt = dt.astype(jnp.float32)
    xdt = x * dt[..., None].astype(cdt)                       # [B,S,H,P]
    da = dt * a[None, None, :].astype(jnp.float32)            # [B,S,H]

    def r(t, shape):  # reshape seq into chunks
        return t.reshape((bs, l, chunk) + shape)

    x_c, da_c = r(xdt, (h, p)), r(da, (h,))
    b_c, c_c = r(b.astype(cdt), (n,)), r(c.astype(cdt), (n,))
    da_cs = jnp.cumsum(da_c, axis=2)                          # [B,L,Q,H] f32

    # 1. intra-chunk (diagonal blocks)
    ll = jnp.exp(_segsum(da_c)).astype(cdt)                   # [B,L,H,Q,Q]
    scores = jnp.einsum("blqn,blkn->blqk", c_c, b_c)          # [B,L,Q,K]
    y_diag = jnp.einsum("blqk,blhqk,blkhp->blqhp",
                        scores, ll, x_c)

    # 2. per-chunk terminal states
    decay_states = jnp.exp(da_cs[:, :, -1:, :] - da_cs).astype(cdt)
    states = jnp.einsum("blqn,blqh,blqhp->blhpn",
                        b_c, decay_states, x_c)               # [B,L,H,P,N]

    # 3. inter-chunk recurrence (f32 carry; emits in compute dtype)
    chunk_decay = jnp.exp(da_cs[:, :, -1, :])                 # [B,L,H] f32
    final, entering = _inter_chunk(states, chunk_decay, init_state, cdt)

    # 4. off-diagonal: prior state read out through intra-chunk decay
    state_decay = jnp.exp(da_cs).astype(cdt)                  # [B,L,Q,H]
    y_off = jnp.einsum("blqn,blhpn,blqh->blqhp",
                       c_c, entering, state_decay)

    y = (y_diag + y_off).reshape(bs, s, h, p)
    return y, final


def ssd_reference(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                  c: jax.Array,
                  init_state: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """Naive sequential recurrence — the oracle for ssd_chunked."""
    bs, s, h, p = x.shape
    n = b.shape[-1]
    h0 = init_state if init_state is not None else \
        jnp.zeros((bs, h, p, n), x.dtype)

    def step(carry, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * a[None, :])[..., None, None]    # [B,H,1,1]
        upd = (xt * dtt[..., None])[..., None] * bt[:, None, None, :]
        new = carry * decay + upd                             # [B,H,P,N]
        y = jnp.einsum("bhpn,bn->bhp", new, ct)
        return new, y

    xs = (jnp.moveaxis(x, 1, 0), jnp.moveaxis(dt, 1, 0),
          jnp.moveaxis(b, 1, 0), jnp.moveaxis(c, 1, 0))
    final, ys = jax.lax.scan(step, h0, xs)
    return jnp.moveaxis(ys, 0, 1), final


def _causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array,
                 state: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal conv; x: [B,S,C], w: [W,C].  Returns (y, new_state)
    where state is the last W-1 inputs (for decode).

    Perf iteration C2: lax.conv_general_dilated instead of a gathered
    [B,S,W,C] window tensor — the gather (and its scatter transpose in the
    backward) was ~3.6 GB of traffic per layer at 4k seq."""
    width = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], width - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    full = jnp.concatenate([pad, x], axis=1)                  # [B,S+W-1,C]
    if x.shape[1] == 1:
        # decode: one dot against the window
        y = jnp.einsum("bwc,wc->bc", full, w)[:, None, :] + bias
        return jax.nn.silu(y), full[:, -(width - 1):, :]
    y = jax.lax.conv_general_dilated(
        full, w[:, None, :],                 # rhs [W, 1, C] (depthwise)
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[2]) + bias
    return jax.nn.silu(y), full[:, -(width - 1):, :]


def mamba2_forward(p: Params, cfg: ModelConfig, x: jax.Array,
                   state: Optional[Tuple[jax.Array, jax.Array]] = None,
                   ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full Mamba2 mixer.  x: [B,S,d].  state = (conv_state, ssm_state) for
    incremental decode (S small, typically 1).  Returns (out, new_state)."""
    din, h, pdim, n = ssm_dims(cfg)
    conv_state, ssm_state = state if state is not None else (None, None)

    proj = x @ p["in_proj"]                                   # [B,S,...]
    z, xbc, dt_raw = jnp.split(proj, [din, 2 * din + 2 * n], axis=-1)
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))  # [B,S,H]
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs, b, c = jnp.split(xbc, [din, din + n], axis=-1)
    xs = xs.reshape(x.shape[0], x.shape[1], h, pdim)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))

    with jax.named_scope(obs.SSD):
        if x.shape[1] % cfg.ssm_chunk == 0 and x.shape[1] >= cfg.ssm_chunk:
            # intra-chunk math runs in the input dtype (C1: bf16 in training)
            y, new_ssm = ssd_chunked(xs, dt, a, b, c, cfg.ssm_chunk,
                                     ssm_state)
        else:
            y, new_ssm = ssd_reference(xs.astype(jnp.float32), dt, a,
                                       b.astype(jnp.float32),
                                       c.astype(jnp.float32), ssm_state)
    y = y.astype(jnp.float32) \
        + xs.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(x.shape[0], x.shape[1], din).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["norm_w"], cfg.norm_eps)
    return y @ p["out_proj"], (new_conv, new_ssm)


def init_ssm_state(cfg: ModelConfig, batch: int, dtype=jnp.float32
                   ) -> Tuple[jax.Array, jax.Array]:
    din, h, pdim, n = ssm_dims(cfg)
    conv = jnp.zeros((batch, cfg.ssm_conv_width - 1, din + 2 * n), dtype)
    ssm = jnp.zeros((batch, h, pdim, n), jnp.float32)
    return conv, ssm
