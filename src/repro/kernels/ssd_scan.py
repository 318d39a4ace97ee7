"""Pallas TPU kernels for the Mamba2 SSD intra-chunk block, forward and
backward.

Per chunk of Q steps and per head (x_j [P], B_j, C_j [N] shared by every
head, dt_j > 0, A < 0):

    cum[i]   = sum_{t<=i} dt[t]·A                              (f32)
    L[i,j]   = exp(cum[i] - cum[j])   (i >= j, else 0)          [Q, Q]
    y[i]     = sum_j (C[i]·B[j]) * L[i,j] * dt[j]·x[j]          [Q, P]
    state    = sum_j exp(cum[Q-1] - cum[j]) * dt[j]·x[j] ⊗ B[j]  [P, N]

`ssd_chunk_intra` returns (y, per-chunk states, cum); the inter-chunk
recurrence and the read-out of the entering state stay in XLA
(`repro.models.ssm.ssd_chunked`), which reads `cum` instead of taking a
cumulative sum of its own.  Its gradient is a second kernel
(`jax.custom_vjp`, residuals: the inputs alone), so no [H, Q, Q] decay or
score tensor reaches HBM in any pass.

Grid: (batch, chunk, head block).  x is [B, S, H·P], each head's P lanes
side by side; a head block is up to 256 lanes (four heads of P 64) in
128-lane groups.  B and C are indexed by chunk only, so one copy serves
every head.  In VMEM each block holds the chunk's cumulative sum (shifted
adds along lanes), C·Bᵀ one 128x128 tile at a time, and the decay
matrix, built only on the diagonal tiles: the decay between two tiles
factors through the tiles' ends (`_Chunk`), so tiles below the diagonal
are scaled products and tiles above it are skipped.  Precision: bf16
operands into the MXU (f32 when the inputs are f32) with f32
accumulation; the cumulative sum, decay exponents and states in f32.  The
backward rebuilds cum, L and the scores the same way and sums dB and dC
over the head blocks into one f32 block (the head axis is `arbitrary`).

Validated in interpret mode against `ref.ssd_chunk_reference` and
against the XLA path of `ssd_chunked`, gradients included
(tests/test_kernels.py); tests/test_chip_compile.py compiles both kernels
for v5e at the models' widths.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
TILE = 128                                   # the decay matrix's tile
LANES = 256                                  # a head block's lanes, at most
# The shortest chunk that takes the kernels (`repro.models.ssm.
# ssd_kernel_fits`).  On a v5e one layer alone runs faster in XLA at any
# chunk (forward + backward 1.2x at 512, 1.7x at 256: XLA's [Q, Q] work
# halves with the chunk, the kernels' work per block does not), but in
# the mamba2-780m train step, chunk 512, the kernels spare the relayouts
# XLA's path forces around the layer and the step is 21% shorter.
MIN_CHUNK = 512
_NN = (((1,), (0,)), ((), ()))               # a @ b
_NT = (((1,), (1,)), ((), ()))               # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))               # aᵀ @ b


def heads_per_block(num_heads: int, head_dim: int) -> int:
    """Most heads whose lanes fill whole 128-lane groups, up to LANES; all
    of them if no such count divides the heads."""
    fit = [hb for hb in range(1, num_heads + 1)
           if num_heads % hb == 0 and (hb * head_dim) % 128 == 0
           and hb * head_dim <= LANES]
    return fit[-1] if fit else num_heads


def kernel_fits(num_heads: int, head_dim: int, state_dim: int, chunk: int
                ) -> bool:
    """Whether the compiled kernels take these widths: whole 128-step tiles
    and 128-lane groups, a head block of at most LANES lanes, a state of
    at most 256."""
    w = heads_per_block(num_heads, head_dim) * head_dim
    return (chunk % TILE == 0 and chunk <= 1024 and w % 128 == 0
            and w <= LANES and state_dim <= 256)


def _mm(a, b, dims):
    """MXU product with f32 accumulation (full f32 for f32 operands)."""
    prec = jax.lax.Precision.HIGHEST if a.dtype == F32 else None
    return jax.lax.dot_general(a, b, dims, precision=prec,
                               preferred_element_type=F32)


def _iotas(shape):
    return (jax.lax.broadcasted_iota(jnp.int32, shape, 0),
            jax.lax.broadcasted_iota(jnp.int32, shape, 1))


def _scan(v, reverse=False):
    """Inclusive prefix sums along the lanes of v [R, Q] (suffix sums if
    `reverse`), in log2(Q) shifted adds (Hillis-Steele)."""
    q = v.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    k = 1
    while k < q:
        if reverse:      # lane i takes v[i + k]
            v = v + jnp.where(lane < q - k, pltpu.roll(v, q - k, 1), 0.0)
        else:            # lane i takes v[i - k]
            v = v + jnp.where(lane >= k, pltpu.roll(v, k, 1), 0.0)
        k *= 2
    return v


class _Chunk:
    """What both kernels derive from one block's inputs.

    The decay between tiles factors through the tiles' ends: for i in row
    tile I and j in an earlier tile J, with R_K the cumulative sum at the
    end of tile K,

        L[i,j] = exp(cum[i] - R[I-1]) · exp(R[I-1] - R[J]) · exp(R[J] - cum[j])
               =      into[i]       ·       gap[I,J]      ·     out[j]

    and every factor is at most 1.  So only the diagonal tiles build L;
    the others scale rows of dt·x or of a gradient, and one product with
    C·Bᵀ serves every head of the block.  On the diagonal, a product
    with a whole 128-lane group of dt·x gives one head's lanes; a select
    keeps them."""

    def __init__(self, a_ref, x_ref, dt_ref, cum_ref, *, hb, p, t):
        q, w = x_ref.shape[1], x_ref.shape[2]
        self.q, self.w, self.hb, self.t, self.nt = q, w, hb, t, q // t
        self.cdt = x_ref.dtype
        # 128-lane groups of whole heads, else one group of every lane
        gw = 128 if w % 128 == 0 and 128 % p == 0 else w
        self.groups = [slice(g * gw, (g + 1) * gw) for g in range(w // gw)]
        self.heads_per_group = gw // p
        self.a = a_ref[0]                                     # [hb, 1]
        self.dt = dt_ref[0, 0]                                # [hb, Q]
        # in-chunk inclusive cumulative sum of dt·A (f32)
        cum_ref[...] = _scan(self.dt * self.a)                # [hb, Q]
        self.cum_ref = cum_ref
        self.cumT = cum_ref[...].T                            # [Q, hb]
        self.lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1) // p
        self.dt_lanes = self.lanes(self.dt.T)                 # [Q, W]
        self.cum_lanes = self.lanes(self.cumT)                # [Q, W]
        self.xdt = x_ref[0].astype(F32) * self.dt_lanes       # [Q, W]
        self.xdt_b = self.xdt.astype(self.cdt)                # MXU operand
        self.xdt_c = self.xdt_b.astype(F32)                   # its value
        self.causal = _iotas((t, t))[0] >= _iotas((t, t))[1]
        # state decay exp(cum[Q-1] - cum[j])
        self.wdec = jnp.exp(self.end(self.nt - 1) - self.cum_lanes)
        # dt·x scaled out to its tile's end, by tile
        self.z = [(self.xdt[self.rows(j)] * self.out_of(j)).astype(self.cdt)
                  for j in range(self.nt)]

    def rows(self, i):
        return slice(i * self.t, (i + 1) * self.t)

    def end(self, i):
        """R[i] over each head's lanes, [1, W]."""
        return self.cum_lanes[(i + 1) * self.t - 1:(i + 1) * self.t, :]

    def into(self, i):
        """exp(cum[i] - R[I-1]) over tile I's rows, [t, W]."""
        return jnp.exp(self.cum_lanes[self.rows(i)] - self.end(i - 1))

    def out_of(self, j):
        """exp(R[J] - cum[j]) over tile J's rows, [t, W]."""
        return jnp.exp(self.end(j) - self.cum_lanes[self.rows(j)])

    def gap(self, i, j):
        """exp(R[I-1] - R[J]), [1, W]."""
        return jnp.exp(self.end(i - 1) - self.end(j))

    def lanes(self, cols):
        """[R, hb] per-head columns -> [R, W], each over its head's lanes
        (built a 128-lane group at a time)."""
        parts = []
        for g, lanes in enumerate(self.groups):
            heads = self.heads(g)
            out = jnp.broadcast_to(cols[:, heads[0]:heads[0] + 1],
                                   (cols.shape[0], lanes.stop - lanes.start))
            for k in heads[1:]:
                out = jnp.where(self.lane_head[:, lanes] == k,
                                cols[:, k:k + 1], out)
            parts.append(out)
        return _lanes_concat(parts)

    def head_sums(self, v):
        """[R, W] -> [R, hb]: each row's sum over each head's lanes."""
        hb_idx = jax.lax.broadcasted_iota(jnp.int32, (1, self.hb), 1)
        out = jnp.zeros((v.shape[0], self.hb), F32)
        for g, lanes in enumerate(self.groups):
            vg = v[:, lanes]
            for k in self.heads(g):
                s = jnp.sum(jnp.where(self.lane_head[:, lanes] == k, vg, 0.0),
                            axis=1, keepdims=True)
                out = jnp.where(hb_idx == k, s, out)
        return out

    def diagonal(self, i, k):
        """L's diagonal tile i for head k, f32 [t, t]."""
        rows = self.rows(i)
        diff = (self.cumT[rows, k:k + 1] - self.cum_ref[k:k + 1, rows])
        return jnp.exp(jnp.where(self.causal, diff, -jnp.inf))

    def by_head(self, g, products):
        """Per-head products over lane group g (one per head of the group,
        in order) -> the group's lanes, each from its own head's."""
        lanes = self.lane_head[:, self.groups[g]]
        first = g * self.heads_per_group
        out = products[0]
        for k, prod in enumerate(products[1:], first + 1):
            out = jnp.where(lanes == k, prod, out)
        return out

    def heads(self, g):
        return range(g * self.heads_per_group, (g + 1) * self.heads_per_group)


def _lanes_concat(parts):
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _fwd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref,
                y_ref, st_ref, cum_ref, *, hb, p, t):
    ch = _Chunk(a_ref, x_ref, dt_ref, cum_ref.at[0, 0], hb=hb, p=p, t=t)
    for ti in range(ch.nt):
        rows = ch.rows(ti)
        c_i = c_ref[0, rows, :]
        s = _mm(c_i, b_ref[0, rows, :], _NT)                   # [t, t]
        parts = []
        for g, lanes in enumerate(ch.groups):
            xg = ch.xdt_b[rows, lanes]
            parts.append(ch.by_head(g, [
                _mm((s * ch.diagonal(ti, k)).astype(ch.cdt), xg, _NN)
                for k in ch.heads(g)]))
        y = _lanes_concat(parts)
        if ti:
            off = jnp.zeros((t, ch.w), F32)
            for tj in range(ti):
                s = _mm(c_i, b_ref[0, ch.rows(tj), :], _NT).astype(ch.cdt)
                off = off + _mm(s, ch.z[tj], _NN) * ch.gap(ti, tj)
            y = y + off * ch.into(ti)
        y_ref[0, rows, :] = y.astype(y_ref.dtype)
    z = (ch.xdt * ch.wdec).astype(ch.cdt)                      # [Q, W]
    st_ref[0, 0] = _mm(z, b_ref[0], _TN)                       # [W, N]


def _bwd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, gy_ref, gst_ref,
                gcum_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                cum_buf, *, hb, p, t):
    ch = _Chunk(a_ref, x_ref, dt_ref, cum_buf, hb=hb, p=p, t=t)
    q, w, nt, cdt = ch.q, ch.w, ch.nt, ch.cdt
    n = b_ref.shape[2]
    gy_b = gy_ref[0]                                           # [Q, W]
    gy = gy_b.astype(F32)
    # d(cum)'s terms, whose rows and columns must cancel as the terms of
    # dL∘L do: each pair is formed from the same rounded MXU operands
    rowdot = [None] * nt            # gy·(dL∘L's rows), by tile, [t, W]
    dxdt = [None] * nt                                   # diagonal tiles
    dx_off = [jnp.zeros((t, w), F32) for _ in range(nt)]  # before out_of
    dbs = [jnp.zeros((t, n), F32) for _ in range(nt)]
    dcs = []
    for ti in range(nt):
        rows = ch.rows(ti)
        c_i = c_ref[0, rows, :]
        b_i = b_ref[0, rows, :]
        s = _mm(c_i, b_i, _NT)                                 # [t, t]
        ds = jnp.zeros((t, t), F32)
        ys, dxs = [], []
        for g, lanes in enumerate(ch.groups):
            xg, gyg = ch.xdt_b[rows, lanes], gy_b[rows, lanes]
            lane_head = ch.lane_head[:, lanes]
            yk, dxk = [], []
            for k in ch.heads(g):
                ll = ch.diagonal(ti, k)
                m = (s * ll).astype(cdt)
                yk.append(_mm(m, xg, _NN))
                dxk.append(_mm(m, gyg, _TN))
                gyk = jnp.where(lane_head == k, gy[rows, lanes], 0.0)
                ds = ds + _mm(gyk.astype(cdt), xg, _NT) * ll
            ys.append(ch.by_head(g, yk))
            dxs.append(ch.by_head(g, dxk))
        dxdt[ti] = _lanes_concat(dxs)
        ds = ds.astype(cdt)                                    # Σ heads
        dc_i = _mm(ds, b_i, _NN)
        dbs[ti] = dbs[ti] + _mm(ds, c_i, _TN)
        rowdot[ti] = gy[rows] * _lanes_concat(ys)
        if ti:
            g_i = (gy[rows] * ch.into(ti)).astype(cdt)         # [t, W]
            off = jnp.zeros((t, w), F32)
            for tj in range(ti):
                b_j = b_ref[0, ch.rows(tj), :]
                s = _mm(c_i, b_j, _NT).astype(cdt)
                gap = ch.gap(ti, tj)                           # [1, W]
                off = off + _mm(s, ch.z[tj], _NN) * gap
                dx_off[tj] = dx_off[tj] + _mm(s, g_i, _TN) * gap
                ds = _mm((g_i.astype(F32) * gap).astype(cdt), ch.z[tj],
                         _NT).astype(cdt)                      # Σ heads
                dc_i = dc_i + _mm(ds, b_j, _NN)
                dbs[tj] = dbs[tj] + _mm(ds, c_i, _TN)
            rowdot[ti] = rowdot[ti] + g_i.astype(F32) * off
        dcs.append(dc_i)
    # the states' share: d(dt·x) and dB, and d(cum) through their decay
    gst = gst_ref[0, 0].astype(cdt)                            # [W, N]
    bg = ch.wdec * _mm(b_ref[0], gst, _NT)                     # [Q, W]
    dxdt_all = jnp.concatenate(
        [dxdt[j] + dx_off[j] * ch.out_of(j) for j in range(nt)], axis=0) + bg
    dx_ref[0] = (dxdt_all * ch.dt_lanes).astype(dx_ref.dtype)
    z = (ch.xdt * ch.wdec).astype(cdt)
    db = jnp.concatenate(dbs, axis=0) + _mm(z, gst, _NN)
    # d(cum): rows of dL∘L sum to gy·y, its columns to (dt·x)·d(dt·x); the
    # states add their decay's share, at Q-1 and at each step
    coldot = jnp.concatenate(
        [ch.xdt_c[ch.rows(j)] * dxdt[j] + ch.z[j].astype(F32) * dx_off[j]
         for j in range(nt)], axis=0)
    by_state = ch.head_sums(ch.xdt_c * bg).T                   # [hb, Q]
    dcum = (ch.head_sums(jnp.concatenate(rowdot, axis=0) - coldot).T
            - by_state + gcum_ref[0, 0])                       # [hb, Q]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    dcum = dcum + jnp.where(lane == q - 1,
                            jnp.sum(by_state, axis=1, keepdims=True), 0.0)
    # d(dt·A)[t] = Σ_{i>=t} d(cum)[i]
    dda = _scan(dcum, reverse=True)                            # [hb, Q]
    x32 = x_ref[0].astype(F32)
    ddt_ref[0, 0] = dda * ch.a + ch.head_sums(x32 * dxdt_all).T
    da_ref[0, 0, 0] = jnp.sum(dda * ch.dt, axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    db_ref[0] += db
    dc_ref[0] += jnp.concatenate(dcs, axis=0)


def _layout(dt, a, hb):
    """dt [B,S,H] -> [B,G,hb,S]; a [H] -> [G,hb,1]."""
    bs, s, h = dt.shape
    g = h // hb
    dt_r = jnp.swapaxes(dt.astype(F32), 1, 2).reshape(bs, g, hb, s)
    return dt_r, a.astype(F32).reshape(g, hb, 1)


def _specs(bs, s, h, p, n, chunk, hb):
    l, g, w = s // chunk, h // hb, hb * p
    lanes = pl.BlockSpec((1, chunk, w), lambda b, i, j: (b, i, j))
    rows = pl.BlockSpec((1, 1, hb, chunk), lambda b, i, j: (b, j, 0, i))
    seq = pl.BlockSpec((1, chunk, n), lambda b, i, j: (b, i, 0))
    state = pl.BlockSpec((1, 1, w, n), lambda b, i, j: (b, i, j, 0))
    a = pl.BlockSpec((1, hb, 1), lambda b, i, j: (j, 0, 0))
    return (bs, l, g), dict(lanes=lanes, rows=rows, seq=seq, state=state,
                            a=a)


def _forward(x, dt, a, b, c, chunk, tile, interpret):
    (bs, s, w), h, n = x.shape, dt.shape[-1], b.shape[-1]
    p = w // h
    hb = heads_per_block(h, p)
    grid, sp = _specs(bs, s, h, p, n, chunk, hb)
    dt_r, a_r = _layout(dt, a, hb)
    y, st, cum = pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p, t=tile),
        grid=grid,
        in_specs=[sp["a"], sp["lanes"], sp["rows"], sp["seq"], sp["seq"]],
        out_specs=[sp["lanes"], sp["state"], sp["rows"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bs, s // chunk, w, n), F32),
            jax.ShapeDtypeStruct(dt_r.shape, F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(a_r, x, dt_r, b, c)
    return y, st.reshape(bs, s // chunk, h, p, n), cum.reshape(bs, h, s)


def _backward(x, dt, a, b, c, gy, gst, gcum, chunk, tile, interpret):
    (bs, s, w), h, n = x.shape, dt.shape[-1], b.shape[-1]
    p = w // h
    hb = heads_per_block(h, p)
    grid, sp = _specs(bs, s, h, p, n, chunk, hb)
    l, g = s // chunk, h // hb
    dt_r, a_r = _layout(dt, a, hb)
    dx, ddt, da, db, dc = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p, t=tile),
        grid=grid,
        in_specs=[sp["a"], sp["lanes"], sp["rows"], sp["seq"], sp["seq"],
                  sp["lanes"], sp["state"], sp["rows"]],
        out_specs=[sp["lanes"], sp["rows"],
                   pl.BlockSpec((1, 1, 1, hb, 1),
                                lambda b, i, j: (b, i, j, 0, 0)),
                   sp["seq"], sp["seq"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt_r.shape, F32),
            jax.ShapeDtypeStruct((bs, l, g, hb, 1), F32),
            jax.ShapeDtypeStruct(b.shape, F32),
            jax.ShapeDtypeStruct(c.shape, F32),
        ],
        scratch_shapes=[pltpu.VMEM((hb, chunk), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a_r, x, dt_r, b, c, gy.astype(x.dtype),
      gst.reshape(bs, l, w, n).astype(F32),
      gcum.reshape(dt_r.shape).astype(F32))
    ddt = jnp.swapaxes(ddt.reshape(bs, h, s), 1, 2)
    return (dx, ddt.astype(dt.dtype),
            da.sum(axis=(0, 1)).reshape(h).astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _intra(x, dt, a, b, c, chunk, tile, interpret):
    return _forward(x, dt, a, b, c, chunk, tile, interpret)


def _intra_fwd(x, dt, a, b, c, chunk, tile, interpret):
    return (_forward(x, dt, a, b, c, chunk, tile, interpret),
            (x, dt, a, b, c))


def _intra_bwd(chunk, tile, interpret, res, g):
    gy, gst, gcum = g
    return _backward(*res, gy, gst, gcum, chunk, tile, interpret)


_intra.defvjp(_intra_fwd, _intra_bwd)


def ssd_chunk_intra(x: jax.Array, dt: jax.Array, a: jax.Array,
                    b: jax.Array, c: jax.Array, *, chunk: int,
                    interpret: bool = False
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The SSD's intra-chunk block, differentiable.

    x: [B,S,H·P] (compute dtype; each head's P lanes side by side, as the
    Mamba2 projection lays them out), dt: [B,S,H] (> 0), a: [H] (< 0),
    b, c: [B,S,N] (one group, shared by every head; x's dtype).
    Returns (y_diag [B,S,H·P] in x's dtype, states [B,S/chunk,H,P,N] f32:
    each chunk's terminal state from a zero start, cum [B,H,S] f32: the
    in-chunk inclusive cumulative sum of dt·a).  The decay matrix's tile
    is TILE, or the chunk if shorter."""
    s = x.shape[1]
    tile = min(TILE, chunk)
    if s % chunk or chunk % tile or x.shape[2] % dt.shape[2]:
        raise ValueError(f"seq {s}, chunk {chunk} and tile {tile} must "
                         f"nest, and x's lanes split into dt's heads")
    return _intra(x, dt, a, b, c, chunk, tile, interpret)
