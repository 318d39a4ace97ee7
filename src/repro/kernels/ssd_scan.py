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
side by side.  A head block is the widest run of heads that divides the
heads, fills whole 128-lane groups and whose blocks fit `VMEM_BUDGET`
(`heads_per_block`).  B and C are indexed by chunk only, so one copy
serves every head.  A grid step first does what no head changes, once:
the chunk's C·Bᵀ, one 128x128 tile at a time into VMEM, and the block's
cumulative sums (shifted adds along lanes).  Then a rolled loop
(`pl.loop`) visits the block's 128-lane groups, each at its lane offset
in the refs, so the kernel's body is as large at any width.  In a group
the decay matrix is built only on the diagonal tiles: the decay between
two tiles factors through the tiles' ends (`_Group`), so tiles below the
diagonal are scaled products and tiles above it are skipped.  Precision:
bf16 operands into the MXU (f32 when the inputs are f32) with f32
accumulation; the cumulative sum, decay exponents and states in f32.  The
backward rebuilds cum, L and the scores the same way, sums the scores'
gradient over the groups in VMEM, turns it into dB and dC once a grid
step, and sums those over the head blocks into one f32 block (the head
axis is `arbitrary`).

Validated in interpret mode against `ref.ssd_chunk_reference` and
against the XLA path of `ssd_chunked`, gradients included
(tests/test_kernels.py); tests/test_chip_compile.py compiles both kernels
for v5e at the widths `heads_per_block` picks for the models.
"""
from __future__ import annotations

import functools
import math
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs

F32 = jnp.float32
TILE = 128                                   # the decay matrix's tile
GROUP = 128                                  # lanes of one rolled group
#: VMEM a head block's kernels may take (`vmem_bytes`), forward and
#: backward alike; it decides the block's width.  At mamba2-780m's widths
#: 512 lanes fit (backward 11.7 MiB) and 768 do not (13.6 MiB).
VMEM_BUDGET = 12 * 2 ** 20
#: Mosaic's scoped VMEM limit on v5e where a kernel sets none; no kernel
#: here asks for less (`vmem_limit`).
SCOPED_VMEM = 16 * 2 ** 20
# The shortest chunk that takes the kernels (`repro.models.ssm.
# ssd_kernel_fits`).  On a v5e one zamba2-1.2b layer alone (S 2048,
# forward + backward) ran faster in XLA at chunk 256: 0.82 ms against
# 0.96 for the kernels at 512 lanes, read while their group loop was
# still unrolled; the rolled kernels' wider blocks are not measured there
# yet.  In the mamba2-780m train step, chunk 512, the kernels spare the
# relayouts XLA's path forces around the layer and the step is 21%
# shorter.
MIN_CHUNK = 512
_NN = (((1,), (0,)), ((), ()))               # a @ b
_NT = (((1,), (1,)), ((), ()))               # a @ bᵀ
_TN = (((0,), (0,)), ((), ()))               # aᵀ @ b


def _ceil(n: int, m: int) -> int:
    return -(-n // m) * m


def _rows(heads: int) -> int:
    """Rows a block's per-head [heads, Q] arrays take: whole 8-row tiles
    (a block of 12 heads is padded to 16), or 1, 2 or 4."""
    return heads if 8 % heads == 0 else _ceil(heads, 8)


def _buffers(heads: int, head_dim: int, state_dim: int, chunk: int,
             dtype, backward: bool) -> Tuple[List, List]:
    """(blocks, scratch) of one grid step, as (shape, dtype) pairs: the
    blocks each kernel's BlockSpecs move (inputs, then outputs) and the
    VMEM scratch it asks for."""
    q, w, n, r = chunk, heads * head_dim, state_dim, _rows(heads)
    t = min(TILE, chunk)
    nt = q // t
    pairs = max(1, nt * (nt - 1) // 2)         # tiles below the diagonal
    a, rows = ((r, 1), F32), ((r, q), F32)
    lanes, seq = ((q, w), dtype), ((q, n), dtype)
    state = ((w, n), F32)
    scratch = [((2, q, _ceil(r, GROUP)), F32),         # dt, cum as columns
               ((nt, r, t), F32),                      # cum by tile
               ((nt, t, t), F32),                      # C·Bᵀ, diagonal
               ((pairs, t, t), dtype)]                 # below it
    if not backward:
        return [a, lanes, rows, seq, seq, lanes, state, rows], scratch
    blocks = [a, lanes, rows, seq, seq, lanes, state, rows,
              lanes, rows, a, ((q, n), F32), ((q, n), F32)]
    scratch = [rows] + scratch + [
        ((nt, t, t), F32), ((pairs, t, t), F32),
        ((q, n), F32), ((3, q, _ceil(r, GROUP)), F32)]
    return blocks, scratch


def _vmem(shape, dtype) -> int:
    """Bytes of a VMEM buffer: its last two dimensions padded to the
    (sublane, lane) tile of its dtype."""
    size = jnp.dtype(dtype).itemsize
    *lead, r, c = shape
    return math.prod(lead) * _ceil(r, 32 // size) * _ceil(c, 128) * size


#: VMEM for the compiler's own values, in [Q, 128] f32 arrays: twice
#: the most it needed beside the buffers at chunk 512, 256 to 3072 lanes
#: (forward 3, backward 9 at 256 lanes and fewer wider; compiled for v5e)
_WORKING = {False: 6, True: 12}
#: and a flat margin over them: f32 inputs at 1024 lanes, chunk 512,
#: needed 52 KiB past the rest of the reckoning
_MARGIN = 2 ** 20


def vmem_bytes(heads: int, head_dim: int, state_dim: int, chunk: int,
               dtype=jnp.bfloat16, backward: bool = False) -> int:
    """The VMEM one grid step of a kernel takes at this head block: its
    blocks twice (the pipeline's double buffers), its scratch, and the
    compiler's own values.  The kernel passes it as `vmem_limit_bytes`
    (`vmem_limit`)."""
    blocks, scratch = _buffers(heads, head_dim, state_dim, chunk, dtype,
                               backward)
    return (2 * sum(_vmem(*b) for b in blocks)
            + sum(_vmem(*s) for s in scratch)
            + _WORKING[backward] * _vmem((chunk, GROUP), F32) + _MARGIN)


def heads_per_block(num_heads: int, head_dim: int, state_dim: int,
                    chunk: int, dtype=jnp.bfloat16) -> int:
    """The widest head block that divides the heads, fills whole 128-lane
    groups and fits VMEM_BUDGET, forward and backward; the narrowest that
    fills whole groups if none fits, all of the heads if none does."""
    whole = [hb for hb in range(1, min(num_heads, GROUP) + 1)
             if num_heads % hb == 0 and (hb * head_dim) % GROUP == 0]
    fit = [hb for hb in whole if max(
        vmem_bytes(hb, head_dim, state_dim, chunk, dtype, bwd)
        for bwd in (False, True)) <= VMEM_BUDGET]
    return fit[-1] if fit else whole[0] if whole else num_heads


def kernel_fits(num_heads: int, head_dim: int, state_dim: int, chunk: int,
                dtype=jnp.bfloat16) -> bool:
    """Whether the compiled kernels take these widths: whole 128-step tiles,
    chunks of at most 1024, a head block of whole 128-lane groups, a state
    of at most 256."""
    hb = heads_per_block(num_heads, head_dim, state_dim, chunk, dtype)
    return (chunk % TILE == 0 and chunk <= 1024
            and (hb * head_dim) % GROUP == 0 and state_dim <= 256)


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
    """What a grid step derives once, whatever the head: the block's
    cumulative sums, its heads' dt and cum as columns (`cols_ref`, head k
    at lane k) and as rows by tile (`tiles_ref`), and the chunk's C·Bᵀ
    tiles."""

    def __init__(self, a_ref, x_ref, dt_ref, cum_ref, cols_ref, tiles_ref,
                 *, p, t):
        q, w = x_ref.shape[1], x_ref.shape[2]
        self.q, self.w, self.p, self.t, self.nt = q, w, p, t, q // t
        self.x_ref, self.cdt = x_ref, x_ref.dtype
        # 128-lane groups of whole heads, else one group of every lane
        self.gw = GROUP if w % GROUP == 0 and GROUP % p == 0 else w
        self.ng = w // self.gw
        self.a = a_ref[0]                                     # [hb, 1]
        self.dt = dt_ref[0, 0]                                # [hb, Q]
        self.hb = hb = self.dt.shape[0]
        # in-chunk inclusive cumulative sum of dt·A (f32)
        cum_ref[...] = _scan(self.dt * self.a)                # [hb, Q]
        self.cum_ref = cum_ref
        cols_ref[0, :, :hb] = self.dt.T                       # [Q, hb]
        cols_ref[1, :, :hb] = cum_ref[...].T
        for i in range(self.nt):
            tiles_ref[i] = cum_ref[:, self.rows(i)]
        self.cols_ref, self.tiles_ref = cols_ref, tiles_ref
        self.causal = _iotas((t, t))[0] >= _iotas((t, t))[1]
        self.pair = {(i, j): k for k, (i, j) in enumerate(
            (i, j) for i in range(self.nt) for j in range(i))}

    def rows(self, i):
        return slice(i * self.t, (i + 1) * self.t)

    def scores(self, b_ref, c_ref, sd_ref, so_ref):
        """C·Bᵀ by tile: the diagonal in f32 (sd_ref[i]), the tiles below
        it in the compute dtype (so_ref[pair[i, j]])."""
        for i in range(self.nt):
            c_i = c_ref[0, self.rows(i), :]
            sd_ref[i] = _mm(c_i, b_ref[0, self.rows(i), :], _NT)
            for j in range(i):
                so_ref[self.pair[i, j]] = _mm(
                    c_i, b_ref[0, self.rows(j), :], _NT).astype(self.cdt)

    def groups(self, body):
        """body(_Group) for each 128-lane group of the block, rolled."""
        pl.loop(0, self.ng)(lambda g: body(_Group(self, g)))


class _Group:
    """One 128-lane group of a block: its heads' lanes, and the decay over
    them.

    The decay between tiles factors through the tiles' ends: for i in row
    tile I and j in an earlier tile J, with R_K the cumulative sum at the
    end of tile K,

        L[i,j] = exp(cum[i] - R[I-1]) · exp(R[I-1] - R[J]) · exp(R[J] - cum[j])
               =      into[i]       ·       gap[I,J]      ·     out[j]

    and every factor is at most 1.  So only the diagonal tiles build L;
    the others scale rows of dt·x or of a gradient, and one product with
    C·Bᵀ serves every head of the group.  On the diagonal, a product
    with the group's dt·x gives one head's lanes; a select keeps them."""

    def __init__(self, ch, g):
        self.ch, gw, p = ch, ch.gw, ch.p
        self.nh = gw // p                         # heads in the group
        self.h0 = g * self.nh                     # its first head
        self.lanes = (pl.ds(0, gw) if ch.ng == 1
                      else pl.ds(pl.multiple_of(g * gw, gw), gw))
        self.lane_head = jax.lax.broadcasted_iota(jnp.int32, (1, gw), 1) // p
        # the group's heads' columns at lanes 0..nh-1
        width = ch.cols_ref.shape[2]
        dt_cols, self.cum_cols = (
            pltpu.roll(ch.cols_ref[k], (width - self.h0) % width, 1)
            for k in range(2))                                # [Q, *]
        self.dt_lanes = self.spread(dt_cols)                  # [Q, gw]
        self.cum_lanes = self.spread(self.cum_cols)           # [Q, gw]
        self.x32 = ch.x_ref[0, :, self.lanes].astype(F32)
        self.xdt = self.x32 * self.dt_lanes                   # [Q, gw]
        self.xdt_b = self.xdt.astype(ch.cdt)                  # MXU operand
        self.xdt_c = self.xdt_b.astype(F32)                   # its value
        # state decay exp(cum[Q-1] - cum[j])
        self.wdec = jnp.exp(self.end(ch.nt - 1) - self.cum_lanes)
        # dt·x scaled out to its tile's end, by tile
        self.z = [(self.xdt[ch.rows(j)] * self.out_of(j)).astype(ch.cdt)
                  for j in range(ch.nt)]

    def end(self, i):
        """R[i] over each head's lanes, [1, gw]."""
        t = self.ch.t
        return self.cum_lanes[(i + 1) * t - 1:(i + 1) * t, :]

    def into(self, i):
        """exp(cum[i] - R[I-1]) over tile I's rows, [t, gw]."""
        return jnp.exp(self.cum_lanes[self.ch.rows(i)] - self.end(i - 1))

    def out_of(self, j):
        """exp(R[J] - cum[j]) over tile J's rows, [t, gw]."""
        return jnp.exp(self.end(j) - self.cum_lanes[self.ch.rows(j)])

    def gap(self, i, j):
        """exp(R[I-1] - R[J]), [1, gw]."""
        return jnp.exp(self.end(i - 1) - self.end(j))

    def diagonal(self, i, k):
        """L's diagonal tile i for the group's head k, f32 [t, t]."""
        row = self.ch.tiles_ref[i, pl.ds(self.h0 + k, 1), :]   # [1, t]
        diff = self.cum_cols[self.ch.rows(i), k:k + 1] - row
        return jnp.exp(jnp.where(self.ch.causal, diff, -jnp.inf))

    def by_head(self, products):
        """Per-head products over the group's lanes (one per head, in
        order) -> the group's lanes, each from its own head's."""
        out = products[0]
        for k, prod in enumerate(products[1:], 1):
            out = jnp.where(self.lane_head == k, prod, out)
        return out

    def spread(self, cols):
        """[R, *] columns, the group's head k at lane k -> [R, gw], each
        over its head's lanes."""
        shape = (cols.shape[0], self.ch.gw)
        heads = [jnp.broadcast_to(cols[:, k:k + 1], shape)
                 for k in range(self.nh)]
        if self.nh == 1:    # Mosaic cannot broadcast a row of a broadcast
            heads[0] = jnp.where(self.lane_head == 0, heads[0], 0.0)
        return self.by_head(heads)

    def head_sums(self, v):
        """[R, gw] -> [R, *]: each row's sum over each head's lanes, at the
        head's lane of `cols_ref` (zero at the other heads')."""
        width = self.ch.cols_ref.shape[2]
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
        out = jnp.zeros((v.shape[0], width), F32)
        for k in range(self.nh):
            s = jnp.sum(jnp.where(self.lane_head == k, v, 0.0), axis=1,
                        keepdims=True)
            out = jnp.where(lane == k, s, out)
        return pltpu.roll(out, self.h0, 1)


def _fwd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref,
                y_ref, st_ref, cum_ref, cols_ref, tiles_ref, sd_ref, so_ref,
                *, p, t):
    ch = _Chunk(a_ref, x_ref, dt_ref, cum_ref.at[0, 0], cols_ref, tiles_ref,
                p=p, t=t)
    ch.scores(b_ref, c_ref, sd_ref, so_ref)

    def group(gr):
        for ti in range(ch.nt):
            rows = ch.rows(ti)
            s, xg = sd_ref[ti], gr.xdt_b[rows]
            y = gr.by_head([
                _mm((s * gr.diagonal(ti, k)).astype(ch.cdt), xg, _NN)
                for k in range(gr.nh)])
            if ti:
                off = jnp.zeros((t, ch.gw), F32)
                for tj in range(ti):
                    off = off + (_mm(so_ref[ch.pair[ti, tj]], gr.z[tj], _NN)
                                 * gr.gap(ti, tj))
                y = y + off * gr.into(ti)
            y_ref[0, rows, gr.lanes] = y.astype(y_ref.dtype)
        z = (gr.xdt * gr.wdec).astype(ch.cdt)                  # [Q, gw]
        st_ref[0, 0, gr.lanes, :] = _mm(z, b_ref[0], _TN)      # [gw, N]

    ch.groups(group)


def _bwd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, gy_ref, gst_ref,
                gcum_ref, dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                cum_buf, cols_ref, tiles_ref, sd_ref, so_ref, dsd_ref,
                dso_ref, dbz_ref, sums_ref, *, p, t):
    ch = _Chunk(a_ref, x_ref, dt_ref, cum_buf, cols_ref, tiles_ref, p=p,
                t=t)
    ch.scores(b_ref, c_ref, sd_ref, so_ref)
    q, nt, cdt, gw = ch.q, ch.nt, ch.cdt, ch.gw
    n = b_ref.shape[2]
    # summed over the groups: the scores' gradient (diagonal, below it),
    # the states' share of dB, and per-head sums for d(cum) and d(dt)
    for ref in (dsd_ref, dso_ref, dbz_ref, sums_ref):
        ref[...] = jnp.zeros_like(ref)

    def group(gr):
        gy_b = gy_ref[0, :, gr.lanes]                          # [Q, gw]
        gy = gy_b.astype(F32)
        # d(cum)'s terms, whose rows and columns must cancel as the terms
        # of dL∘L do: each pair is formed from the same rounded MXU operands
        rowdot = [None] * nt        # gy·(dL∘L's rows), by tile, [t, gw]
        dxdt = [None] * nt                               # diagonal tiles
        dx_off = [jnp.zeros((t, gw), F32) for _ in range(nt)]  # pre out_of
        for ti in range(nt):
            rows = ch.rows(ti)
            s, xg, gyg = sd_ref[ti], gr.xdt_b[rows], gy_b[rows]
            ds = jnp.zeros((t, t), F32)
            ys, dxs = [], []
            for k in range(gr.nh):
                ll = gr.diagonal(ti, k)
                m = (s * ll).astype(cdt)
                ys.append(_mm(m, xg, _NN))
                dxs.append(_mm(m, gyg, _TN))
                gyk = jnp.where(gr.lane_head == k, gy[rows], 0.0)
                ds = ds + _mm(gyk.astype(cdt), xg, _NT) * ll
            dsd_ref[ti] += ds
            dxdt[ti] = gr.by_head(dxs)
            rowdot[ti] = gy[rows] * gr.by_head(ys)
            if ti:
                g_i = (gy[rows] * gr.into(ti)).astype(cdt)     # [t, gw]
                off = jnp.zeros((t, gw), F32)
                for tj in range(ti):
                    s = so_ref[ch.pair[ti, tj]]
                    gap = gr.gap(ti, tj)                       # [1, gw]
                    off = off + _mm(s, gr.z[tj], _NN) * gap
                    dx_off[tj] = dx_off[tj] + _mm(s, g_i, _TN) * gap
                    dso_ref[ch.pair[ti, tj]] += _mm(
                        (g_i.astype(F32) * gap).astype(cdt), gr.z[tj], _NT)
                rowdot[ti] = rowdot[ti] + g_i.astype(F32) * off
        # the states' share: d(dt·x) and dB, and d(cum) through their decay
        gst = gst_ref[0, 0, gr.lanes, :].astype(cdt)           # [gw, N]
        bg = gr.wdec * _mm(b_ref[0], gst, _NT)                 # [Q, gw]
        dxdt_all = jnp.concatenate(
            [dxdt[j] + dx_off[j] * gr.out_of(j) for j in range(nt)],
            axis=0) + bg
        dx_ref[0, :, gr.lanes] = (dxdt_all * gr.dt_lanes).astype(
            dx_ref.dtype)
        z = (gr.xdt * gr.wdec).astype(cdt)
        dbz_ref[...] += _mm(z, gst, _NN)
        # d(cum): rows of dL∘L sum to gy·y, its columns to
        # (dt·x)·d(dt·x); the states add their decay's share
        coldot = jnp.concatenate(
            [gr.xdt_c[ch.rows(j)] * dxdt[j] + gr.z[j].astype(F32) * dx_off[j]
             for j in range(nt)], axis=0)
        sums_ref[0] += gr.head_sums(jnp.concatenate(rowdot, axis=0)
                                    - coldot)
        sums_ref[1] += gr.head_sums(gr.xdt_c * bg)
        sums_ref[2] += gr.head_sums(gr.x32 * dxdt_all)

    ch.groups(group)
    # dB and dC from the scores' gradient, summed over the block's heads
    dbs = [jnp.zeros((t, n), F32) for _ in range(nt)]
    dcs = []
    for ti in range(nt):
        c_i = c_ref[0, ch.rows(ti), :]
        ds = dsd_ref[ti].astype(cdt)
        dc_i = _mm(ds, b_ref[0, ch.rows(ti), :], _NN)
        dbs[ti] = dbs[ti] + _mm(ds, c_i, _TN)
        for tj in range(ti):
            ds = dso_ref[ch.pair[ti, tj]].astype(cdt)
            dc_i = dc_i + _mm(ds, b_ref[0, ch.rows(tj), :], _NN)
            dbs[tj] = dbs[tj] + _mm(ds, c_i, _TN)
        dcs.append(dc_i)
    # per-head sums, [hb, Q]; the states' decay adds its share at Q-1 and
    # at each step
    rows_less_cols, by_state, x_dxdt = (sums_ref[k].T[:ch.hb]
                                        for k in range(3))
    dcum = rows_less_cols - by_state + gcum_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    dcum = dcum + jnp.where(lane == q - 1,
                            jnp.sum(by_state, axis=1, keepdims=True), 0.0)
    # d(dt·A)[t] = Σ_{i>=t} d(cum)[i]
    dda = _scan(dcum, reverse=True)                            # [hb, Q]
    ddt_ref[0, 0] = dda * ch.a + x_dxdt
    da_ref[0, 0, 0] = jnp.sum(dda * ch.dt, axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)

    db_ref[0] += jnp.concatenate(dbs, axis=0) + dbz_ref[...]
    dc_ref[0] += jnp.concatenate(dcs, axis=0)


def _layout(dt, a, hb):
    """dt [B,S,H] -> [B,G,R,S]; a [H] -> [G,R,1]: each block's hb heads
    in `_rows(hb)` rows, zeros below them."""
    bs, s, h = dt.shape
    g = h // hb
    dt_r = jnp.swapaxes(dt.astype(F32), 1, 2).reshape(bs, g, hb, s)
    return _pad_rows(dt_r, 2), _pad_rows(a.astype(F32).reshape(g, hb, 1), 1)


def _pad_rows(v, axis):
    """v with its head axis padded to `_rows` with zeros."""
    extra = _rows(v.shape[axis]) - v.shape[axis]
    if not extra:
        return v
    pad = [(0, 0)] * v.ndim
    pad[axis] = (0, extra)
    return jnp.pad(v, pad)


def _specs(bs, s, h, p, n, chunk, hb):
    l, g, w = s // chunk, h // hb, hb * p
    lanes = pl.BlockSpec((1, chunk, w), lambda b, i, j: (b, i, j))
    r = _rows(hb)
    rows = pl.BlockSpec((1, 1, r, chunk), lambda b, i, j: (b, j, 0, i))
    seq = pl.BlockSpec((1, chunk, n), lambda b, i, j: (b, i, 0))
    state = pl.BlockSpec((1, 1, w, n), lambda b, i, j: (b, i, j, 0))
    a = pl.BlockSpec((1, r, 1), lambda b, i, j: (j, 0, 0))
    return (bs, l, g), dict(lanes=lanes, rows=rows, seq=seq, state=state,
                            a=a)


def vmem_limit(heads: int, head_dim: int, state_dim: int, chunk: int,
               dtype=jnp.bfloat16, backward: bool = False) -> int:
    """The kernel's `vmem_limit_bytes`: its `vmem_bytes`, and never less
    than the scoped default it would have without one."""
    return max(SCOPED_VMEM, vmem_bytes(heads, head_dim, state_dim, chunk,
                                       dtype, backward))


def _pallas(kernel, grid, hb, p, n, chunk, tile, dtype, backward,
            interpret, **kw):
    """The kernel's pallas_call at head block hb: its scratch and its
    `vmem_limit`."""
    _, scratch = _buffers(hb, p, n, chunk, dtype, backward)
    return pl.pallas_call(
        functools.partial(kernel, p=p, t=tile),
        grid=grid,
        scratch_shapes=[pltpu.VMEM(shape, dt) for shape, dt in scratch],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if backward else "parallel"),
            vmem_limit_bytes=vmem_limit(hb, p, n, chunk, dtype, backward)),
        interpret=interpret, **kw)


def _forward(x, dt, a, b, c, chunk, tile, hb, interpret):
    (bs, s, w), h, n = x.shape, dt.shape[-1], b.shape[-1]
    p = w // h
    grid, sp = _specs(bs, s, h, p, n, chunk, hb)
    dt_r, a_r = _layout(dt, a, hb)
    y, st, cum = _pallas(
        _fwd_kernel, grid, hb, p, n, chunk, tile, x.dtype, False, interpret,
        in_specs=[sp["a"], sp["lanes"], sp["rows"], sp["seq"], sp["seq"]],
        out_specs=[sp["lanes"], sp["state"], sp["rows"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((bs, s // chunk, w, n), F32),
            jax.ShapeDtypeStruct(dt_r.shape, F32),
        ],
    )(a_r, x, dt_r, b, c)
    return (y, st.reshape(bs, s // chunk, h, p, n),
            cum[:, :, :hb].reshape(bs, h, s))


def _backward(x, dt, a, b, c, gy, gst, gcum, chunk, tile, hb, interpret):
    (bs, s, w), h, n = x.shape, dt.shape[-1], b.shape[-1]
    p = w // h
    grid, sp = _specs(bs, s, h, p, n, chunk, hb)
    l, g = s // chunk, h // hb
    dt_r, a_r = _layout(dt, a, hb)
    dx, ddt, da, db, dc = _pallas(
        _bwd_kernel, grid, hb, p, n, chunk, tile, x.dtype, True, interpret,
        in_specs=[sp["a"], sp["lanes"], sp["rows"], sp["seq"], sp["seq"],
                  sp["lanes"], sp["state"], sp["rows"]],
        out_specs=[sp["lanes"], sp["rows"],
                   pl.BlockSpec((1, 1, 1, _rows(hb), 1),
                                lambda b, i, j: (b, i, j, 0, 0)),
                   sp["seq"], sp["seq"]],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct(dt_r.shape, F32),
            jax.ShapeDtypeStruct((bs, l, g, _rows(hb), 1), F32),
            jax.ShapeDtypeStruct(b.shape, F32),
            jax.ShapeDtypeStruct(c.shape, F32),
        ],
    )(a_r, x, dt_r, b, c, gy.astype(x.dtype),
      gst.reshape(bs, l, w, n).astype(F32),
      _pad_rows(gcum.reshape(bs, g, hb, s).astype(F32), 2))
    ddt = jnp.swapaxes(ddt[:, :, :hb].reshape(bs, h, s), 1, 2)
    return (dx, ddt.astype(dt.dtype),
            da[:, :, :, :hb].sum(axis=(0, 1)).reshape(h).astype(a.dtype),
            db.astype(b.dtype), dc.astype(c.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _intra(x, dt, a, b, c, chunk, tile, hb, interpret):
    return _forward(x, dt, a, b, c, chunk, tile, hb, interpret)


def _intra_fwd(x, dt, a, b, c, chunk, tile, hb, interpret):
    return (_forward(x, dt, a, b, c, chunk, tile, hb, interpret),
            (x, dt, a, b, c))


def _intra_bwd(chunk, tile, hb, interpret, res, g):
    gy, gst, gcum = g
    return _backward(*res, gy, gst, gcum, chunk, tile, hb, interpret)


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
    is TILE, or the chunk if shorter.  Each call is counted by the lanes
    of its head block (`obs.count_ssd_lanes`)."""
    (s, w), h = x.shape[1:], dt.shape[2]
    tile = min(TILE, chunk)
    if s % chunk or chunk % tile or w % h:
        raise ValueError(f"seq {s}, chunk {chunk} and tile {tile} must "
                         f"nest, and x's lanes split into dt's heads")
    hb = heads_per_block(h, w // h, b.shape[2], chunk, x.dtype)
    obs.count_ssd_lanes(hb * (w // h))
    return _intra(x, dt, a, b, c, chunk, tile, hb, interpret)
