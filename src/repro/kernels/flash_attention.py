"""Pallas TPU flash attention (GQA, causal/sliding-window/prefix, softcap).

Canonical TPU online-softmax pattern: grid = (B, H, num_q_blocks,
num_kv_blocks) with the kv dimension innermost and marked "arbitrary" so the
VMEM scratch accumulators (m, l, acc) carry across kv steps.  Block sizes
are MXU-aligned (q/kv blocks multiples of 128 on TPU; smaller for tests).

VMEM working set per step:
    q block  [bq, D] + k/v blocks [bk, D]*2 + acc [bq, D] + m/l [bq]
e.g. bq=bk=512, D=128, fp32: ~1.3 MB — well under the ~16 MB/core VMEM.

Validated in interpret=True mode against `ref.mha_reference` over shape and
dtype sweeps (tests/test_kernels.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  prefix_len: int, logit_cap: Optional[float],
                  block_q: int, block_kv: int, num_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)               # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)               # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)

    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale   # [bq, bk]
    if logit_cap is not None:
        logits = logit_cap * jnp.tanh(logits / logit_cap)

    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 0)
    kv_pos = ik * block_kv + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_kv), 1)
    if causal:
        ok = kv_pos <= q_pos
        if window is not None:
            ok &= kv_pos > q_pos - window
        if prefix_len:
            ok |= kv_pos < prefix_len
        logits = jnp.where(ok, logits, NEG_INF)

    m_prev = m_ref[...]                               # [bq]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, logits.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new[:, None])              # [bq, bk]
    l_new = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ik == num_kv - 1)
    def _finish():
        o_ref[0, 0, :, :] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)[:, None]
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "prefix_len", "logit_cap",
                              "block_q", "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    prefix_len: int = 0,
                    logit_cap: Optional[float] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: [B, H, Sq, D]; k, v: [B, Hkv, Skv, D] (H = Hkv * groups).
    Returns [B, H, Sq, D]."""
    b, h, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    g = h // hkv
    bq = min(block_q, sq)
    bk = min(block_kv, skv)
    if sq % bq or skv % bk:
        raise ValueError(f"seq lens ({sq},{skv}) must divide blocks ({bq},{bk})")
    nq, nk = sq // bq, skv // bk
    grid = (b, h, nq, nk)
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        prefix_len=prefix_len, logit_cap=logit_cap,
        block_q=bq, block_kv=bk, num_kv=nk)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, iq, ik: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),               # m
            pltpu.VMEM((bq,), jnp.float32),               # l
            pltpu.VMEM((bq, d), jnp.float32),             # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
