"""Attention: GQA/MQA/MHA with RoPE, sliding window, logit softcap, qk-norm,
prefix-LM, cross-attention, and KV-cache decode.

Masks are never materialised as [S, T] arrays — they are *described* by
(causal, window, prefix_len) plus position vectors and evaluated inline.
Two execution paths:

* direct      — small sequences / decode: one einsum, inline mask.
* blockwise   — long sequences: lax.map over query blocks, online-softmax
                lax.scan over KV blocks (flash attention expressed in XLA;
                O(block^2) memory).  For sliding-window attention only the
                window-adjacent KV blocks are visited, so compute is
                O(S * window) — this is what makes mixtral's long_500k
                cell tractable.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, ContextManager, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import (ModelConfig, NEG_INF, Params, apply_rope, dense_init,
                     rms_norm, softcap)

BLOCKWISE_THRESHOLD = 2048      # use blockwise path above this many kv rows
BLOCK_Q = 1024
BLOCK_KV = 1024


@dataclasses.dataclass(frozen=True)
class MaskSpec:
    """Logical attention mask: evaluated lazily from positions."""
    causal: bool = True
    window: Optional[int] = None        # sliding window (None = unbounded)
    prefix_len: int = 0                 # bidirectional prefix (prefix-LM)

    def allowed(self, q_pos: jax.Array, kv_pos: jax.Array) -> jax.Array:
        """q_pos: [...,S], kv_pos: [...,T] -> bool [...,S,T].
        Negative kv positions are never attended (ring-buffer caches encode
        not-yet-written rows as negative positions)."""
        qp = q_pos[..., :, None]
        kp = kv_pos[..., None, :]
        if self.causal:
            ok = kp <= qp
            if self.window is not None:
                ok &= kp > qp - self.window
        else:
            ok = jnp.ones(jnp.broadcast_shapes(qp.shape, kp.shape), bool)
        if self.prefix_len:
            ok |= kp < self.prefix_len
        return ok & (kp >= 0)


FULL = MaskSpec(causal=False)
CAUSAL = MaskSpec(causal=True)


def ring_positions(index: jax.Array, cache_len: int) -> jax.Array:
    """Absolute position held by each row of a (possibly ring-buffer) cache
    when the current decode position is `index`.  Rows never written resolve
    to negative positions, which MaskSpec.allowed() always rejects."""
    r = jnp.arange(cache_len)
    return index - jnp.mod(index - r, cache_len)


def init_attention(key: jax.Array, cfg: ModelConfig,
                   dtype=jnp.float32, d_in: Optional[int] = None) -> Params:
    """q, k and v project `d_in` (d_model unless given) onto the heads; o
    projects back onto d_model."""
    hd = cfg.hd
    d_in = d_in or cfg.d_model
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_in, cfg.num_heads * hd), dtype),
        "wk": dense_init(ks[1], (d_in, cfg.num_kv_heads * hd), dtype),
        "wv": dense_init(ks[2], (d_in, cfg.num_kv_heads * hd), dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, cfg.d_model), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


# ---------------------------------------------------------------------- #
# core attend
# ---------------------------------------------------------------------- #

def _scaled(logits: jax.Array, d: int, scale: Optional[float]) -> jax.Array:
    """Scores times `scale`, d^-1/2 unless given."""
    return logits / jnp.sqrt(d) if scale is None else logits * scale


def _direct_attend(q, k, v, q_pos, kv_pos, spec: MaskSpec,
                   logit_cap: Optional[float],
                   scale: Optional[float] = None) -> jax.Array:
    b, s, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, s, hkv, g, d)
    logits = jnp.einsum("bshgd,bthd->bhgst", qg, k).astype(jnp.float32)
    logits = softcap(_scaled(logits, d, scale), logit_cap)
    ok = spec.allowed(q_pos, kv_pos)                  # [B,S,T] or [S,T]
    if ok.ndim == 2:
        ok = ok[None]
    logits = jnp.where(ok[:, None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhgst,bthd->bshgd", probs, v)
    return out.reshape(b, s, h, d)


def _blockwise_attend(q, k, v, q_pos, kv_pos, spec: MaskSpec,
                      logit_cap: Optional[float],
                      scale: Optional[float] = None,
                      block_q: int = BLOCK_Q,
                      block_kv: int = BLOCK_KV) -> jax.Array:
    """Online-softmax flash attention in XLA.  Sliding-window masks visit
    only the KV blocks that can intersect the window.

    Head-parallel under SPMD: GQA kv heads are expanded to full heads up
    front and q/k/v are constrained head-sharded (launcher policy
    'attn_qkv', with batch-sharded / replicated fallbacks), so the block
    loops are collective-free."""
    from .common import constrain
    b, s, h, d = q.shape
    t = k.shape[1]
    hkv = k.shape[2]
    g = h // hkv
    if g > 1:
        # SP->TP boundary: gather kv to model-replicated FIRST (cheap — kv
        # heads are few), expand GQA locally, then slice into head shards.
        # Direct seq-sharded -> head-sharded resharding of the expanded kv
        # makes GSPMD fall back to full rematerialisation.
        k = constrain(k, "attn_kv_full")
        v = constrain(v, "attn_kv_full")
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
        hkv = h
        g = 1
    q = constrain(q, "attn_qkv")
    k = constrain(k, "attn_qkv")
    v = constrain(v, "attn_qkv")
    bq = min(block_q, s)
    bkv = min(block_kv, t)
    pad_q = (-s) % bq
    pad_kv = (-t) % bkv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, pad_q),), constant_values=-1)
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, pad_kv),), constant_values=2 ** 30)
    sq, st = s + pad_q, t + pad_kv
    nq, nk = sq // bq, st // bkv

    windowed = spec.causal and spec.window is not None and spec.prefix_len == 0
    kblocks_per_q = nk if not windowed else \
        min(nk, -(-(spec.window + bq) // bkv) + 1)

    k_r = k.reshape(b, nk, bkv, hkv, d)
    v_r = v.reshape(b, nk, bkv, hkv, d)
    kp_r = kv_pos.reshape(nk, bkv)

    def q_block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * bq, bq, axis=1)
        qpi = jax.lax.dynamic_slice_in_dim(q_pos, i * bq, bq, axis=0)
        qg = qi.reshape(b, bq, hkv, g, d)

        def kv_iter(carry, j):
            m, l, acc = carry
            if windowed:
                # only blocks [j0, j0+kblocks) can intersect the window;
                # anchor on the LAST query row's diagonal block so any
                # (block_q, block_kv) alignment is covered
                jmax = ((i + 1) * bq - 1) // bkv
                j0 = jnp.maximum(0, jmax - (kblocks_per_q - 1))
                jj = jnp.minimum(j0 + j, nk - 1)
            else:
                jj = j
            kj = k_r[:, jj]                      # [B,bkv,hkv,d]
            vj = v_r[:, jj]
            kpj = kp_r[jj]
            logits = jnp.einsum("bshgd,bthd->bhgst", qg, kj
                                ).astype(jnp.float32)
            logits = softcap(_scaled(logits, d, scale), logit_cap)
            ok = spec.allowed(qpi, kpj)
            logits = jnp.where(ok[None, None, None], logits, NEG_INF)
            blk_max = logits.max(axis=-1)                     # [B,hkv,g,bq]
            new_m = jnp.maximum(m, blk_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(logits - new_m[..., None])
            new_l = l * corr + p.sum(axis=-1)
            # (perf iteration A1 tried p.astype(bf16) for this dot — flash
            # kernels do it on-chip — but XLA materialises the convert as a
            # separate kernel, a net traffic REGRESSION here; reverted.)
            pv = jnp.einsum("bhgst,bthd->bhgsd", p, vj.astype(jnp.float32))
            new_acc = acc * corr[..., None] + pv
            return (new_m, new_l, new_acc), None

        m0 = jnp.full((b, hkv, g, bq), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, bq), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, bq, d), jnp.float32)
        # flash backward: recompute block probabilities instead of stashing
        # them (otherwise autodiff saves O(S^2) logits across the scan)
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_iter,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (m0, l0, a0), jnp.arange(kblocks_per_q))
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        return jnp.moveaxis(out, 3, 1).reshape(b, bq, h, d)   # [B,bq,H,D]

    blocks = jax.lax.map(q_block, jnp.arange(nq))             # [nq,B,bq,H,D]
    out = jnp.moveaxis(blocks, 0, 1).reshape(b, sq, h, d)
    return out[:, :s].astype(q.dtype)


def attend(q: jax.Array, k: jax.Array, v: jax.Array,
           q_pos: jax.Array, kv_pos: jax.Array, spec: MaskSpec,
           logit_cap: Optional[float] = None,
           scale: Optional[float] = None) -> jax.Array:
    """q: [B,S,H,D], k/v: [B,T,Hkv,D], positions: [S]/[T] int; scores are
    scaled by `scale`, D^-1/2 unless given."""
    t = k.shape[1]
    s = q.shape[1]
    if s == 1 or max(s, t) <= BLOCKWISE_THRESHOLD:
        return _direct_attend(q, k, v, q_pos, kv_pos, spec, logit_cap,
                              scale)
    return _blockwise_attend(q, k, v, q_pos, kv_pos, spec, logit_cap,
                             scale)


# ---------------------------------------------------------------------- #
# attention block with optional KV cache / cross-attention
# ---------------------------------------------------------------------- #

def attention_forward(
        p: Params, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
        spec: MaskSpec, *,
        kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
        cache: Optional[Tuple[jax.Array, jax.Array]] = None,
        cache_index: Optional[jax.Array] = None,
        cache_positions: Optional[jax.Array] = None,
        logit_cap: Optional[float] = None,
        lora: Optional[Dict[str, Tuple[jax.Array, jax.Array]]] = None,
        scale: Optional[float] = None,
        core_scope: Optional[ContextManager] = None,
) -> Tuple[jax.Array, Optional[Tuple[jax.Array, jax.Array]]]:
    """x: [B,S,d_in]; positions: [S] int32.

    * training / prefill: cache=None.
    * decode: cache = (k_cache, v_cache) [B,Tmax,Hkv,D]; new rows written at
      cache_index (the caller mod-wraps for ring-buffer windowed caches);
      attention runs over the cache with `cache_positions` (defaults to
      arange) giving each row's absolute position for masking.
    * cross-attention: kv_override = precomputed (k, v) (no rope).
    * lora: {"q" | "k" | "v": (A, B)} adds x A B to that projection.
    * scale: the scores' scale, head_dim^-1/2 unless given.
    * core_scope: a context (a named scope) that scores, mask, softmax and
      PV run in.
    """
    hd = cfg.hd
    b, s, _ = x.shape
    lora = lora or {}

    def proj(name, heads):
        y = x @ p["w" + name]
        if name in lora:
            a, bm = lora[name]
            y = y + (x @ a) @ bm
        return y.reshape(b, s, heads, hd)

    q = proj("q", cfg.num_heads)
    if kv_override is None:
        k = proj("k", cfg.num_kv_heads)
        v = proj("v", cfg.num_kv_heads)
        # (perf iteration A3 tried pinning the SP->TP boundary here, before
        # the f32 rope segment — measured +3.7% collective bytes; reverted.)
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override
        if cfg.qk_norm:
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)

    new_cache = None
    kv_pos = positions
    if cache is not None:
        k_cache, v_cache = cache
        clen = k_cache.shape[1]
        kw, vw, widx = k, v, cache_index
        if s >= clen and s > 1:
            # ring-buffer cache shorter than the prompt: keep only the tail,
            # ROLLED so that row r holds absolute position p ≡ r (mod clen)
            # — decode's ring_positions() relies on that alignment
            shift = s % clen
            kw = jnp.roll(k[:, -clen:], shift, axis=1)
            vw = jnp.roll(v[:, -clen:], shift, axis=1)
            widx = jnp.zeros((), jnp.int32)
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, kw.astype(k_cache.dtype), widx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, vw.astype(v_cache.dtype), widx, axis=1)
        new_cache = (k_cache, v_cache)
        if s == 1:
            # decode: attend over the cache; row positions mask garbage /
            # encode ring-buffer wraparound
            k, v = k_cache, v_cache
            kv_pos = cache_positions if cache_positions is not None \
                else jnp.arange(clen)
        # prefill (s > 1): attend over the fresh full-length k/v
    elif kv_override is not None:
        kv_pos = jnp.arange(k.shape[1])

    with core_scope or contextlib.nullcontext():
        out = attend(q, k.astype(q.dtype), v.astype(q.dtype),
                     positions, kv_pos, spec, logit_cap, scale)
    return out.reshape(b, s, cfg.num_heads * hd) @ p["wo"], new_cache
