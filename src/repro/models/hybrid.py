"""Hybrid SSM + shared-attention models (Zamba2 family) and the pure-SSM LM
(Mamba2 family).

Zamba2 (arXiv:2411.15242; `transformers`' `modeling_zamba2`) is a stack of
Mamba2 layers.  Every `hybrid_attn_every`-th layer (ids k, 2k, ... below
`num_layers`: 6, 12, ..., 36 in Zamba2-1.2B) is a hybrid layer: one
SHARED transformer block, whose parameters every such site reuses, runs
first, on the residual stream h beside the embedding output e:

    u  = rmsnorm([h, e]) (1 + ln_attn)                      width 2 d
    q  = u wq + u Aq Bq,  k, v alike                        heads x head_dim
    a  = softmax(rope(q) rope(k)^T (head_dim / 2)^-1/2 + causal) v @ wo
    m  = rmsnorm(a) (1 + ln_mlp)
    [g, up] = m [w_gate, w_up] + m Am Bm
    t  = ((gelu(g) * up) @ w_down) @ linear
    h' = h + mamba2(rmsnorm(h + t) (1 + ln))

The A, B adapters (rank `adapter_rank`; on q, k and v only with
`shared_attn_adapters`) and `linear` are the site's own, stacked
[sites, ...] under "sites"; a stack with no hybrid layer has neither
"shared" nor "sites".  The block has no residual of its own: t
reaches the stream only through the Mamba2 layer's input, and that
layer's residual is h without it (`Zamba2MambaDecoderLayer`).  Decode
keeps a KV cache per site and the SSM states per layer.

Departures from `modeling_zamba2`:

* RMSNorm weights are zero-centred, x (1 + w), as in the Mamba2 family
  (HF: x w, w initialised to ones).
* gate_up is stored as `w_gate` and `w_up` (HF: one [d, 2 d_ff] matrix,
  gate first); the MLP adapter's B keeps HF's [gate, up] column order.
* The q, k, v adapters map 2 d onto heads x head_dim (kv_heads x
  head_dim for k and v), where HF sizes them `attention_hidden_size`
  (2 d): the same at the published widths, 32 x 128 = 2 x 2048.
* One shared block (`num_mem_blocks` 1, as Zamba2-1.2B), hybrid layers
  at every k-th id (the 1.2B layout, not HF's default
  `layers_block_type`), no attention dropout, no long-context RoPE.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from .attention import CAUSAL, attention_forward, init_attention
from .common import (ModelConfig, Params, constrain, dense_init, rms_norm,
                     stacked_init)
from .mlp import init_mlp, mlp_forward
from .ssm import init_mamba2, init_ssm_state, mamba2_forward
from .transformer import embed_tokens, lm_logits, next_token_loss


# ---------------------------------------------------------------------- #
# pure SSM LM (mamba2)
# ---------------------------------------------------------------------- #

def init_ssm_lm(key: jax.Array, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "embed": dense_init(ks[0], (cfg.vocab_size, cfg.d_model), dtype, 0.02),
        "layers": stacked_init(
            ks[1], cfg.num_layers,
            lambda k: {"ln": jnp.zeros((cfg.d_model,), dtype),
                       "mamba": init_mamba2(k, cfg, dtype)}),
        "final_norm": jnp.zeros((cfg.d_model,), dtype),
    }


def _mamba_layer(lp: Params, cfg: ModelConfig, h: jax.Array, st: Any,
                 t: Optional[jax.Array] = None) -> Tuple[jax.Array, Any]:
    """A Mamba2 layer with its pre-norm and residual; a hybrid layer's t is
    added to the layer's input, not to its residual."""
    x_in = rms_norm(h if t is None else h + t, lp["ln"], cfg.norm_eps)
    out, new_st = mamba2_forward(lp["mamba"], cfg, x_in, st)
    return h + out, new_st


def ssm_stack(params: Params, cfg: ModelConfig, h: jax.Array,
              states: Optional[Any] = None,
              remat: bool = False) -> Tuple[jax.Array, Any]:
    """states: stacked (conv [L,B,W-1,C], ssm [L,B,H,P,N]) or None."""
    def layer(lp, hh, st):
        return _mamba_layer(lp, cfg, hh, st)

    if remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)

    def body(hh, xs):
        lp, st = xs
        out, new_st = layer(lp, hh, st)
        return constrain(out, "residual"), new_st

    h, new_states = jax.lax.scan(body, h, (params["layers"], states))
    return h, new_states


def ssm_lm_loss(params: Params, cfg: ModelConfig,
                batch: Dict[str, jax.Array],
                remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = embed_tokens(params, cfg, tokens)
    h, _ = ssm_stack(params, cfg, h, remat=remat)
    loss = next_token_loss(params, cfg, h, tokens, batch.get("loss_mask"))
    return loss, loss


def init_ssm_lm_states(cfg: ModelConfig, batch: int, dtype=jnp.float32):
    conv, ssm = init_ssm_state(cfg, batch, dtype)
    stack = lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape)
    return (stack(conv), stack(ssm))


def ssm_lm_decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                       states: Any) -> Tuple[jax.Array, Any]:
    """O(1) decode: no positions, no cache index — SSM state carries time."""
    h = embed_tokens(params, cfg, token)
    h, states = ssm_stack(params, cfg, h, states)
    return lm_logits(params, cfg, h), states


# ---------------------------------------------------------------------- #
# hybrid LM (zamba2)
# ---------------------------------------------------------------------- #

def hybrid_layer_ids(cfg: ModelConfig) -> List[int]:
    """The layers the shared block runs before, one per site."""
    return [cfg.hybrid_attn_every * (k + 1)
            for k in range(cfg.num_shared_sites)]


def _init_site(key: jax.Array, cfg: ModelConfig, dtype) -> Params:
    """A site's `linear` and adapters; each adapter's B starts at zero
    (LoRA's initialisation), so that every site starts with the shared
    block itself."""
    d, r, hd = cfg.d_model, cfg.adapter_rank, cfg.hd
    ks = jax.random.split(key, 5)
    p = {"linear": dense_init(ks[0], (d, d), dtype),
         "mlp_a": dense_init(ks[1], (d, r), dtype),
         "mlp_b": jnp.zeros((r, 2 * cfg.d_ff), dtype)}
    if cfg.shared_attn_adapters:
        for k, (name, heads) in zip(ks[2:], (("q", cfg.num_heads),
                                             ("k", cfg.num_kv_heads),
                                             ("v", cfg.num_kv_heads))):
            p[f"{name}_a"] = dense_init(k, (2 * d, r), dtype)
            p[f"{name}_b"] = jnp.zeros((r, heads * hd), dtype)
    return p


def init_hybrid_lm(key: jax.Array, cfg: ModelConfig,
                   dtype=jnp.float32) -> Params:
    """A stack too short for a hybrid layer has no shared block."""
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    params = {
        "embed": dense_init(ks[0], (cfg.vocab_size, d), dtype, 0.02),
        "layers": stacked_init(
            ks[1], cfg.num_layers,
            lambda k: {"ln": jnp.zeros((d,), dtype),
                       "mamba": init_mamba2(k, cfg, dtype)}),
        "final_norm": jnp.zeros((d,), dtype),
    }
    if cfg.num_shared_sites:
        params["shared"] = {
            "ln_attn": jnp.zeros((2 * d,), dtype),
            "attn": init_attention(ks[2], cfg, dtype, d_in=2 * d),
            "ln_mlp": jnp.zeros((d,), dtype),
            "mlp": init_mlp(ks[3], d, cfg.d_ff, dtype),
        }
        params["sites"] = stacked_init(ks[4], cfg.num_shared_sites,
                                       lambda k: _init_site(k, cfg, dtype))
    return params


def _attn_input(h: jax.Array, emb: jax.Array) -> jax.Array:
    """The shared block's input: the residual stream beside the embedding
    output."""
    return jnp.concatenate([h, emb], axis=-1)


def shared_block(shared: Params, site: Params, cfg: ModelConfig,
                 h: jax.Array, emb: jax.Array, positions: jax.Array,
                 cache: Optional[Tuple[jax.Array, jax.Array]] = None,
                 cache_index: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, Any]:
    """One application of the shared block at a site: t (after the site's
    `linear`) and the site's new KV cache."""
    with jax.named_scope(obs.SHARED):
        u = rms_norm(_attn_input(h, emb), shared["ln_attn"], cfg.norm_eps)
        lora = {n: (site[f"{n}_a"], site[f"{n}_b"]) for n in "qkv"
                if f"{n}_a" in site}
        a, new_cache = attention_forward(
            shared["attn"], cfg, u, positions, CAUSAL, cache=cache,
            cache_index=cache_index, lora=lora, scale=(cfg.hd / 2) ** -0.5,
            core_scope=jax.named_scope(obs.SHARED_ATTN))
        m = rms_norm(a, shared["ln_mlp"], cfg.norm_eps)
        f = mlp_forward(shared["mlp"], m, cfg.activation,
                        lora=(site["mlp_a"], site["mlp_b"]))
        return f @ site["linear"], new_cache


def _hybrid_layer(cfg: ModelConfig, shared: Params, site: Params,
                  lp: Params, h: jax.Array, emb: jax.Array,
                  positions: jax.Array, st: Any, kv: Any,
                  cache_index: Optional[jax.Array]):
    t, new_kv = shared_block(shared, site, cfg, h, emb, positions, kv,
                             cache_index)
    h, new_st = _mamba_layer(lp, cfg, h, st, t)
    return h, new_st, new_kv


def hybrid_stack(params: Params, cfg: ModelConfig, h: jax.Array,
                 positions: jax.Array,
                 ssm_states: Optional[Any] = None,
                 kv_caches: Optional[Any] = None,
                 cache_index: Optional[jax.Array] = None,
                 remat: bool = False
                 ) -> Tuple[jax.Array, Any, Any]:
    """The layers over the embedding output h: runs of plain Mamba2
    layers (one `lax.scan` each) between the hybrid layers.  ssm_states:
    stacked (conv, ssm) [L, ...]; kv_caches: (k, v) [sites, B, T, Hkv,
    D].  Under `remat` each hybrid layer is checkpointed as a whole, as
    `ssm_stack` checkpoints each Mamba2 layer."""
    emb = h
    ids = hybrid_layer_ids(cfg)
    ends = ids[1:] + [cfg.num_layers]
    take = lambda t, a, b: None if t is None else jax.tree.map(
        lambda x: x[a:b], t)
    layer = functools.partial(_hybrid_layer, cfg)
    if remat:
        layer = jax.checkpoint(
            layer, policy=jax.checkpoint_policies.nothing_saveable)
    new_states, new_kv = [], []

    def plain(h, a, b):
        if a < b:
            h, st = ssm_stack({"layers": take(params["layers"], a, b)}, cfg,
                              h, take(ssm_states, a, b), remat=remat)
            new_states.append(st)
        return h

    h = plain(h, 0, ids[0] if ids else cfg.num_layers)
    for k, (i, end) in enumerate(zip(ids, ends)):
        obs.count_shared(k)
        site = jax.tree.map(lambda x: x[k], params["sites"])
        lp = jax.tree.map(lambda x: x[i], params["layers"])
        st = None if ssm_states is None else jax.tree.map(
            lambda x: x[i], ssm_states)
        kv = None if kv_caches is None else (kv_caches[0][k],
                                             kv_caches[1][k])
        h, st, kv = layer(params["shared"], site, lp, h, emb, positions, st,
                          kv, cache_index)
        h = constrain(h, "residual")
        new_states.append(jax.tree.map(lambda x: x[None], st))
        new_kv.append(kv)
        h = plain(h, i + 1, end)
    out_states = None
    if ssm_states is not None:
        out_states = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0), *new_states)
    out_kv = kv_caches
    if kv_caches is not None and new_kv:
        out_kv = (jnp.stack([c[0] for c in new_kv]),
                  jnp.stack([c[1] for c in new_kv]))
    return h, out_states, out_kv


def hybrid_lm_loss(params: Params, cfg: ModelConfig,
                   batch: Dict[str, jax.Array],
                   remat: bool = False) -> Tuple[jax.Array, jax.Array]:
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = embed_tokens(params, cfg, tokens)
    h, _, _ = hybrid_stack(params, cfg, h, jnp.arange(s), remat=remat)
    loss = next_token_loss(params, cfg, h, tokens, batch.get("loss_mask"))
    return loss, loss


def init_hybrid_caches(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=jnp.float32):
    sites = cfg.num_shared_sites
    conv, ssm = init_ssm_state(cfg, batch, dtype)
    stack_l = lambda x: jnp.broadcast_to(x[None], (cfg.num_layers,) + x.shape)
    kv_shape = (sites, batch, max_len, cfg.num_kv_heads, cfg.hd)
    return ((stack_l(conv), stack_l(ssm)),
            (jnp.zeros(kv_shape, dtype), jnp.zeros(kv_shape, dtype)))


def hybrid_decode_step(params: Params, cfg: ModelConfig, token: jax.Array,
                       ssm_states: Any, kv_caches: Any, index: jax.Array
                       ) -> Tuple[jax.Array, Any, Any]:
    h = embed_tokens(params, cfg, token)
    h, ssm_states, kv_caches = hybrid_stack(
        params, cfg, h, index[None], ssm_states, kv_caches, index)
    return lm_logits(params, cfg, h), ssm_states, kv_caches
