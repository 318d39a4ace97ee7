"""Plain reference of the Zamba2 language model (arXiv:2411.15242;
`transformers`' modeling_zamba2): weights from a seed, and the next-token
loss in float32.

It imports nothing of the program.  The parameter tree uses the key names
and layouts of the program's parameters, so one tree can be fed to both;
every value is made here.

The layers are Mamba2 layers, each as bench/references/mamba2.py's.
Before the layers at ids k, 2k, ... (k = hybrid_attn_every) one shared
transformer block runs, with the site's own adapters and `linear`
(stacked [sites, ...] under "sites"); with x the residual stream and e the
embedding output [B, S, d]:

    u        = rmsnorm([x, e]) * (1 + ln_attn)             width 2 d
    q        = u @ wq + u @ q_a @ q_b                      k, v alike
    q, k     = rope(q), rope(k)                            per head
    a        = softmax(q k^T (head_dim / 2)^-1/2 + causal) v @ wo
    m        = rmsnorm(a) * (1 + ln_mlp)
    g, up    = split(m @ [w_gate | w_up] + m @ mlp_a @ mlp_b)
    t        = ((gelu(g) * up) @ w_down) @ linear          exact GELU
    x        = x + mamba2(rmsnorm(x + t) * (1 + ln))

so t enters the Mamba2 layer's input and not the residual.  Attention is
exact over each whole causal row, taken one kv head at a time; the q, k,
v adapters are there when "shared_attn_adapters" is set.  The loss takes
the logits a few hundred positions at a time: with the program's AdamW
state beside it, the reference holds only one head's scores and one
piece's logits, and so fits one chip at the configuration's 25 layers.
`loss(..., low=...)` is the fp8 control of bench/references/mamba2.py,
on every product here too (one scale per head in the attention core and
per piece of the logits).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from . import mamba2
from .mamba2 import _layer, _mm, _rmsnorm, _round


#: positions whose logits the loss takes at once
LOSS_ROWS = 256


def sites(m: dict) -> int:
    every = m["hybrid_attn_every"]
    return (m["num_layers"] - 1) // every if every else 0


def param_shapes(m: dict) -> dict:
    """The Mamba2 layers' tree, with the shared block and the sites' leaves
    where there is a site."""
    d, r, hd = m["d_model"], m["adapter_rank"], m["head_dim"]
    q, kv = m["num_heads"] * hd, m["num_kv_heads"] * hd
    n, ff = sites(m), m["d_ff"]
    if not n:
        return mamba2.param_shapes(m)
    site = {"linear": (n, d, d), "mlp_a": (n, d, r),
            "mlp_b": (n, r, 2 * ff)}
    if m["shared_attn_adapters"]:
        for name, out in (("q", q), ("k", kv), ("v", kv)):
            site[f"{name}_a"] = (n, 2 * d, r)
            site[f"{name}_b"] = (n, r, out)
    return dict(mamba2.param_shapes(m), sites=site, shared={
        "ln_attn": (2 * d,),
        "attn": {"wq": (2 * d, q), "wk": (2 * d, kv), "wv": (2 * d, kv),
                 "wo": (q, d)},
        "ln_mlp": (d,),
        "mlp": {"w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)},
    })


def _leaf_init(path, key, shape, m: dict):
    """The Mamba2 layers' leaves as bench/references/mamba2.py makes them;
    the shared block's and the sites' matrices with unit-variance outputs
    (fan-in scaling), the adapters' B included so that every term moves
    the result; norm weights as the Mamba2 layers'."""
    name = str(path[-1].key)
    if str(path[0].key) not in ("shared", "sites"):
        return mamba2._leaf_init(name, key, shape, m["d_model"],
                                 mamba2.dims(m)[1])
    if name.startswith("ln"):
        return jax.random.normal(key, shape, jnp.float32) * 0.1
    return jax.random.normal(key, shape, jnp.float32) / math.sqrt(
        shape[-2])


def init_params(key, m: dict) -> dict:
    """The float32 weights for `key`; jit it with output shardings to make
    them on the device."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    leaves = [_leaf_init(path, k, shape, m)
              for (path, shape), k in zip(paths, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _rope(x, theta: float):
    """x [B, S, H, D]: each position's pairs (i, i + D/2) turned by
    position * theta^(-2i/D) (rotate-half form)."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


def _attention(mm, q, k, v, scale):
    """softmax(q k^T scale + causal mask) v for q [B, S, H, D] and k, v
    [B, S, KV, D]: query head h reads kv head h // (H / KV).  Exact, over
    the whole causal row; one kv head (with its query heads) at a time,
    each checkpointed, so that one head's [S, S] scores and their
    cotangents are live at once and not every head's."""
    b, s, h, d = q.shape
    kv = k.shape[2]
    t_idx = jnp.arange(s)
    causal = t_idx[:, None] >= t_idx[None, :]

    @jax.checkpoint
    def one(qkv):
        q, k, v = qkv                             # [B,S,G,D], [B,S,D] x2
        scores = mm("btgd,bsd->bgts", q, k) * scale
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        return mm("bgts,bsd->btgd", probs, v)

    heads = jax.lax.map(one, (jnp.moveaxis(q.reshape(b, s, kv, h // kv, d),
                                           2, 0),
                              jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    return jnp.moveaxis(heads, 0, 2).reshape(b, s, h, d)


def _shared_block(m: dict, mm, shared, site, x, e):
    eps, hd = m["norm_eps"], m["head_dim"]
    b, s, _ = x.shape
    u = _rmsnorm(jnp.concatenate([x, e], -1), shared["ln_attn"], eps)
    att = shared["attn"]

    def proj(name, heads):
        y = mm("bsi,io->bso", u, att[f"w{name}"])
        if f"{name}_a" in site:
            low = mm("bsi,ir->bsr", u, site[f"{name}_a"])
            y = y + mm("bsr,ro->bso", low, site[f"{name}_b"])
        return y.reshape(b, s, heads, hd)

    q = _rope(proj("q", m["num_heads"]), m["rope_theta"])
    k = _rope(proj("k", m["num_kv_heads"]), m["rope_theta"])
    v = proj("v", m["num_kv_heads"])
    a = _attention(mm, q, k, v, (hd / 2) ** -0.5).reshape(b, s, -1)
    a = mm("bsq,qd->bsd", a, att["wo"])

    f_in = _rmsnorm(a, shared["ln_mlp"], eps)
    mlp = shared["mlp"]
    ff = m["d_ff"]
    delta = mm("bsr,rf->bsf", mm("bsd,dr->bsr", f_in, site["mlp_a"]),
               site["mlp_b"])
    gate = mm("bsd,df->bsf", f_in, mlp["w_gate"]) + delta[..., :ff]
    up = mm("bsd,df->bsf", f_in, mlp["w_up"]) + delta[..., ff:]
    f = mm("bsf,fd->bsd", jax.nn.gelu(gate, approximate=False) * up,
           mlp["w_down"])
    return mm("bsd,de->bse", f, site["linear"])


def hidden(params: dict, tokens, m: dict, low=None):
    """The last layer's output [B, S, d] for `tokens` [B, S]: one scan over
    the layers, the shared block taken (`lax.cond`) at a site's layer, t
    = 0 elsewhere.  A layer is checkpointed as a whole and, inside, its
    shared block and its Mamba2 layer apart: a layer's backward holds one
    of them at a time, and slices of the stacked layers are never copied
    (the reference has to fit beside the program's AdamW state)."""
    mm = _mm(low)
    x = params["embed"][tokens]
    if low is not None:
        x = _round(x, low)
    e = x
    every = m["hybrid_attn_every"]
    shared = jax.checkpoint(partial(_shared_block, m, mm))
    layer = jax.checkpoint(partial(_layer, m, mm))

    @jax.checkpoint
    def body(x, inp):
        i, lp = inp
        t = jnp.zeros_like(x)
        if sites(m):
            site = jax.tree.map(lambda w: w[jnp.maximum(i // every - 1, 0)],
                                params["sites"])
            t = jax.lax.cond((i > 0) & (i % every == 0),
                             lambda: shared(params["shared"], site, x, e),
                             lambda: t)
        # the Mamba2 layer on x + t, with x as its residual
        return layer(x + t, lp) - t, None

    x, _ = jax.lax.scan(body, x, (jnp.arange(m["num_layers"]),
                                  params["layers"]))
    return _rmsnorm(x, params["final_norm"], m["norm_eps"]), mm


def logits(params: dict, tokens, m: dict, low=None):
    """The logits [B, S, V] at every position (tied embedding)."""
    x, mm = hidden(params, tokens, m, low)
    return mm("bsd,vd->bsv", x, params["embed"])


def loss(params: dict, tokens, m: dict, low=None):
    """Mean next-token cross-entropy of `tokens` [B, S] (the last position
    of each row has no target).  The logits are taken for LOSS_ROWS
    positions at a time (or the largest common divisor with S), each piece
    checkpointed: one piece's [B, LOSS_ROWS, V] logits are live at once."""
    x, mm = hidden(params, tokens, m, low)
    b, s, d = x.shape
    c = math.gcd(s, LOSS_ROWS)
    # the last position's target is a placeholder, weighed 0
    gold = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], 1)

    @jax.checkpoint
    def piece(inp):
        x, gold = inp                              # [B, c, d], [B, c]
        z = mm("bsd,vd->bsv", x, params["embed"])
        return jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, gold[..., None], -1)[..., 0]

    nll = jax.lax.map(piece, (
        jnp.swapaxes(x.reshape(b, s // c, c, d), 0, 1),
        jnp.swapaxes(gold.reshape(b, s // c, c), 0, 1)))
    nll = jnp.swapaxes(nll, 0, 1).reshape(b, s)
    return jnp.sum(nll[:, :-1]) / (b * (s - 1))


def stored_param_count(m: dict) -> int:
    """Every parameter stored: the shared block once."""
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple)))


def param_count(m: dict) -> int:
    """The N of 6 N: the parameters a token's products pass through, the
    shared block counted once for each site that applies it."""
    if not sites(m):
        return stored_param_count(m)
    shared = sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(m)["shared"], is_leaf=lambda x: isinstance(x, tuple)))
    return stored_param_count(m) + (sites(m) - 1) * shared
