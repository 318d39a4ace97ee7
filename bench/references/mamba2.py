"""Plain reference of the Mamba2 language model (arXiv:2405.21060): weights
from a seed, and the next-token loss in float32.

It imports nothing of the program.  The parameter tree uses the key names
and stacked-layer layout of the program's parameters, so one tree can be
fed to both; every value is made here.

Per layer, with x the residual stream [B, S, d]:

    u            = rmsnorm(x) * (1 + ln)
    z, xBC, dt_r = split(u @ in_proj)                   widths din, din+2N, H
    dt           = softplus(dt_r + dt_bias)             [B, S, H]
    xBC          = silu(causal depthwise conv(xBC) + conv_b)
    xs, B, C     = split(xBC)                           [B,S,H,P], [B,S,N] x2
    y_t          = sum_{s<=t} exp(sum_{s<r<=t} dt_r A) (C_t . B_s) dt_s xs_s
                   + D xs_t                             A = -exp(A_log)
    x           += rmsnorm(y * silu(z)) * (1 + norm_w) @ out_proj

then logits = rmsnorm(x) * (1 + final_norm) @ embed^T (tied) and the mean
cross-entropy of each position against the next token.  The state-space
term is the quadratic ("attention") form of the SSD duality, computed in
one piece per layer: no chunking, no recurrence.  One B and C group
(ngroups = 1), as in the published mamba2 checkpoints.

`loss(..., low=(fwd, bwd))` rounds every operand of a matrix product to
`fwd`, and the gradient each backward product gives to `bwd`, with one
scale per tensor (its largest magnitude onto the format's largest finite
value), as fp8 training does (float8_e4m3fn forward, float8_e5m2
gradients): the control of a bfloat16 configuration.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def dims(m: dict):
    """(d, din, heads, head_dim, state, conv width) of a model dict."""
    d = m["d_model"]
    din = m["ssm_expand"] * d
    p = m["ssm_head_dim"]
    h = m.get("ssm_num_heads") or din // p
    return d, din, h, p, m["ssm_state_dim"], m["ssm_conv_width"]


def param_shapes(m: dict) -> dict:
    d, din, h, _, n, w = dims(m)
    L, v = m["num_layers"], m["vocab_size"]
    return {
        "embed": (v, d),
        "layers": {
            "ln": (L, d),
            "mamba": {
                "in_proj": (L, d, 2 * din + 2 * n + h),
                "conv_w": (L, w, din + 2 * n),
                "conv_b": (L, din + 2 * n),
                "A_log": (L, h),
                "D": (L, h),
                "dt_bias": (L, h),
                "norm_w": (L, din),
                "out_proj": (L, din, d),
            },
        },
        "final_norm": (d,),
    }


def _leaf_init(name: str, key, shape, d: int, din: int):
    """Mamba2's initialisation: A in [1, 16], dt in [1e-3, 1e-1]; norm
    weights and biases are drawn away from zero so that every term of the
    layer is exercised."""
    normal = lambda s: jax.random.normal(key, shape, jnp.float32) * s
    uniform = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32,
                                                lo, hi)
    if name == "embed":
        return normal(0.02)
    if name == "in_proj":
        return normal(1 / math.sqrt(d))
    if name == "out_proj":
        return normal(1 / math.sqrt(din))
    if name == "conv_w":
        return normal(0.5)
    if name == "A_log":
        return jnp.log(uniform(1.0, 16.0))
    if name == "D":
        return uniform(0.5, 1.5)
    if name == "dt_bias":
        dt = jnp.exp(uniform(math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    return normal(0.1)                    # ln, norm_w, final_norm, conv_b


def init_params(key, m: dict) -> dict:
    """The float32 weights for `key`; jit it with output shardings to make
    them on the device."""
    d, din = m["d_model"], dims(m)[1]
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(paths))
    leaves = [_leaf_init(str(path[-1].key), k, shape, d, din)
              for (path, shape), k in zip(paths, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _quantize(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _round(x, low):
    return _quantize(x, low[0])


def _round_fwd(x, low):
    return _quantize(x, low[0]), None


def _round_bwd(low, _, g):
    return (_quantize(g, low[1]),)


_round.defvjp(_round_fwd, _round_bwd)


def _mm(low):
    """einsum at full float32 precision, its operands first rounded to
    `low` when given."""
    def mm(spec, *ops):
        if low is not None:
            ops = [_round(o, low) for o in ops]
        return jnp.einsum(spec, *ops, precision=HIGHEST)
    return mm


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def _layer(m: dict, mm, x, lp):
    d, din, h, p, n, w = dims(m)
    eps = m["norm_eps"]
    mp = lp["mamba"]
    b, s, _ = x.shape
    u = _rmsnorm(x, lp["ln"], eps)
    proj = mm("bsd,de->bse", u, mp["in_proj"])
    z = proj[..., :din]
    xbc = proj[..., din:2 * din + 2 * n]
    dt = jax.nn.softplus(proj[..., 2 * din + 2 * n:] + mp["dt_bias"])
    # causal depthwise conv: out[t] = sum_k w[k] * in[t - (W-1) + k]
    padded = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
    conv = sum(padded[:, k:k + s, :] * mp["conv_w"][k] for k in range(w))
    xbc = jax.nn.silu(conv + mp["conv_b"])
    xs = xbc[..., :din].reshape(b, s, h, p)
    bm, cm = xbc[..., din:din + n], xbc[..., din + n:]
    a = -jnp.exp(mp["A_log"])                                    # [H]
    # sum_{s < r <= t} dt_r A = cum[t] - cum[s]: float32 rounding of the
    # running sum is ~1e-4 relative at S = 2048, far below bfloat16's 4e-3
    cum = jnp.cumsum(jnp.moveaxis(dt * a, 1, 2), axis=-1)        # [B,H,S]
    t_idx = jnp.arange(s)
    causal = t_idx[:, None] >= t_idx[None, :]                    # [t, s]
    decay = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))                         # [B,H,t,s]
    cb = mm("btn,bsn->bts", cm, bm)                              # [B,t,s]
    weights = decay * cb[:, None] * jnp.moveaxis(dt, 1, 2)[:, :, None, :]
    y = mm("bhts,bshp->bthp", weights, xs)
    y = y + xs * mp["D"][:, None]
    y = y.reshape(b, s, din) * jax.nn.silu(z)
    y = _rmsnorm(y, mp["norm_w"], eps)
    return x + mm("bse,ed->bsd", y, mp["out_proj"])


def loss(params: dict, tokens, m: dict, low=None):
    """Mean next-token cross-entropy of `tokens` [B, S] (the last position
    of each row has no target)."""
    mm = _mm(low)
    x = params["embed"][tokens]
    if low is not None:
        x = _round(x, low)

    @jax.checkpoint
    def body(x, lp):
        return _layer(m, mm, x, lp), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _rmsnorm(x, params["final_norm"], m["norm_eps"])
    logits = mm("bsd,vd->bsv", x, params["embed"])
    logz = jax.nn.logsumexp(logits[:, :-1], axis=-1)
    gold = jnp.take_along_axis(logits[:, :-1], tokens[:, 1:, None],
                               axis=-1)[..., 0]
    return jnp.mean(logz - gold)


def param_count(m: dict) -> int:
    return sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        param_shapes(m), is_leaf=lambda x: isinstance(x, tuple)))
