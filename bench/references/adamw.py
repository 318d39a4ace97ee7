"""Plain AdamW in float32 `jax.numpy`: global-norm clipping, bias
correction, decoupled weight decay, linear warm-up then cosine decay.

The optimiser settings come from the traffic file.  Weight decay applies
to every parameter tensor of rank >= `decay_min_rank` as stored (the
stacked per-layer vectors are rank 2).  It imports nothing of the program.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp


def learning_rate(opt: Dict, step: int) -> float:
    """Rate of the update that takes the state from `step` to step + 1."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    frac = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = 0.5 * (1 + math.cos(math.pi * frac))
    lo = opt["min_lr_ratio"]
    return opt["lr"] * warm * (lo + (1 - lo) * cos)


def hyper(opt: Dict, step: int):
    """(lr, 1 - b1^t, 1 - b2^t) of the update from `step`, as arguments of
    the jitted `update`."""
    t = step + 1
    return (jnp.float32(learning_rate(opt, step)),
            jnp.float32(1 - opt["b1"] ** t), jnp.float32(1 - opt["b2"] ** t))


def norms(tree):
    return [jnp.sqrt(jnp.sum(jnp.square(x))) for x in jax.tree.leaves(tree)]


def clip_scale(opt: Dict, grads):
    """The factor global-norm clipping puts on `grads`."""
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree.leaves(grads)))
    return jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-9))


def update(opt: Dict, params, grads, mu, nu, lr, c1, c2):
    """One step; returns (params, mu, nu, per-leaf norms of the clipped
    gradient)."""
    scale = clip_scale(opt, grads)
    grads = jax.tree.map(lambda g: g * scale, grads)
    b1, b2 = opt["b1"], opt["b2"]
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def new(p, m, v):
        step = (m / c1) / (jnp.sqrt(v / c2) + opt["eps"])
        if p.ndim >= opt["decay_min_rank"]:
            step = step + opt["weight_decay"] * p
        return p - lr * step

    return jax.tree.map(new, params, mu, nu), mu, nu, norms(grads)
