"""Gated MLPs (SwiGLU / GeGLU)."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .common import ModelConfig, Params, activation_fn, dense_init


def init_mlp(key: jax.Array, d_model: int, d_ff: int,
             dtype=jnp.float32, variant: str = "gated") -> Params:
    ks = jax.random.split(key, 3)
    if variant == "plain":
        return {
            "w_in": dense_init(ks[0], (d_model, d_ff), dtype),
            "w_out": dense_init(ks[1], (d_ff, d_model), dtype),
        }
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), dtype),
    }


def mlp_forward(p: Params, x: jax.Array, activation: str = "silu",
                lora: Optional[Tuple[jax.Array, jax.Array]] = None
                ) -> jax.Array:
    """`lora` = (A [d, r], B [r, 2 d_ff]) adds x A B to the gated MLP's
    [gate, up] projection: B's first d_ff columns to the gate."""
    act = activation_fn(activation)
    if "w_in" in p:
        return act(x @ p["w_in"]) @ p["w_out"]
    gate, up = x @ p["w_gate"], x @ p["w_up"]
    if lora is not None:
        a, b = lora
        delta = (x @ a) @ b
        d_ff = gate.shape[-1]
        gate, up = gate + delta[..., :d_ff], up + delta[..., d_ff:]
    return (act(gate) * up) @ p["w_down"]
