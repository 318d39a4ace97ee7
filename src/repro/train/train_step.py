"""The training step: loss -> grad -> (optional tree-pipeline allreduce) ->
AdamW, with microbatch gradient accumulation and a dtype policy.

Two collective modes:

* "xla"      — grads flow through pjit/GSPMD; XLA inserts its own
               all-reduces.  This is the stock baseline.
* "pipeline" — gradients are reduced with the paper's bandwidth-optimal
               tree-pipeline schedules (repro.comms) inside shard_map.
               Used by the shard_map training driver and the perf loop.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.models.model_zoo import Model
from .optimizer import AdamWConfig, AdamWState, adamw_update, init_adamw


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    optimizer: AdamWConfig = AdamWConfig()
    microbatches: int = 1            # grad accumulation steps
    compute_dtype: Any = jnp.float32  # bf16 on TPU
    collectives: str = "xla"         # xla | pipeline
    # optional: pin the bf16 cast of each param to its sharding so FSDP
    # weight all-gathers (and the transposed grad reductions) move bf16
    # wire bytes instead of f32 (perf iteration A2, EXPERIMENTS.md §Perf)
    cast_sharding: Any = None        # pytree of NamedSharding or None


def cast_params(params, dtype, cast_sharding=None):
    cast = jax.tree.map(
        lambda p: p.astype(dtype) if p.dtype in
        (jnp.float32, jnp.bfloat16, jnp.float16) else p, params)
    if cast_sharding is not None:
        cast = jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s)
            if s is not None else x, cast, cast_sharding)
    return cast


def loss_and_grad(model: Model, params, batch,
                  cfg: TrainConfig) -> Tuple[jax.Array, Any, jax.Array]:
    """Returns (loss, grads, raw_token_loss); microbatched if configured."""
    def loss_fn(p, b):
        with jax.named_scope(obs.FORWARD):
            cast = cast_params(p, cfg.compute_dtype, cfg.cast_sharding)
            total, token_loss = model.loss(cast, b)
        return total, token_loss

    if cfg.microbatches <= 1:
        (loss, tok), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, grads, tok

    # split the per-device batch into microbatches and scan-accumulate
    def split(x):
        b = x.shape[0]
        assert b % cfg.microbatches == 0, \
            f"batch {b} not divisible by microbatches {cfg.microbatches}"
        return x.reshape((cfg.microbatches, b // cfg.microbatches)
                         + x.shape[1:])

    micro = jax.tree.map(split, batch)

    def body(carry, mb):
        acc_loss, acc_tok, acc_g = carry
        (loss, tok), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, mb)
        acc_g = jax.tree.map(jnp.add, acc_g, grads)
        return (acc_loss + loss, acc_tok + tok, acc_g), None

    zero_g = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (loss, tok, grads), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), zero_g), micro)
    n = cfg.microbatches
    return loss / n, jax.tree.map(lambda g: g / n, grads), tok / n


def make_train_step(model: Model, cfg: TrainConfig,
                    grad_reduce: Optional[Callable[[Any], Any]] = None):
    """Build the jit-able train_step(params, opt_state, batch).

    grad_reduce: optional callable applied to the gradient pytree before the
    optimizer — the hook where the paper's tree-pipeline allreduce plugs in
    (inside shard_map).  Under pure pjit, leave None (XLA reduces via the
    sharding constraints)."""

    def train_step(params, opt_state: AdamWState, batch
                   ) -> Tuple[Any, AdamWState, Dict[str, jax.Array]]:
        loss, grads, tok = loss_and_grad(model, params, batch, cfg)
        if grad_reduce is not None:
            with jax.named_scope(obs.GRAD_REDUCE):
                grads = grad_reduce(grads)
                loss = grad_reduce(loss)  # average the scalar too
        with jax.named_scope(obs.ADAMW):
            new_params, new_state, metrics = adamw_update(
                cfg.optimizer, grads, opt_state, params)
        metrics = dict(metrics, loss=loss, token_loss=tok)
        return new_params, new_state, metrics

    return train_step


def init_train_state(model: Model, rng: jax.Array,
                     param_dtype=jnp.float32) -> Tuple[Any, AdamWState]:
    params = model.init(rng, param_dtype)
    return params, init_adamw(params)
