"""The system under test: the training step that `repro.launch.train`
builds, made from the same public functions.

`build_step` mirrors `repro.launch.train.main` (src/repro/launch/train.py,
the step builder `build_step_jit`): the trainer keeps it in a closure of
`main` and checkpoints from its step loop, so the benchmark cannot call it.
Everything else (model, step, optimizer, collectives, loader, sharding
rules) is the program's own.  The weights are the benchmark's: made from
the seed by the reference's initialiser, on the device, in one jitted call
with the cell's shardings.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.launch.sharding import batch_specs, opt_specs, param_specs, to_named
from repro.models import build_model
from repro.models.common import ModelConfig
from repro.train import AdamWConfig, TrainConfig, init_adamw, make_train_step
from repro.train.data import DataConfig, make_global_batch

from .references import load_reference


def seed_key_data(seed: int) -> np.ndarray:
    """Two uint32 words of a threefry key from any whole-number seed
    (the driver's seeds do not fit 32 bits)."""
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)


def model_config(config: dict) -> ModelConfig:
    return ModelConfig(**config["model"])


@dataclasses.dataclass
class Program:
    """One cell's compiled step, weights maker and feed."""
    config: dict
    traffic: dict
    mesh: Mesh
    step: Any            # jitted (params, opt, batch) -> (params, opt, metrics)
    init: Any            # jitted key data -> (params, opt)
    data: DataConfig

    @property
    def tokens_per_step(self) -> int:
        return self.data.global_batch * self.data.seq_len

    def batch(self, step: int) -> dict:
        return make_global_batch(self.data, step, self.mesh, ("data",))


def train_config(traffic: dict) -> TrainConfig:
    opt = {k: v for k, v in traffic["optimizer"].items()
           if k != "decay_min_rank"}
    return TrainConfig(optimizer=AdamWConfig(**opt),
                       microbatches=traffic["microbatches"],
                       compute_dtype=jnp.dtype(traffic["compute_dtype"]))


def build_step(model, tc: TrainConfig, mesh: Mesh, traffic: dict,
               p_named, o_named, b_named):
    """The jitted step, as `repro.launch.train` builds it."""
    dp = mesh.shape["data"]
    if traffic["collectives"] == "pipeline":
        from repro.api import Collectives
        from repro.comms import CollectiveContext
        ctx = CollectiveContext(dict(mesh.shape),
                                collectives=Collectives(cache=None))
        red = ctx.bucketed_allreduce(
            "data", bucket_bytes=traffic["bucket_mib"] << 20,
            wire_dtype=None)

        def grad_reduce(tree):
            return jax.tree.map(lambda x: x / dp, red(tree))

        base_step = make_train_step(model, tc, grad_reduce=grad_reduce)

        def spmd_step(params, opt_state, batch):
            p, o, m = base_step(params, opt_state, batch)
            m = {k: jax.lax.pmean(v, "data") for k, v in m.items()}
            return p, o, m

        step_sm = jax.shard_map(spmd_step, mesh=mesh,
                                in_specs=(P(), P(), P("data")),
                                out_specs=(P(), P(), P()),
                                check_vma=False)
        return jax.jit(step_sm, donate_argnums=(0, 1))
    if traffic["collectives"] != "xla":
        raise ValueError(f"collectives {traffic['collectives']!r}")
    return jax.jit(make_train_step(model, tc),
                   in_shardings=(p_named, o_named, b_named),
                   out_shardings=(p_named, o_named, None),
                   donate_argnums=(0, 1))


def build(config: dict, traffic: dict, devices: Sequence,
          seed: int = 0) -> Program:
    dp = traffic["data_parallel"]
    if len(devices) != dp:
        raise ValueError(f"{dp}-way data parallelism on {len(devices)} "
                         f"devices")
    ref = load_reference(config)
    m = config["model"]
    cfg = model_config(config)
    model = build_model(cfg, remat=True)
    shapes = model.init_shape()
    want = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32),
                        ref.param_shapes(m),
                        is_leaf=lambda x: isinstance(x, tuple))
    if jax.tree.structure(shapes) != jax.tree.structure(want) or any(
            a.shape != b.shape for a, b in zip(jax.tree.leaves(shapes),
                                               jax.tree.leaves(want))):
        raise ValueError("the program's parameter tree differs from the "
                         "reference's layout")

    mesh = Mesh(np.array(devices).reshape(dp, 1), ("data", "model"))
    p_spec = param_specs(shapes, mesh,
                         fsdp=traffic["collectives"] == "xla")
    p_named = to_named(p_spec, mesh)
    o_named = to_named(opt_specs(p_spec), mesh)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=traffic["seq_len"],
                      global_batch=traffic["rows_per_chip"] * dp, seed=seed)
    batch_shape = {"tokens": jax.ShapeDtypeStruct(
        (data.global_batch, data.seq_len), jnp.int32)}
    b_named = to_named(batch_specs(batch_shape, mesh), mesh)

    def make_state(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        params = ref.init_params(key, m)
        return params, init_adamw(params)

    init = jax.jit(make_state, out_shardings=(p_named, o_named))
    step = build_step(model, train_config(traffic), mesh, traffic,
                      p_named, o_named, b_named)
    return Program(config=config, traffic=traffic, mesh=mesh, step=step,
                   init=init, data=data)
