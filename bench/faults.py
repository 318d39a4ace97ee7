"""Faults planted under the timed path, for the checks that `correct`
catches them (bench/tests/test_faults.py) and for their readings on the
chip (bench/calibrate.py).  Each is a context manager that patches the
program while a cell's step is built; the harness is not changed.

* `unchanged_state` — the step returns its parameters and optimizer state
  as it got them;
* `half_batch` — the loss leaves out the second half of every row's
  positions and takes the mean over the rest;
* `no_exchange` — the gradient allreduce between chips is left out (each
  chip applies its own gradient, divided by the data-parallel size).
"""
from __future__ import annotations

import contextlib

from unittest import mock


@contextlib.contextmanager
def unchanged_state():
    from bench import program
    build = program.make_train_step

    def make(model, cfg, grad_reduce=None):
        step = build(model, cfg, grad_reduce=grad_reduce)

        def frozen(params, opt_state, batch):
            _, _, metrics = step(params, opt_state, batch)
            return params, opt_state, metrics
        return frozen

    with mock.patch.object(program, "make_train_step", make):
        yield


@contextlib.contextmanager
def half_batch():
    import jax.numpy as jnp
    from repro.train import train_step as ts
    loss_and_grad = ts.loss_and_grad

    def halved(model, params, batch, cfg):
        s = batch["tokens"].shape[1]
        keep = jnp.broadcast_to(jnp.arange(s) < s // 2,
                                batch["tokens"].shape).astype(jnp.int32)
        return loss_and_grad(model, params, dict(batch, loss_mask=keep), cfg)

    with mock.patch.object(ts, "loss_and_grad", halved):
        yield


@contextlib.contextmanager
def no_exchange():
    from repro.comms.overlap import BucketedAllReduce
    with mock.patch.object(BucketedAllReduce, "__call__",
                           lambda self, grads: grads):
        yield


FAULTS = {"unchanged_state": unchanged_state, "half_batch": half_batch,
          "no_exchange": no_exchange}


def applies(name: str, traffic: dict) -> bool:
    """A fault the cell can have: no exchange needs one to leave out."""
    return name != "no_exchange" or traffic["collectives"] == "pipeline"
