"""How `correct` is decided for a training cell.

Set-up drives the compiled step from the seed through its first
`checked_steps` steps on the window's own call and feed.  From the
program it reads each step's loss, the first gradient as the optimizer got
it (AdamW's first moment after one step is (1 - b1) times the clipped
gradient), and each parameter leaf's change after the last checked step.
The float32 reference follows the same steps from the same weights and
rows, after the window, with the program's state freed.  Four numbers are
read; a cell compares those that `limits/<cell>.json` gives a limit (the
file says why a number is left out):

* `loss_gap`: the relative gap of the first step's loss (the later steps'
  losses also carry the two sides' different trajectories, which swing
  from seed to seed; see PERF.md);
* `grad_gap`: over leaves, the largest gap between the norms of the
  program's and the reference's first gradient, over the larger of the
  reference's norm of that leaf and of the median leaf;
* `update_gap`: the same for the norm of each leaf's change, leaving out
  leaves whose reference gradient is under a thousandth of the median
  leaf's (they move by round-off alone);
* `grad_err`: the relative error of the whole first gradient,
  |g - g_ref| / |g_ref| over all leaves.  The gaps of norms above are
  blind to rounding noise, which leaves a norm all but unchanged; this one
  is what tells a lower precision apart (see PERF.md).
"""
from __future__ import annotations

import math
import statistics
import time
from functools import partial
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .references import adamw, data, load_reference

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "grad_err")
#: the control's precision: fp8 training's formats, float8_e4m3fn for the
#: operands of the forward products and float8_e5m2 for the gradients
CONTROL = (jnp.float8_e4m3fn, jnp.float8_e5m2)


def _leaf_norms(tree) -> List[jax.Array]:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def grad_norm_probe(traffic: dict):
    """Jitted per-leaf norms of the first gradient, read from the opt state
    after one step (mu = (1 - b1) g, as mu starts at zero)."""
    b1 = traffic["optimizer"]["b1"]
    return jax.jit(lambda opt_state: _leaf_norms(
        jax.tree.map(lambda mu: mu / (1 - b1), opt_state.mu)))


def to_floats(xs) -> List[float]:
    return [float(x) for x in jax.device_get(xs)]


def _replicated(devices):
    mesh = Mesh(np.array(devices), ("data",))
    return NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))


def _seed_weights(config: dict, sharding):
    """Jitted: key data -> the reference's starting weights."""
    ref = load_reference(config)
    return jax.jit(lambda kd: ref.init_params(
        jax.random.wrap_key_data(kd, impl="threefry2x32"), config["model"]),
        out_shardings=sharding)


_diff_norms = jax.jit(lambda a, b: adamw.norms(
    jax.tree.map(jnp.subtract, a, b)))


def _squares(path, x):
    """A leaf's sum of squares: one per layer for the stacked layers' leaves,
    one in all for the others."""
    if path[0].key == "layers":
        return jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim)))
    return jnp.sum(jnp.square(x))[None]


@jax.jit
def _gradient_errors(other, other_scale, grads, clip):
    """The squares (by leaf, and by layer in the stacked leaves) of
    other * other_scale - clip * grads and of clip * grads."""
    diff = jax.tree.map(lambda a, b: a * other_scale - b * clip, other,
                        grads)
    sq = partial(jax.tree_util.tree_map_with_path, _squares)
    return sq(diff), sq(jax.tree.map(lambda b: b * clip, grads))


def reference_readings(config: dict, traffic: dict, seed: int,
                       key_data: np.ndarray, devices: Sequence, low=None,
                       other_grad: Optional[list] = None,
                       other_scale: float = 1.0, keep_grad: bool = False,
                       log=None) -> dict:
    """Losses, first-gradient leaf norms and change leaf norms of the
    reference over the checked steps, all on the device, rows split over
    `devices`.  `other_grad` (host leaves, times `other_scale`) is another
    side's first gradient: its relative error against the reference's is
    returned as "grad_err", and its squares by leaf and layer as
    "grad_err_parts" (for `leaf_gaps`).  `keep_grad` returns the
    reference's own clipped first gradient as host leaves, under
    "grad_leaves"."""
    ref = load_reference(config)
    m = config["model"]
    opt = traffic["optimizer"]
    rows = traffic["rows_per_chip"] * traffic["data_parallel"]
    rep, by_row = _replicated(devices)
    init = _seed_weights(config, rep)
    value_grad = jax.jit(
        jax.value_and_grad(partial(ref.loss, m=m, low=low)),
        in_shardings=(rep, by_row), out_shardings=(rep, rep))
    step = jax.jit(partial(adamw.update, opt), donate_argnums=(0, 1, 2, 3))
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=rep)

    p = init(key_data)
    out = {"loss": []}
    for k in range(traffic["checked_steps"]):
        t = time.perf_counter()
        tokens = data.token_rows(seed, k, rows, traffic["seq_len"],
                                 m["vocab_size"])
        loss, g = value_grad(p, jax.device_put(tokens, by_row))
        if k == 0:
            # before the moments exist, so that two gradients fit
            clip = jax.jit(partial(adamw.clip_scale, opt))(g)
            if other_grad is not None:
                other = jax.device_put(
                    jax.tree.unflatten(jax.tree.structure(g), other_grad),
                    rep)
                err, norm = jax.device_get(_gradient_errors(
                    other, jnp.float32(other_scale), g, clip))
                del other
                err = [e.astype(np.float64) for e in jax.tree.leaves(err)]
                norm = [n.astype(np.float64) for n in jax.tree.leaves(norm)]
                out["grad_err"] = math.sqrt(sum(map(np.sum, err))
                                            / sum(map(np.sum, norm)))
                out["grad_err_parts"] = (err, norm)
            if keep_grad:
                out["grad_leaves"] = [np.asarray(x) * np.float32(clip)
                                      for x in jax.tree.leaves(g)]
            mu, nu = zeros(p), zeros(p)
        p, mu, nu, g_norms = step(p, g, mu, nu, *adamw.hyper(opt, k))
        out["loss"].append(float(loss))
        if k == 0:
            out["grad"] = to_floats(g_norms)
        if log:
            log(f"reference step {k}: {time.perf_counter() - t:.2f} s")
    del mu, nu, g
    out["change"] = to_floats(_diff_norms(p, init(key_data)))
    return out


def program_change(config: dict, key_data: np.ndarray, devices: Sequence,
                   params: list) -> List[float]:
    """Per-leaf norms of the program's change: its weights after the
    checked steps (host leaves) less the seed's starting weights."""
    rep, _ = _replicated(devices)
    p0 = _seed_weights(config, rep)(key_data)
    mine = jax.device_put(
        jax.tree.unflatten(jax.tree.structure(p0), params), rep)
    return to_floats(_diff_norms(mine, p0))


def _worst_leaf(got: Sequence[float], want: Sequence[float],
                keep: Optional[Sequence[bool]] = None) -> float:
    med = statistics.median(want)
    keep = keep or [True] * len(want)
    gaps = [abs(g - w) / max(w, med)
            for g, w, k in zip(got, want, keep) if k]
    return max(gaps) if all(map(math.isfinite, got)) else math.inf


def compare(prog: Dict[str, List[float]], ref: Dict[str, List[float]]
            ) -> Dict[str, float]:
    """The three numbers of the module docstring."""
    first, want = prog["loss"][0], ref["loss"][0]
    med = statistics.median(ref["grad"])
    moving = [g >= 1e-3 * med for g in ref["grad"]]
    err = ref.get("grad_err", math.nan)
    return {
        "loss_gap": abs(first - want) / abs(want) if math.isfinite(first)
        else math.inf,
        "grad_gap": _worst_leaf(prog["grad"], ref["grad"]),
        "update_gap": _worst_leaf(prog["change"], ref["change"], moving),
        "grad_err": err if math.isfinite(err) else math.inf,
    }


def leaf_gaps(config: dict, prog: Dict[str, List[float]],
              ref: Dict[str, List[float]]) -> Dict[str, Dict[str, float]]:
    """Each leaf's gap behind `grad_gap` and `update_gap`, and its share
    of `grad_err`, by leaf name (for the look at which leaf or layer sets
    a reading)."""
    shapes = load_reference(config).param_shapes(config["model"])
    paths, _ = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    names = ["/".join(str(k.key) for k in path) for path, _ in paths]
    out = {}
    for kind, key in (("grad", "grad"), ("update", "change")):
        med = statistics.median(ref[key])
        out[kind] = {n: abs(g - w) / max(w, med)
                     for n, g, w in zip(names, prog[key], ref[key])}
    if "grad_err_parts" in ref:
        # each leaf's relative error and its share of the squared error;
        # the stacked leaves' relative error layer by layer
        err, norm = ref["grad_err_parts"]
        total = sum(map(np.sum, err))
        out["grad_err"] = {n: [math.sqrt(e.sum() / d.sum()), e.sum() / total]
                           for n, e, d in zip(names, err, norm)}
        stacked = [(e, d) for n, e, d in zip(names, err, norm)
                   if n.startswith("layers/")]
        out["grad_err_by_layer"] = list(np.sqrt(
            sum(e for e, _ in stacked) / sum(d for _, d in stacked)))
    return out


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """True when every number the cell compares (those `limits` names) is
    within its limit; a NaN is never within."""
    return all(numbers[k] <= limit for k, limit in limits.items())
