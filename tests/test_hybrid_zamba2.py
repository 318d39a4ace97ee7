"""The hybrid family (models/hybrid.py) against the plain Zamba2 reference
(bench/references/zamba2.py), on the CPU in float32 at the highest matmul
precision: the training loss and every gradient leaf, serving's prefill
and cached decode, the parameter tree and its count, and two wrong blocks
the comparison has to catch."""
import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model, hybrid
from repro.models.common import ModelConfig

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
from bench.references import zamba2 as ref  # noqa: E402

BENCH_CONFIG = ROOT / "bench" / "configs" / "zamba2-1.2b.json"
B, S = 2, 48            # three SSD chunks of 16
#: float32 at the highest precision on both sides: the program's chunked
#: SSD and the reference's quadratic form differ by rounding alone (read:
#: at most 1.1e-5 of a gradient leaf's norm over 13 layers, 3 seeds, the
#: loss exactly); 1e-4 leaves room for seeds, and a wrong block moves a
#: gradient leaf by over 100 times that (the loss by 2.3e-3)
RTOL = 1e-4


def small(adapters: bool = True, kv_heads: int = 4, layers: int = 13
          ) -> ModelConfig:
    """Zamba2-1.2B's flags at d_model 64: 2 periods of six at 13 layers
    (sites before layers 6 and 12); head_dim 2 d / heads, as published."""
    return dataclasses.replace(
        get_config("zamba2-1.2b"), num_layers=layers, d_model=64,
        num_heads=4, num_kv_heads=kv_heads, head_dim=32, d_ff=128,
        adapter_rank=8, shared_attn_adapters=adapters, vocab_size=256,
        ssm_state_dim=16, ssm_head_dim=16, ssm_chunk=16)


def model_dict(cfg: ModelConfig) -> dict:
    return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)
            if f.name != "dtype"}


def weights_and_tokens(cfg: ModelConfig, seed: int = 0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = ref.init_params(k1, model_dict(cfg))
    tokens = jax.random.randint(k2, (B, S), 0, cfg.vocab_size)
    return params, tokens


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def loss_and_grads(cfg: ModelConfig, params, tokens, program: bool):
    if program:
        model = build_model(cfg, remat=True)
        fn = lambda p: model.loss(p, {"tokens": tokens})[0]
    else:
        fn = lambda p: ref.loss(p, tokens, model_dict(cfg))
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(fn))(params)


CASES = [(True, 4), (False, 4), (True, 2)]


@pytest.mark.parametrize("adapters, kv_heads", CASES)
def test_loss_and_every_gradient_leaf_equal_the_reference(adapters,
                                                           kv_heads):
    cfg = small(adapters, kv_heads)
    params, tokens = weights_and_tokens(cfg)
    loss, grads = loss_and_grads(cfg, params, tokens, program=True)
    want_loss, want = loss_and_grads(cfg, params, tokens, program=False)
    assert abs(float(loss) - float(want_loss)) <= RTOL * abs(
        float(want_loss))
    paths = jax.tree_util.tree_flatten_with_path(want)[0]
    for (path, w), g in zip(paths, jax.tree.leaves(grads)):
        assert float(jnp.linalg.norm(w)) > 0, path
        assert rel_err(g, w) <= RTOL, (jax.tree_util.keystr(path),
                                       rel_err(g, w))


@pytest.mark.parametrize("prompt", [1, 17])
@pytest.mark.parametrize("adapters", [True, False])
def test_prefill_then_cached_decode_gives_the_reference_logits(prompt,
                                                               adapters):
    """Serving: prefill the first `prompt` tokens, then decode the rest one
    at a time through the sites' KV caches and the SSM states; each step's
    logits are the reference's full-forward logits at that position."""
    cfg = small(adapters)
    params, tokens = weights_and_tokens(cfg, seed=1)
    model = build_model(cfg)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p, t: ref.logits(
            p, t, model_dict(cfg)))(params, tokens))
        state = model.init_decode_state(B, S)
        state, logits = jax.jit(model.prefill)(
            params, {"tokens": tokens[:, :prompt]}, state)
        got = [logits[:, -1]]
        decode = jax.jit(model.decode_step)
        for i in range(prompt, S):
            logits, state = decode(params, tokens[:, i:i + 1], state,
                                   jnp.asarray(i, jnp.int32))
            got.append(logits[:, -1])
    got = np.stack([np.asarray(g) for g in got], axis=1)
    for j, pos in enumerate(range(prompt - 1, S)):
        assert rel_err(got[:, j], want[:, pos]) <= RTOL, pos


@pytest.mark.parametrize("adapters, layers", [(True, 13), (False, 13),
                                              (True, 8), (True, 2)])
def test_parameter_tree_is_the_references_layout(adapters, layers):
    cfg = small(adapters, layers=layers)
    shapes = build_model(cfg).init_shape()
    want = ref.param_shapes(model_dict(cfg))
    is_shape = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(shapes) == jax.tree.structure(want,
                                                            is_leaf=is_shape)
    assert [s.shape for s in jax.tree.leaves(shapes)] == \
        jax.tree.leaves(want, is_leaf=is_shape)
    assert cfg.param_count() == ref.stored_param_count(model_dict(cfg))
    assert cfg.num_shared_sites == ref.sites(model_dict(cfg)) == \
        len(hybrid.hybrid_layer_ids(cfg))


@pytest.mark.parametrize("layers, stored, six_n", [
    (38, 1_205_078_912, 1_205_078_912 + 5 * 109_058_048),
    (25, 853_055_808, 1_180_229_952),
    (19, 689_837_504, 907_953_600),
])
def test_param_count_at_the_published_widths(layers, stored, six_n):
    """The registry's Zamba2-1.2B and the benchmark's configuration (its
    25-layer cut) count what the reference stores; the 6 N count takes the
    shared block once per site."""
    cfg = dataclasses.replace(get_config("zamba2-1.2b"), num_layers=layers)
    m = model_dict(cfg)
    assert cfg.param_count() == ref.stored_param_count(m) == stored
    assert cfg.active_param_count() == ref.param_count(m) == six_n
    bench = json.loads(BENCH_CONFIG.read_text())
    m = dict(bench["model"], num_layers=layers)
    assert ModelConfig(**m).param_count() == ref.stored_param_count(m)
    if layers == bench["model"]["num_layers"]:
        assert bench["parameters"] == stored
        assert ModelConfig(**bench["model"]) == dataclasses.replace(
            get_config("zamba2-1.2b"), num_layers=layers,
            max_seq_len=bench["model"]["max_seq_len"])


def _residual_block(lp, cfg, h, st, t=None):
    """Wrong: the shared block's output added to the residual as well."""
    out, new_st = _MAMBA_LAYER(lp, cfg, h, st, t)
    return (out if t is None else out + t), new_st


_MAMBA_LAYER = hybrid._mamba_layer


@pytest.mark.parametrize("fault", ["residual", "no_concat"])
def test_a_wrong_block_fails_the_comparison(fault, monkeypatch):
    cfg = small()
    params, tokens = weights_and_tokens(cfg)
    want_loss, want = loss_and_grads(cfg, params, tokens, program=False)
    if fault == "residual":
        monkeypatch.setattr(hybrid, "_mamba_layer", _residual_block)
    else:
        monkeypatch.setattr(hybrid, "_attn_input", lambda h, emb:
                            jnp.concatenate([h, jnp.zeros_like(emb)], -1))
    loss, grads = loss_and_grads(cfg, params, tokens, program=True)
    errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(grads),
                                          jax.tree.leaves(want))]
    assert max(errs) > 100 * RTOL
    if fault == "residual":
        assert abs(float(loss) - float(want_loss)) > RTOL * abs(
            float(want_loss))
