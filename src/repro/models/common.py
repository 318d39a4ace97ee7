"""Shared model building blocks (pure JAX — no flax).

Parameters are nested dicts of jnp arrays; layer stacks are stored stacked
along a leading [L, ...] axis so the forward pass is a single `lax.scan`
over layers (O(1) HLO size — essential for compiling 40-layer models for a
512-device mesh on this container).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Params = Dict[str, Any]


# ---------------------------------------------------------------------- #
# config
# ---------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | vlm | audio | hybrid | ssm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: Optional[int] = None   # default d_model // num_heads
    # attention flavour
    rope_theta: float = 10_000.0
    sliding_window: Optional[int] = None
    local_global_pattern: bool = False      # gemma2: alternate local/global
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    qk_norm: bool = False
    # moe
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_d_ff: int = 0
    capacity_factor: float = 1.25
    # ssm (mamba2 / SSD)
    ssm_state_dim: int = 0
    ssm_num_heads: int = 0
    ssm_head_dim: int = 64
    ssm_chunk: int = 64
    ssm_conv_width: int = 4
    ssm_expand: int = 2
    # hybrid (zamba2): the shared transformer block runs before the Mamba2
    # layer at every `hybrid_attn_every`-th layer (ids k, 2k, ...), with
    # per-site rank-`adapter_rank` adapters (on q, k and v too when
    # `shared_attn_adapters`)
    hybrid_attn_every: int = 0
    adapter_rank: int = 0
    shared_attn_adapters: bool = False
    # encoder-decoder (whisper)
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    encoder_seq: int = 1500
    # vlm (paligemma): prefix-lm over image tokens
    num_image_tokens: int = 0
    # misc
    tie_embeddings: bool = True
    norm_eps: float = 1e-6
    activation: str = "silu"
    mlp_variant: str = "gated"       # gated (SwiGLU/GeGLU) | plain (fc1/fc2)
    sandwich_norm: bool = False      # gemma2 pre+post block norms
    scale_embeddings: bool = False   # gemma-family sqrt(d) embedding scale
    max_seq_len: int = 131_072
    dtype: Any = jnp.float32         # compute dtype (bf16 on TPU)

    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // self.num_heads)

    @property
    def q_groups(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    @property
    def num_shared_sites(self) -> int:
        """Hybrid layers: ids hybrid_attn_every * k (k >= 1) below
        num_layers."""
        if not self.hybrid_attn_every:
            return 0
        return (self.num_layers - 1) // self.hybrid_attn_every

    def _mamba2_layer_params(self) -> int:
        """One Mamba2 layer: pre-norm, in_proj, conv (with bias), A, D,
        dt bias, gated-norm weight and out_proj."""
        d, n = self.d_model, self.ssm_state_dim
        din = self.ssm_expand * d
        h = self.ssm_num_heads or din // self.ssm_head_dim
        conv = din + 2 * n
        return (d + d * (2 * din + 2 * n + h) + (self.ssm_conv_width + 1)
                * conv + 3 * h + din + din * d)

    def _shared_block_params(self) -> int:
        """The hybrid family's shared block: attention over [h, embedding]
        (width 2 d) and the gated MLP, with their norms."""
        d, hd = self.d_model, self.hd
        q, kv = self.num_heads * hd, self.num_kv_heads * hd
        return 2 * d + 2 * d * (q + 2 * kv) + q * d + d + 3 * d * self.d_ff

    def _site_params(self) -> int:
        """Per hybrid layer: its `linear` and adapters."""
        d, r, hd = self.d_model, self.adapter_rank, self.hd
        n = d * d + r * (d + 2 * self.d_ff)
        if self.shared_attn_adapters:
            n += r * (3 * 2 * d + (self.num_heads + 2 * self.num_kv_heads)
                      * hd)
        return n

    def param_count(self) -> int:
        """Parameter count (reported in roofline MODEL_FLOPS); exact for the
        ssm and hybrid families, whose layers are counted leaf by leaf, and
        the stored total for hybrid (the shared block once, where a site
        applies it)."""
        d, v, l = self.d_model, self.vocab_size, self.num_layers
        emb = v * d * (1 if self.tie_embeddings else 2)
        if self.family == "ssm":
            return emb + l * self._mamba2_layer_params() + d
        if self.family == "hybrid":
            n = self.num_shared_sites
            return (emb + l * self._mamba2_layer_params() + d
                    + n * self._site_params()
                    + (self._shared_block_params() if n else 0))
        att = d * self.num_heads * self.hd + 2 * d * self.num_kv_heads * self.hd \
            + self.num_heads * self.hd * d
        if self.num_experts:
            ff = self.num_experts * 3 * d * self.moe_d_ff \
                + self.num_shared_experts * 3 * d * self.moe_d_ff \
                + d * self.num_experts
        else:
            ff = 3 * d * self.d_ff
        total = emb + l * (att + ff)
        if self.is_encoder_decoder:
            total += self.encoder_layers * (att + 3 * d * self.d_ff) \
                + l * att  # cross attention
        return total

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed-in experts count;
        hybrid: the shared block once for each site that applies it)."""
        if self.family == "hybrid":
            return self.param_count() + max(self.num_shared_sites - 1, 0) \
                * self._shared_block_params()
        if not self.num_experts:
            return self.param_count()
        d, l = self.d_model, self.num_layers
        att = d * self.num_heads * self.hd + 2 * d * self.num_kv_heads * self.hd \
            + self.num_heads * self.hd * d
        ff_active = (self.num_experts_per_tok + self.num_shared_experts) \
            * 3 * d * self.moe_d_ff + d * self.num_experts
        emb = self.vocab_size * d * (1 if self.tie_embeddings else 2)
        return emb + l * (att + ff_active)


# ---------------------------------------------------------------------- #
# activation-sharding policy (set by the launcher; models stay mesh-free)
# ---------------------------------------------------------------------- #

_ACT_SHARDING: Dict[str, Any] = {}


def set_activation_sharding(policy: Optional[Dict[str, Any]]) -> None:
    """policy: {kind: NamedSharding} for kinds 'residual' [B,S,d] and
    'logits' [B,S,V].  The launcher installs these so GSPMD keeps the batch
    dim on the data axes instead of replicating activations."""
    _ACT_SHARDING.clear()
    if policy:
        _ACT_SHARDING.update(policy)


def _divides(x: jax.Array, sh: Any) -> bool:
    spec = getattr(sh, "spec", None)
    if spec is None:
        return True
    mesh = sh.mesh
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, names in enumerate(spec):
        if names is None:
            continue
        names = names if isinstance(names, tuple) else (names,)
        size = 1
        for n in names:
            size *= sizes[n]
        if dim >= x.ndim or x.shape[dim] % size:
            return False
    return True


def constrain(x: jax.Array, kind: str) -> jax.Array:
    """Apply the first policy candidate whose named dims divide x's shape
    (policies may be a single NamedSharding or an ordered candidate list);
    no-op when nothing fits (decode's seq=1, odd vocabs, few heads)."""
    cands = _ACT_SHARDING.get(kind)
    if cands is None:
        return x
    if not isinstance(cands, (list, tuple)):
        cands = (cands,)
    for sh in cands:
        if _divides(x, sh):
            return jax.lax.with_sharding_constraint(x, sh)
    return x


# ---------------------------------------------------------------------- #
# primitives
# ---------------------------------------------------------------------- #

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + w.astype(jnp.float32))).astype(dt)


def activation_fn(name: str) -> Callable[[jax.Array], jax.Array]:
    """The activation named: "gelu" is the tanh approximation,
    "gelu_exact" the erf form."""
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu,
            "gelu_exact": functools.partial(jax.nn.gelu, approximate=False),
            "relu": jax.nn.relu}[name]


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: [..., S, H, D]; positions: [..., S]."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # [D/2]
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs  # [...,S,1,D/2]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------- #
# init helpers
# ---------------------------------------------------------------------- #

def dense_init(key: jax.Array, shape: Tuple[int, ...],
               dtype=jnp.float32, scale: Optional[float] = None) -> jax.Array:
    fan_in = shape[0] if len(shape) >= 2 else 1
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def stacked_init(key: jax.Array, num: int, init_fn) -> Any:
    """Initialise `num` copies of a param tree and stack leaves on axis 0."""
    keys = jax.random.split(key, num)
    trees = [init_fn(k) for k in keys]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def param_count_tree(params: Params) -> int:
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))


# ---------------------------------------------------------------------- #
# masks
# ---------------------------------------------------------------------- #

NEG_INF = -2.0 ** 30


def causal_mask(q_len: int, kv_len: int, *, q_offset: int = 0,
                window: Optional[int] = None,
                prefix_len: int = 0) -> jax.Array:
    """[q_len, kv_len] additive mask.  q position i attends kv position j iff
    j <= i + q_offset (causal), within `window` if set, or unconditionally
    when j < prefix_len (prefix-LM bidirectional region)."""
    q_pos = jnp.arange(q_len)[:, None] + q_offset
    kv_pos = jnp.arange(kv_len)[None, :]
    ok = kv_pos <= q_pos
    if window is not None:
        ok &= kv_pos > q_pos - window
    if prefix_len:
        ok |= kv_pos < prefix_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       ignore_index: int = -100) -> jax.Array:
    """Mean token NLL; logits [..., V], labels [...] int."""
    valid = labels != ignore_index
    safe = jnp.where(valid, labels, 0)
    logz = jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1)
    gold = jnp.take_along_axis(
        logits.astype(jnp.float32), safe[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * valid
    return nll.sum() / jnp.maximum(valid.sum(), 1)
