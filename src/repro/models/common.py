"""Shared model building blocks (pure-JAX, no flax).

Parameters are declared once as ``Param`` leaves carrying shape, initializer
and *logical axes*; ``init_params`` instantiates them (works under
``jax.eval_shape`` for the allocation-free dry-run) and ``param_specs``
derives the matching ``PartitionSpec`` tree for any sharding policy.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

# ---------------------------------------------------------------------------
# Named scopes


def scoped(name: str) -> Callable[[Callable], Callable]:
    """Decorator: trace the function under ``jax.named_scope(name)``, so
    every op it emits carries ``name`` in its metadata and a device trace
    attributes the op to the phase that emitted it.  The model's phases
    carry plain names (``kv_write``, ``decode_attention``, ``mlp``); the
    serving step names its own ``orca/...``.  Metadata only: the
    computation is unchanged."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return deco


# ---------------------------------------------------------------------------
# Param declarations


@dataclasses.dataclass(frozen=True)
class Param:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]       # logical axes, len == ndim
    init: str = "normal"                  # normal | zeros | ones | embed | small
    scale: float = 1.0
    dtype: str = "float32"

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _leaf_init(p: Param, key) -> jnp.ndarray:
    dt = jnp.dtype(p.dtype)
    if p.init == "zeros":
        return jnp.zeros(p.shape, dt)
    if p.init == "ones":
        return jnp.ones(p.shape, dt)
    if p.init == "normal":
        fan_in = p.shape[0] if len(p.shape) >= 2 else max(p.shape[-1], 1)
        std = p.scale / math.sqrt(fan_in)
        return (jax.random.normal(key, p.shape, jnp.float32) * std).astype(dt)
    if p.init == "embed":
        return (jax.random.normal(key, p.shape, jnp.float32) * 0.02 * p.scale).astype(dt)
    if p.init == "small":
        return (jax.random.normal(key, p.shape, jnp.float32) * 0.02 * p.scale).astype(dt)
    raise ValueError(p.init)


def is_param(x) -> bool:
    return isinstance(x, Param)


def init_params(decls, rng) -> Dict[str, Any]:
    leaves, treedef = jax.tree.flatten(decls, is_leaf=is_param)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [_leaf_init(p, k) for p, k in zip(leaves, keys)])


def param_specs(decls, rules: Dict[str, Any]):
    """Map logical axes -> PartitionSpec tree under a rules dict."""
    def one(p: Param) -> PartitionSpec:
        return PartitionSpec(*[rules.get(a) if a is not None else None for a in p.axes])
    return jax.tree.map(one, decls, is_leaf=is_param)


def param_shapes(decls):
    return jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.dtype(p.dtype)),
                        decls, is_leaf=is_param)


def stack_decls(decls, n: int, axis_name: Optional[str] = None):
    """Add a leading stacked-layer dim to every declaration (for lax.scan)."""
    def one(p: Param) -> Param:
        return dataclasses.replace(p, shape=(n,) + p.shape, axes=(axis_name,) + p.axes)
    return jax.tree.map(one, decls, is_leaf=is_param)


# ---------------------------------------------------------------------------
# Numerics helpers

def cdtype(cfg) -> jnp.dtype:
    return jnp.dtype(cfg.dtype)


def rmsnorm(x, weight, eps: float = 1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * weight.astype(jnp.float32)).astype(dt)


def layernorm(x, weight, bias, eps: float = 1e-5):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_decls(cfg, name: str = "norm"):
    if cfg.norm == "rmsnorm":
        return {"scale": Param((cfg.d_model,), (None,), "ones")}
    return {"scale": Param((cfg.d_model,), (None,), "ones"),
            "bias": Param((cfg.d_model,), (None,), "zeros")}


def apply_norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return rmsnorm(x, p["scale"])
    return layernorm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# Rotary position embeddings (supports partial rotary)

def rope_frequencies(d_rot: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32) / d_rot))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float,
               rotary_pct: float = 1.0) -> jnp.ndarray:
    """x: (..., seq, n_heads, d_head); positions: (..., seq)."""
    d_head = x.shape[-1]
    d_rot = int(d_head * rotary_pct)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    freqs = rope_frequencies(d_rot, theta)                      # (d_rot/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., seq, d_rot/2)
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    xr, xp = x[..., :d_rot], x[..., d_rot:]
    x1, x2 = xr[..., : d_rot // 2], xr[..., d_rot // 2:]
    out1 = x1 * cos - x2 * sin
    out2 = x2 * cos + x1 * sin
    return jnp.concatenate([out1, out2, xp], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations

def gelu(x):
    return jax.nn.gelu(x, approximate=True)


def swiglu(gate, up):
    return jax.nn.silu(gate.astype(jnp.float32)).astype(gate.dtype) * up


def relu_sq(x):
    r = jax.nn.relu(x)
    return r * r


# ---------------------------------------------------------------------------
# Cross-entropy with vocab-sharded logits

def softmax_xent(logits: jnp.ndarray, targets: jnp.ndarray,
                 mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """logits (B,S,V) [possibly vocab-sharded], targets (B,S) int32.

    Written so a vocab-sharded logits tensor never gets all-gathered
    (§Perf iteration B1): the gold logit is extracted with a masked
    reduction over the vocab axis (shard-local + small all-reduce) instead
    of take_along_axis, and logsumexp reduces over the vocab axis the same
    way.  The f32 upcast happens per-element inside the reductions.
    """
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    hit = vocab_iota == targets[..., None]
    gold = jnp.sum(jnp.where(hit, lf, 0.0), axis=-1)
    loss = lse - gold
    if mask is not None:
        loss = loss * mask
        return jnp.sum(loss) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(loss)
