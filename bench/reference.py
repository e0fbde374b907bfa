"""The plain reference: float32 forward passes written from the published
architectures, with no cache, no kernels and no batching of requests.

It imports nothing of the program.  This module holds what every family's
pass is built from (``bench.families``): float32 matmuls, their int8
control, RMSNorm, rotary embeddings (rotate-half) and causal grouped-query
attention; and ``run_rows``, which runs a family's pass over one request.
Every matmul runs at ``Precision.HIGHEST``, so float32 on the TPU is float32
and not one bfloat16 pass.

``low=True`` is the control: the pass one precision below the
configuration's, the step that would tempt a later PR (for a bfloat16
configuration, ``_mm`` rounds weights per output channel and inputs per
token to symmetric int8).  A comparison that cannot tell it from the
program is not tight enough.

A family's pass runs one layer at a time under ``lax.scan`` and attention in
blocks of query rows, so a whole long context fits beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
RMS_EPS = 1e-6


def _q8(x, axis):
    """Symmetric int8 rounding along ``axis`` (the contraction axis)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s) * s


def _mm(x, w, low: bool):
    """x (..., n) @ w (n, m) in float32, or through int8 operands."""
    if low:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.einsum("...n,nm->...m", x, w, precision=HI)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + RMS_EPS) * scale


def _rope(x, pos, theta):
    """x (T, heads, dh), rotate-half convention."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    c, s = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], -1)


def _attention(q, k, v):
    """Causal grouped-query attention, query rows in blocks.
    q (T, H, dh); k, v (T, KV, dh) -> (T, H * dh)."""
    t, h, dh = q.shape
    kv = k.shape[1]
    g = h // kv
    qb = q.reshape(t // Q_BLOCK, Q_BLOCK, kv, g, dh)
    kpos = jnp.arange(t)

    def block(args):
        i, qi = args
        s = jnp.einsum("qkgd,skd->kgqs", qi, k, precision=HI) / jnp.sqrt(
            jnp.float32(dh))
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HI)
        return o.reshape(Q_BLOCK, h * dh)

    out = jax.lax.map(block, (jnp.arange(t // Q_BLOCK), qb))
    return out.reshape(t, h * dh)


_DICT, _LIST = "{}", "[]"


def freeze(value):
    """A hashable form of a configuration (for the jit cache): dicts and
    lists at any depth become tagged tuples, so ``thaw`` rebuilds them as
    they were; a value of any other type than a JSON scalar raises."""
    if isinstance(value, dict):
        return (_DICT, tuple(sorted((k, freeze(v)) for k, v in value.items())))
    if isinstance(value, (list, tuple)):
        return (_LIST, tuple(freeze(v) for v in value))
    if value is None or isinstance(value, (bool, int, float, str)):
        return value
    raise TypeError(f"cannot freeze {value!r} of type {type(value).__name__}")


def thaw(frozen):
    """The configuration ``freeze`` was given."""
    if not isinstance(frozen, tuple):
        return frozen
    tag, body = frozen
    if tag == _DICT:
        return {k: thaw(v) for k, v in body}
    return [thaw(v) for v in body]


@functools.lru_cache(maxsize=16)
def _rows_fn(family, cfg_key, low: bool, rows: int):
    cfg = thaw(cfg_key)

    def fn(w, tokens, start):
        h = family.hidden_states(cfg, w, tokens, low=low)
        idx = start + jnp.arange(rows)
        hr = jnp.take(h, idx, axis=0, mode="clip")
        return hr, family.logits(w, hr)
    return jax.jit(fn)


def run_rows(family, cfg: dict, w, tokens, start: int, n: int, *,
             low: bool = False):
    """Hidden states and logits of positions [start, start + n) of
    ``tokens`` under ``family``'s pass, as float32 numpy.  One program per
    power-of-two row count, so the shapes a run meets compile once and then
    come from the cache."""
    rows = max(64, 1 << (max(n, 1) - 1).bit_length())
    h, lg = _rows_fn(family, freeze(cfg), bool(low), rows)(
        w, jnp.asarray(tokens, jnp.int32), jnp.int32(start))
    return np.asarray(h)[:n], np.asarray(lg)[:n]
