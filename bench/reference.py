"""The plain reference: a float32 forward pass written from the published
architecture, with no cache, no kernels and no batching of requests.

It imports nothing of the program.  It covers the decoder families of the
configurations under ``bench/configs``: pre-norm RMSNorm blocks, rotary
position embeddings (rotate-half), grouped-query causal attention, a SwiGLU
FFN or a softmax top-k mixture of SwiGLU experts with renormalised gates,
tied embeddings.  Every matmul runs at ``Precision.HIGHEST``, so float32 on
the TPU is float32 and not one bfloat16 pass.

``low=True`` is the control: the same pass with every linear layer's
weights (per output channel) and inputs (per token) rounded to symmetric
int8 (W8A8), the step below the configuration's bfloat16 that would tempt a
later PR.  A comparison that cannot tell it from the program is not tight
enough.

The pass runs one layer at a time under ``lax.scan`` and attention in
blocks of query rows, so a whole long context fits beside nothing else.
"""
from __future__ import annotations

import functools
from typing import Dict

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


def _swiglu(x, wg, wu, wd, low):
    return _mm(jax.nn.silu(_mm(x, wg, low)) * _mm(x, wu, low), wd, low)


def _moe(cfg, lw, x, low):
    """Softmax over all experts, top-k, gates renormalised over the k; the
    output is the gate-weighted sum of the k experts' SwiGLU FFNs."""
    moe = cfg["moe"]
    e = moe["n_experts"]
    logits = jnp.einsum("td,de->te", x, lw["router"], precision=HI)
    probs = jax.nn.softmax(logits, -1)
    top, idx = jax.lax.top_k(probs, moe["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    gate = jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32)
                   * top[..., None], axis=1)                       # (T, E)

    def expert(acc, ew):
        wg, wu, wd, ge = ew
        return acc + ge[:, None] * _swiglu(x, wg, wu, wd, low), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (lw["w_gate"], lw["w_up"], lw["w_down"], gate.T))
    return y


def hidden_states(cfg: dict, w: Dict[str, jax.Array], tokens, *,
                  low: bool = False):
    """Final-normed hidden states (T, d) of ``tokens`` (T,), T a multiple
    of ``Q_BLOCK``; causal, so padding at the end changes nothing before
    it."""
    t = tokens.shape[0]
    H, KV, dh = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"]
    theta = float(cfg.get("rope_theta", 10000.0))
    pos = jnp.arange(t)
    x = w["embed"][tokens]
    layer_keys = ["ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                  "w_down"] + (["router"] if cfg.get("moe") else [])

    def layer(x, lw):
        h = _rms(x, lw["ln1"])
        q = _rope(_mm(h, lw["wq"], low).reshape(t, H, dh), pos, theta)
        k = _rope(_mm(h, lw["wk"], low).reshape(t, KV, dh), pos, theta)
        v = _mm(h, lw["wv"], low).reshape(t, KV, dh)
        x = x + _mm(_attention(q, k, v), lw["wo"], low)
        h = _rms(x, lw["ln2"])
        if cfg.get("moe"):
            m = _moe(cfg, lw, h, low)
        else:
            m = _swiglu(h, lw["w_gate"], lw["w_up"], lw["w_down"], low)
        return x + m, None

    x, _ = jax.lax.scan(layer, x, {k: w[k] for k in layer_keys})
    return _rms(x, w["final_norm"])


def logits(w: Dict[str, jax.Array], h):
    """LM head (tied embedding) over the real vocabulary: (n, d) -> (n, V)."""
    return jnp.einsum("nd,vd->nv", h, w["embed"], precision=HI)


def freeze(cfg: dict):
    """A hashable form of a configuration dict (for the jit cache)."""
    return tuple(sorted((k, tuple(sorted(v.items())) if isinstance(v, dict)
                         else v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, dict))))


@functools.lru_cache(maxsize=16)
def _rows_fn(cfg_key, low: bool, rows: int):
    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_key}

    def fn(w, tokens, start):
        h = hidden_states(cfg, w, tokens, low=low)
        idx = start + jnp.arange(rows)
        hr = jnp.take(h, idx, axis=0, mode="clip")
        return hr, logits(w, hr)
    return jax.jit(fn)


def run_rows(cfg: dict, w, tokens, start: int, n: int, *,
             low: bool = False):
    """Hidden states and logits of positions [start, start + n) of
    ``tokens``, as float32 numpy.  One program per power-of-two row count,
    so the shapes a run meets compile once and then come from the cache."""
    rows = max(64, 1 << (max(n, 1) - 1).bit_length())
    h, lg = _rows_fn(freeze(cfg), bool(low), rows)(
        w, jnp.asarray(tokens, jnp.int32), jnp.int32(start))
    return np.asarray(h)[:n], np.asarray(lg)[:n]
