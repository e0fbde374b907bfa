"""The Llama-style decoder family, dense or with a mixture of experts: the
family of ``smollm-360m`` and of the small cells the tests run.

Pre-norm RMSNorm blocks, rotary position embeddings (rotate-half, one
``rope_theta``), grouped-query causal attention over every position in
every layer, a SwiGLU FFN or a softmax top-k mixture of SwiGLU experts with
renormalised gates (every expert the router scores stored), and tied
embeddings: the LM head is the embedding.

Weights: a matrix is N(0, 1/fan_in), the embedding and the router
N(0, 0.02^2), and every norm scale 1 + N(0, 0.1^2), so that a norm whose
scale is dropped shows in the logits.

The reference's control (``low=True``) is the same pass with every linear
layer's weights (per output channel) and inputs (per token) rounded to
symmetric int8 (W8A8), the step below the configuration's bfloat16 that
would tempt a later PR.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp

from bench.reference import HI, _attention, _mm, _rms, _rope
from bench.weights import padded_vocab

# ---------------------------------------------------------------------------
# weights

def canonical(cfg: dict, key: jax.Array) -> Dict[str, jax.Array]:
    L, d, f = cfg["n_layers"], cfg["d_model"], cfg["d_ff"]
    H, KV, dh, V = cfg["n_heads"], cfg["n_kv_heads"], cfg["d_head"], \
        cfg["vocab_size"]
    moe = cfg.get("moe")
    shapes = {
        "embed": ((V, d), 0.02),
        "final_norm": ((d,), None),
        "ln1": ((L, d), None),
        "ln2": ((L, d), None),
        "wq": ((L, d, H * dh), 1 / math.sqrt(d)),
        "wk": ((L, d, KV * dh), 1 / math.sqrt(d)),
        "wv": ((L, d, KV * dh), 1 / math.sqrt(d)),
        "wo": ((L, H * dh, d), 1 / math.sqrt(H * dh)),
    }
    if moe:
        E = moe["n_experts"]
        shapes.update({
            "router": ((L, d, E), 0.02),
            "w_gate": ((L, E, d, f), 1 / math.sqrt(d)),
            "w_up": ((L, E, d, f), 1 / math.sqrt(d)),
            "w_down": ((L, E, f, d), 1 / math.sqrt(f)),
        })
    else:
        shapes.update({
            "w_gate": ((L, d, f), 1 / math.sqrt(d)),
            "w_up": ((L, d, f), 1 / math.sqrt(d)),
            "w_down": ((L, f, d), 1 / math.sqrt(f)),
        })
    keys = jax.random.split(key, len(shapes))
    out = {}
    for k_, (name, (shape, std)) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k_, shape, jnp.float32)
        out[name] = 1.0 + 0.1 * z if std is None else z * std
    return out


def program_tree(cfg: dict, w: Dict[str, jax.Array]) -> dict:
    """The program's parameter layout (``repro.models.transformer.decls``)
    filled with the canonical weights; vocabulary padding rows are 0."""
    vpad = padded_vocab(cfg["vocab_size"])
    embed = jnp.zeros((vpad, cfg["d_model"]), jnp.float32)
    embed = embed.at[:cfg["vocab_size"]].set(w["embed"])
    mlp = {k: w[k] for k in ("w_gate", "w_up", "w_down")}
    if cfg.get("moe"):
        mlp["router"] = w["router"]
    return {
        "embed": embed,
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["ln1"]},
            "ln2": {"scale": w["ln2"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
            "mlp": mlp,
        },
    }


# ---------------------------------------------------------------------------
# the reference forward pass

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


# ---------------------------------------------------------------------------
# useful work: valid positions only, the top-k experts of a token

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer: attention
    projections plus the FFN of the experts it is routed to (and the
    router) — the active parameters, not the stored ones."""
    d, dh = cfg["d_model"], cfg["d_head"]
    attn = d * cfg["n_heads"] * dh * 2 + d * cfg["n_kv_heads"] * dh * 2
    ffn = 3 * d * cfg["d_ff"]
    moe = cfg.get("moe")
    if moe:
        return attn + moe["top_k"] * ffn + d * moe["n_experts"]
    return attn + ffn


def token_flops(cfg: dict, attended: int, head: bool) -> float:
    """Forward FLOPs of one token that attends ``attended`` positions
    (itself included); ``head`` adds the LM head, which a prefilled prompt
    token does not run."""
    L, H, dh = cfg["n_layers"], cfg["n_heads"], cfg["d_head"]
    f = 2.0 * L * layer_matmul_params(cfg) + 4.0 * L * H * dh * attended
    if head:
        f += 2.0 * cfg["d_model"] * cfg["vocab_size"]
    return f


def chunk_flops(cfg: dict, start: int, length: int) -> float:
    """FLOPs of prefilling prompt positions [start, start + length)."""
    L, H, dh = cfg["n_layers"], cfg["n_heads"], cfg["d_head"]
    # token j attends start + j + 1 positions (causal, itself included)
    attended = length * start + length * (length + 1) / 2.0
    return (2.0 * L * layer_matmul_params(cfg) * length
            + 4.0 * L * H * dh * attended)


def _kv_bytes(cfg: dict) -> int:
    return _DTYPE_BYTES[cfg.get("kv_cache_dtype", "bfloat16")]


def decode_attention_work(cfg: dict, cached: Iterable[int]
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged decode kernel over all layers for one
    step, given the cached positions each decoding row reads (the current
    token's own column is merged outside the kernel).  Bytes: K and V of
    the valid positions, the float32 query and the float32 partials out."""
    L, H, KV, dh = (cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["d_head"])
    flops = nbytes = 0.0
    for p in cached:
        flops += 4.0 * H * dh * p
        nbytes += 2.0 * KV * dh * p * _kv_bytes(cfg) + 4.0 * (2 * H * dh
                                                              + 2 * H)
    return L * flops, L * nbytes
