"""Weights made from ``--seed``, on the device, in one jitted call.

``canonical`` names each weight as the published architecture does and is
what the reference reads; ``program_tree`` lays the same arrays out as the
program's parameter tree.  Both are float32: the program keeps float32
master weights and casts them to its compute type inside the step.

Scales: a matrix is N(0, 1/fan_in), the embedding and the router
N(0, 0.02^2), and every norm scale 1 + N(0, 0.1^2), so that a norm whose
scale is dropped shows in the logits.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number up to 2**64 - 1."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    words = np.asarray([seed >> 32, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words), impl="threefry2x32")


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


def padded_vocab(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple


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


def make_program_params(cfg: dict, seed: int, abstract) -> dict:
    """The program's parameters for ``seed``, made on the default device in
    one jitted call.  ``abstract`` is the program's own shape tree: a
    layout that does not match it raises before anything is served."""
    fn = jax.jit(lambda key: program_tree(cfg, canonical(cfg, key)))
    key = seed_key(seed)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), abstract)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                       jax.eval_shape(fn, key))
    if want != got:
        raise ValueError("the benchmark's weight layout does not match the "
                         f"program's parameter tree:\nprogram {want}\n"
                         f"benchmark {got}")
    return fn(key)


def make_canonical(cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """The same weights, as the reference names them."""
    return jax.jit(lambda key: canonical(cfg, key))(seed_key(seed))
