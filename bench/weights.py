"""Weights made from ``--seed``, on the device, in one jitted call.

A family's ``canonical`` (``bench.families``) names each weight as the
published architecture does and is what the reference reads; its
``program_tree`` lays the same arrays out as the program's parameter tree.
Both are float32: the program keeps float32 master weights and casts them
to its compute type inside the step.
"""
from __future__ import annotations

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


def padded_vocab(v: int, multiple: int = 256) -> int:
    return -(-v // multiple) * multiple


def make_program_params(family, cfg: dict, seed: int, abstract) -> dict:
    """The program's parameters for ``seed``, made by ``family`` on the
    default device in one jitted call.  ``abstract`` is the program's own
    shape tree: a layout that does not match it raises before anything is
    served."""
    fn = jax.jit(lambda key: family.program_tree(cfg,
                                                 family.canonical(cfg, key)))
    key = seed_key(seed)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), abstract)
    got = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)),
                       jax.eval_shape(fn, key))
    if want != got:
        raise ValueError("the benchmark's weight layout does not match the "
                         f"program's parameter tree:\nprogram {want}\n"
                         f"benchmark {got}")
    return fn(key)


def make_canonical(family, cfg: dict, seed: int) -> Dict[str, jax.Array]:
    """The same weights, as ``family``'s reference names them."""
    return jax.jit(lambda key: family.canonical(cfg, key))(seed_key(seed))
