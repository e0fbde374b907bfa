"""A family added from outside ``bench/families``: the Llama family with an
untied LM head, a (d, V) matrix of its own, which the program keeps as
``lm_head`` when the configuration says ``"tie_embeddings": false``."""
import math

import jax
import jax.numpy as jnp

from bench.families import llama
from bench.reference import HI
from bench.weights import padded_vocab

hidden_states = llama.hidden_states
token_flops = llama.token_flops
chunk_flops = llama.chunk_flops
decode_attention_work = llama.decode_attention_work


def canonical(cfg, key):
    k_body, k_head = jax.random.split(key)
    w = llama.canonical(cfg, k_body)
    d = cfg["d_model"]
    w["lm_head"] = jax.random.normal(k_head, (d, cfg["vocab_size"]),
                                     jnp.float32) / math.sqrt(d)
    return w


def program_tree(cfg, w):
    """Llama's tree and the head; vocabulary padding columns are 0."""
    tree = llama.program_tree(cfg, w)
    head = jnp.zeros((cfg["d_model"], padded_vocab(cfg["vocab_size"])),
                     jnp.float32)
    tree["lm_head"] = head.at[:, :cfg["vocab_size"]].set(w["lm_head"])
    return tree


def logits(w, h):
    """The untied LM head over the real vocabulary: (n, d) -> (n, V)."""
    return jnp.einsum("nd,dv->nv", h, w["lm_head"], precision=HI)
