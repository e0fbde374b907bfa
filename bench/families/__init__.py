"""Architecture families.  Each configuration file under ``bench/configs``
names, under its key ``family``, the module (a dotted path) that holds all
the benchmark knows of its architecture:

* ``canonical(cfg, key)`` and ``program_tree(cfg, w)``: the weights made
  from the seed, named as the published architecture names them, and the
  same arrays laid out as the program's parameter tree;
* ``hidden_states(cfg, w, tokens, *, low)`` and ``logits(w, h)``: the plain
  float32 reference forward pass, and with ``low=True`` its control one
  precision below the configuration's;
* ``token_flops(cfg, attended, head)``, ``chunk_flops(cfg, start, n)`` and
  ``decode_attention_work(cfg, cached)``: the useful work that the roofline
  and MFU readers divide by.

What every family shares stays in ``bench.reference`` (the float32 and int8
building blocks, ``run_rows``), ``bench.weights`` (the seed's key, the
jitted makers) and ``bench.roofline`` (the peaks, ``roofline_share``).  A new
architecture brings its family module beside its configuration, traffic,
limits and metric readers, and changes no file that is already here.
"""
from __future__ import annotations

import importlib
import types

INTERFACE = ("canonical", "program_tree", "hidden_states", "logits",
             "token_flops", "chunk_flops", "decode_attention_work")


def load(conf: dict) -> types.ModuleType:
    """The family module a configuration file names under ``family``."""
    if "family" not in conf:
        raise ValueError(
            f"configuration {conf.get('name', '?')!r} has no 'family' key: "
            "it has to name its architecture's module, e.g. "
            '"family": "bench.families.llama"')
    module = importlib.import_module(conf["family"])
    missing = [n for n in INTERFACE if not callable(getattr(module, n, None))]
    if missing:
        raise AttributeError(f"family {conf['family']!r} lacks {missing}")
    return module
