"""Where a launch keeps JAX's persistent compilation cache.

A full-width serving step takes tens of seconds to compile; the persistent
cache lets the next process in the same checkout read it back.  JAX keys
entries on the program and reads the directory from
``JAX_COMPILATION_CACHE_DIR`` by itself, so when that is set nothing here
touches the configuration.  Otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (gitignored) — never a temporary name, a pid or a
time, which would make every process miss.

Called from the ``__main__`` path of each entry point (and
``chip_smoke.py``), never at import: importing a module configures nothing.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional, Union

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = Path(__file__).resolve().parents[3]   # src/repro/launch -> root


def use_compile_cache(checkout: Optional[Union[str, Path]] = None) -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins untouched; else ``<checkout>/.jax_cache``
    with ``checkout`` defaulting to this repository's root."""
    env = os.environ.get(ENV)
    if env:
        return env
    path = Path(checkout or CHECKOUT).resolve() / ".jax_cache"
    jax.config.update("jax_compilation_cache_dir", str(path))
    return str(path)
