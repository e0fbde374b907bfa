"""Parallel context: logical-axis sharding rules threaded through model code.

MaxText-style logical axes: model code annotates activations with *logical*
names ("batch", "kv_seq", ...); the launcher installs a ``ParallelContext``
mapping logical names to mesh axes.  Outside any context (unit tests, CPU
smoke runs) every annotation is a no-op, so model code is mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Axes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    mesh: Mesh
    rules: Mapping[str, Axes]            # logical axis -> mesh axes
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    ep_moe: bool = False                 # expert-parallel shard_map MoE path
    flash_decode: bool = False           # seq-sharded decode attention
    attn_impl: str = "einsum"            # einsum | blockwise | pallas
    remat: bool = False

    def spec(self, logical: Sequence[Optional[str]]) -> PartitionSpec:
        out = []
        for name in logical:
            out.append(None if name is None else self.rules.get(name))
        return PartitionSpec(*out)

    def sharding(self, logical: Sequence[Optional[str]]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical))


_CTX: contextvars.ContextVar[Optional[ParallelContext]] = \
    contextvars.ContextVar("repro_parallel_ctx", default=None)


def current_ctx() -> Optional[ParallelContext]:
    return _CTX.get()


@contextlib.contextmanager
def use_parallel(ctx: ParallelContext):
    token = _CTX.set(ctx)
    try:
        with ctx.mesh:
            yield ctx
    finally:
        _CTX.reset(token)


def constrain(x, *logical: Optional[str]):
    """Annotate ``x`` with the mesh axes the active rules map ``logical`` to."""
    ctx = current_ctx()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, ctx.sharding(logical))


def attn_impl() -> str:
    ctx = current_ctx()
    return "einsum" if ctx is None else ctx.attn_impl


def remat_enabled() -> bool:
    ctx = current_ctx()
    return bool(ctx and ctx.remat)
