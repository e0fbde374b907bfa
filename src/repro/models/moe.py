"""Mixture-of-Experts block: top-k token-choice routing.

Two execution paths:

* ``dense``  — oracle path: every expert runs on every token, outputs are
  gate-weighted.  O(E x) compute, used for reduced smoke configs and as the
  correctness reference for the EP path.
* ``ep``     — expert-parallel path (production): experts are sharded over
  the ``model`` mesh axis; activations are replicated over that axis (as
  they are under tensor parallelism), so *dispatch is local*: each shard
  gathers the top-capacity tokens for its own experts, runs the expert FFN,
  scatter-adds into the output and psum-combines over the model axis.
  Capacity-dropping semantics follow GShard/Switch.

Router aux losses (load-balance + z-loss) are returned for the train loss.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import parallel
from repro.models.common import Param, scoped, swiglu


def moe_decls(cfg) -> Dict[str, Param]:
    E, d, f = cfg.moe.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": Param((d, E), (None, None), "small"),
        "w_gate": Param((E, d, f), ("experts", "embed", "mlp")),
        "w_up": Param((E, d, f), ("experts", "embed", "mlp")),
        "w_down": Param((E, f, d), ("experts", "mlp", "embed")),
    }


def _router(params, x, cfg):
    """x (T, d) -> probs (T, E), aux losses."""
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top = jax.lax.top_k(probs, cfg.moe.top_k)
    gates, idx = top                                  # (T, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    # GShard load-balance loss + z-loss
    E = cfg.moe.n_experts
    me = jnp.mean(probs, axis=0)                      # mean prob per expert
    ce = jnp.mean(jax.nn.one_hot(idx[:, 0], E), axis=0)
    aux = E * jnp.sum(me * ce) * cfg.moe.router_aux_coef
    z = jnp.mean(jnp.square(jax.scipy.special.logsumexp(logits, axis=-1)))
    aux = aux + z * cfg.moe.router_z_coef
    return probs, gates, idx, aux


def _expert_ffn(w_gate, w_up, w_down, x):
    """x (..., C, d) with stacked expert dim leading on weights."""
    h = swiglu(jnp.einsum("ecd,edf->ecf", x, w_gate),
               jnp.einsum("ecd,edf->ecf", x, w_up))
    return jnp.einsum("ecf,efd->ecd", h, w_down)


def moe_dense(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Oracle: run all experts on all tokens. x (B,S,d)."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    probs, gates, idx, aux = _router(params, xt, cfg)
    E = cfg.moe.n_experts
    dt = x.dtype
    xe = jnp.broadcast_to(xt[None], (E, b * s, d)).astype(dt)
    ye = _expert_ffn(params["w_gate"].astype(dt), params["w_up"].astype(dt),
                     params["w_down"].astype(dt), xe)       # (E, T, d)
    comb = jnp.zeros((b * s, E), jnp.float32)
    comb = jax.vmap(lambda c, i, g: c.at[i].add(g))(comb, idx, gates)
    y = jnp.einsum("etd,te->td", ye.astype(jnp.float32), comb)
    return y.reshape(b, s, d).astype(dt), aux


def moe_ep(params, x, cfg, ctx: parallel.ParallelContext) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel shard_map path. x (B,S,d) sharded batch->data axes,
    replicated over model; expert weights sharded experts->model."""
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    ax = ctx.model_axis
    n_shards = ctx.mesh.shape[ax]
    E_loc = E // n_shards
    dspec = ctx.rules.get("batch")
    b, s, d = x.shape
    dt = x.dtype

    def shard_fn(router, w_gate, w_up, w_down, x):
        bl = x.shape[0]
        T = bl * s
        xt = x.reshape(T, d)
        pr = {"router": router}
        probs, gates, idx, aux = _router(pr, xt, cfg)
        cap = int((T * k / E) * cfg.moe.capacity_factor) + 1
        shard = jax.lax.axis_index(ax)
        # score of each token for each *local* expert (0 if not routed there)
        local_ids = shard * E_loc + jnp.arange(E_loc)             # (E_loc,)
        sel = (idx[None] == local_ids[:, None, None])             # (E_loc, T, k)
        score = jnp.sum(jnp.where(sel, gates[None], 0.0), axis=-1)  # (E_loc, T)
        routed = jnp.any(sel, axis=-1)                            # (E_loc, T)
        # top-capacity tokens per local expert (capacity dropping)
        top_scores, top_idx = jax.lax.top_k(
            jnp.where(routed, score, -1.0), min(cap, T))          # (E_loc, C)
        keep = top_scores > 0.0
        xc = jnp.take(xt, top_idx.reshape(-1), axis=0)
        xc = xc.reshape(E_loc, -1, d).astype(dt)                  # (E_loc, C, d)
        yc = _expert_ffn(w_gate.astype(dt), w_up.astype(dt), w_down.astype(dt), xc)
        yc = yc.astype(jnp.float32) * (top_scores * keep)[..., None]
        out = jnp.zeros((T, d), jnp.float32)
        out = out.at[top_idx.reshape(-1)].add(yc.reshape(-1, d))
        # combine expert partials in bf16: halves the per-layer all-reduce
        # bytes (§Perf iteration B2); each shard's partial is an f32
        # accumulation, only the cross-shard combine is bf16.
        out = jax.lax.psum(out.astype(dt), ax).astype(jnp.float32)
        # aux is identical across model shards (router inputs replicated) but
        # differs across data shards -> average it so it is fully replicated.
        for a in ctx.data_axes:
            if ctx.mesh.shape[a] > 1:
                aux = jax.lax.pmean(aux, a)
        return out.reshape(bl, s, d).astype(dt), aux

    y, aux = jax.shard_map(
        shard_fn, mesh=ctx.mesh,
        in_specs=(P(), P(ax), P(ax), P(ax), P(dspec)),
        out_specs=(P(dspec), P()),
        check_vma=False,
    )(params["router"], params["w_gate"], params["w_up"], params["w_down"], x)
    return y, aux


@scoped("mlp")
def moe_block(params, x, cfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    ctx = parallel.current_ctx()
    if ctx is not None and ctx.ep_moe:
        return moe_ep(params, x, cfg, ctx)
    return moe_dense(params, x, cfg)
