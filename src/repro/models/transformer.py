"""Decoder-only transformer covering the dense / moe / vlm families.

Layers are homogeneous and stacked (leading ``L`` dim) so the layer loop is a
``lax.scan`` — compile time and HLO size are O(1) in depth, which matters for
the 40-pair dry-run.  Optional activation checkpointing wraps the scanned
body.  The VLM variant consumes precomputed anyres patch embeddings through a
learned projector (vision tower stubbed per the assignment carve-out).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import parallel
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models.common import (Param, apply_norm, apply_rope, cdtype, gelu,
                                 norm_decls, scoped, stack_decls, swiglu)


# ---------------------------------------------------------------------------
# Declarations

def _attn_decls(cfg) -> Dict[str, Param]:
    d, qo, kvo = cfg.d_model, cfg.attn_out_dim, cfg.kv_out_dim
    out = {
        "wq": Param((d, qo), ("embed", "qkv")),
        "wk": Param((d, kvo), ("embed", "kv_qkv")),
        "wv": Param((d, kvo), ("embed", "kv_qkv")),
        "wo": Param((qo, d), ("qkv", "embed")),
    }
    if cfg.qkv_bias:
        out["bq"] = Param((qo,), ("qkv",), "zeros")
        out["bk"] = Param((kvo,), ("kv_qkv",), "zeros")
        out["bv"] = Param((kvo,), ("kv_qkv",), "zeros")
    return out


def _mlp_decls(cfg) -> Dict[str, Param]:
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp == "swiglu":
        return {"w_gate": Param((d, f), ("embed", "mlp")),
                "w_up": Param((d, f), ("embed", "mlp")),
                "w_down": Param((f, d), ("mlp", "embed"))}
    return {"w_in": Param((d, f), ("embed", "mlp")),
            "b_in": Param((f,), ("mlp",), "zeros"),
            "w_out": Param((f, d), ("mlp", "embed")),
            "b_out": Param((d,), (None,), "zeros")}


def layer_decls(cfg) -> Dict[str, Any]:
    out = {"ln1": norm_decls(cfg), "ln2": norm_decls(cfg),
           "attn": _attn_decls(cfg)}
    out["mlp"] = moe_mod.moe_decls(cfg) if cfg.moe is not None else _mlp_decls(cfg)
    return out


def decls(cfg) -> Dict[str, Any]:
    vpad = cfg.padded_vocab()
    tree: Dict[str, Any] = {
        "embed": Param((vpad, cfg.d_model), ("vocab", "embed"), "embed"),
        "final_norm": norm_decls(cfg),
        "layers": stack_decls(layer_decls(cfg), cfg.n_layers, "layers"),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = Param((cfg.d_model, vpad), ("embed", "vocab"))
    if cfg.arch_type == "vlm":
        vd = cfg.frontend.embed_dim
        tree["projector"] = {
            "w1": Param((vd, cfg.d_model), (None, "embed")),
            "b1": Param((cfg.d_model,), (None,), "zeros"),
            "w2": Param((cfg.d_model, cfg.d_model), ("embed", "embed2")),
            "b2": Param((cfg.d_model,), (None,), "zeros"),
        }
    if cfg.n_meta_tokens:
        tree["meta_tokens"] = Param((cfg.n_meta_tokens, cfg.d_model),
                                    (None, "embed"), "embed")
    return tree


# ---------------------------------------------------------------------------
# Blocks

@scoped("mlp")
def mlp_apply(cfg, p, x):
    dt = x.dtype
    if cfg.mlp == "swiglu":
        h = swiglu(x @ p["w_gate"].astype(dt), x @ p["w_up"].astype(dt))
        return h @ p["w_down"].astype(dt)
    h = x @ p["w_in"].astype(dt) + p["b_in"].astype(dt)
    h = gelu(h) if cfg.mlp == "gelu" else h
    return h @ p["w_out"].astype(dt) + p["b_out"].astype(dt)


def _qkv(cfg, p, x):
    dt = x.dtype
    q = x @ p["wq"].astype(dt)
    k = x @ p["wk"].astype(dt)
    v = x @ p["wv"].astype(dt)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    return q, k, v


def layer_prefill(cfg, p, x, positions, window: Optional[int]):
    """x (B,S,d) -> (x', (k,v)) for the cache."""
    b, s, d = x.shape
    h = apply_norm(cfg, p["ln1"], x)
    q, k, v = _qkv(cfg, p["attn"], h)
    q = q.reshape(b, s, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    q = parallel.constrain(q, "batch", None, "heads", None)
    o = attn.attn_prefill(q, k, v, causal=True, window=window)
    o = o.reshape(b, s, cfg.attn_out_dim) @ p["attn"]["wo"].astype(x.dtype)
    x = x + parallel.constrain(o, "batch", None, None)
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe is not None:
        m, aux = moe_mod.moe_block(p["mlp"], h, cfg)
    else:
        m, aux = mlp_apply(cfg, p["mlp"], h), jnp.float32(0)
    x = x + parallel.constrain(m, "batch", None, None)
    # cache entries in (B, KV, S, dh) layout
    return x, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)), aux


def layer_decode(cfg, p, x, cache_l, pos, valid, block_tables=None):
    """x (B,d); cache_l per-layer (B,KV,S,dh) READ-ONLY — or, with
    ``block_tables`` (B,nb), per-layer pages (P,KV,bs,dh) read through the
    table; pos (B,) absolute positions; valid (B,S) masks readable cache
    entries (current slot excluded — the new token's (k, v) attends via
    extra_kv and is written into the cache once, outside the layer scan)."""
    b, d = x.shape
    h = apply_norm(cfg, p["ln1"], x[:, None, :])[:, 0]
    q, k, v = _qkv(cfg, p["attn"], h)
    q = q.reshape(b, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q[:, None], pos[:, None], cfg.rope_theta, cfg.rotary_pct)[:, 0]
    k = apply_rope(k[:, None], pos[:, None], cfg.rope_theta, cfg.rotary_pct)[:, 0]
    if block_tables is not None:
        o = attn.attn_decode_paged(q, cache_l, block_tables, valid, x.dtype,
                                   extra_kv=(k, v))
    else:
        o = attn.attn_decode(q, cache_l, valid, x.dtype, extra_kv=(k, v))
    o = o.reshape(b, cfg.attn_out_dim) @ p["attn"]["wo"].astype(x.dtype)
    x = x + o
    h = apply_norm(cfg, p["ln2"], x[:, None, :])
    if cfg.moe is not None:
        m, _ = moe_mod.moe_block(p["mlp"], h, cfg)
    else:
        m = mlp_apply(cfg, p["mlp"], h)
    return x + m[:, 0], (k, v)


def layer_prefill_chunk(cfg, p, x, cache_l, rows, block_rows, positions,
                        valid):
    """One layer of chunked prefill: x (Bc, C, d) at absolute ``positions``
    (Bc, C); the chunk attends to readable cache entries (``valid``) plus
    causally within itself.  Returns (x', (k, v)) with k/v (Bc, KV, C, dh)
    for the page-by-page cache write outside the scan."""
    b, c, d = x.shape
    h = apply_norm(cfg, p["ln1"], x)
    q, k, v = _qkv(cfg, p["attn"], h)
    q = q.reshape(b, c, cfg.n_heads, cfg.d_head)
    k = k.reshape(b, c, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(b, c, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rotary_pct)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rotary_pct)
    o = attn.attn_prefill_chunk(q, k, v, cache_l, valid, x.dtype,
                                rows=rows, block_tables=block_rows)
    o = o.reshape(b, c, cfg.attn_out_dim) @ p["attn"]["wo"].astype(x.dtype)
    x = x + o
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe is not None:
        m, _ = moe_mod.moe_block(p["mlp"], h, cfg)
    else:
        m = mlp_apply(cfg, p["mlp"], h)
    return x + m, (k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))


def prefill_chunk(cfg, params, tokens, state, rows, pos_start, chunk_len,
                  block_rows=None):
    """Chunked prefill: run C prompt tokens of each request through the
    stack and write their K/V into the request's resident cache, resuming
    at ``pos_start`` (the request's ``prefill_progress``).

    tokens (Bc, C) int32, zero-padded past ``chunk_len``; rows (Bc,) batch
    rows of ``state`` (dense stacked cache — or, when the state carries
    ``block_tables``, the paged pool written through ``block_rows``
    (Bc, nb), the request's physical pages).  ``pos_start``/``chunk_len``
    are traced scalars, so ONE compiled executable covers every chunk of
    every prompt length.  Queries attend to already-written positions
    [0, pos_start) plus causally within the chunk; padded positions beyond
    ``chunk_len`` have their K/V writes dropped (dense: out-of-range
    scatter; paged: routed to the NULL page).  Text-only prompts (no vlm /
    meta-token prefix — those keep the one-shot ``prefill`` path).
    Returns the updated state."""
    bc, c = tokens.shape
    pos_start = jnp.asarray(pos_start, jnp.int32)
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    x = embed_tokens(cfg, params, tokens)
    positions = jnp.broadcast_to(pos_start + jnp.arange(c)[None, :], (bc, c))
    paged = "block_tables" in state
    if paged:
        assert block_rows is not None, "paged prefill_chunk needs block rows"
        scanned = {k: v for k, v in state.items() if k != "block_tables"}
        n_virtual = block_rows.shape[1] * scanned["k"].shape[3]
    else:
        scanned = state
        n_virtual = state["k"].shape[3]
    valid = jnp.broadcast_to(jnp.arange(n_virtual)[None, :] < pos_start,
                             (bc, n_virtual))

    def body(x, xs):
        p_l, cache_l = xs
        x, kv = layer_prefill_chunk(cfg, p_l, x, cache_l, rows, block_rows,
                                    positions, valid)
        return x, kv

    _, (ks, vs) = jax.lax.scan(body, x, (params["layers"], scanned))
    # ks/vs (L, Bc, KV, C, dh): one write for all layers, outside the scan
    if paged:
        pages = attn.cache_write_chunk_paged(scanned, ks, vs, block_rows,
                                             pos_start, chunk_len)
        return dict(pages, block_tables=state["block_tables"])
    return attn.cache_write_chunk(state, ks, vs, rows, pos_start, chunk_len)


def layer_prefill_packed(cfg, p, x, cache_l, rows, seg_tables, positions,
                         seg, seg_starts, chunk_mask):
    """One layer of PACKED chunked prefill: x (1, C, d) holds C tokens of
    up to R requests at per-token absolute ``positions`` (C,); each token
    attends its own request's readable cache prefix plus its own segment's
    preceding chunk tokens (``chunk_mask``).  Returns (x', (k, v)) with
    k/v (KV, C, dh) for the per-token cache write outside the scan."""
    _, c, d = x.shape
    h = apply_norm(cfg, p["ln1"], x)
    q, k, v = _qkv(cfg, p["attn"], h)
    q = q.reshape(1, c, cfg.n_heads, cfg.d_head)
    k = k.reshape(1, c, cfg.n_kv_heads, cfg.d_head)
    v = v.reshape(1, c, cfg.n_kv_heads, cfg.d_head)
    q = apply_rope(q, positions[None], cfg.rope_theta, cfg.rotary_pct)
    k = apply_rope(k, positions[None], cfg.rope_theta, cfg.rotary_pct)
    o = attn.attn_prefill_packed(q[0], k[0], v[0], cache_l, seg, seg_starts,
                                 chunk_mask, x.dtype, rows=rows,
                                 seg_tables=seg_tables)
    o = o.reshape(1, c, cfg.attn_out_dim) @ p["attn"]["wo"].astype(x.dtype)
    x = x + o
    h = apply_norm(cfg, p["ln2"], x)
    if cfg.moe is not None:
        m, _ = moe_mod.moe_block(p["mlp"], h, cfg)
    else:
        m = mlp_apply(cfg, p["mlp"], h)
    return x + m, (k[0].transpose(1, 0, 2), v[0].transpose(1, 0, 2))


def _packed_chunk_core(cfg, params, tokens, state, seg, slots, starts,
                       lengths, block_rows=None, *, depths=None,
                       ancestors=None, write=True):
    """Shared body of ``prefill_packed_chunk`` / ``verify_packed_chunk`` /
    ``verify_packed_tree``: run one fused C-token packed chunk through the
    stack, scatter each token's K/V into its own request's resident cache,
    and return ``(new_state, x, ks, vs)`` with x (1, C, d) the post-stack
    activations (the layer scan computes them either way; prefill merely
    discards them) and ks/vs (L, KV, C, dh) the chunk's own K/V.

    The default shape of a segment is a causal CHAIN at positions
    starts[r] + 0..len-1.  ``depths``/``ancestors`` (C,) generalize it to
    a candidate TREE (speculative multi-draft verify): per-token position
    becomes starts[seg] + depths and the within-chunk mask follows the
    ancestor closure instead of layout order.  ``write=False`` DEFERS the
    cache write entirely (tree verify: same-depth siblings share a target
    position, so only the accepted root-to-leaf path may land — the
    caller commits it through ``commit_packed_kv`` once acceptance is
    known; within-chunk attention never reads the cache for chunk tokens,
    so the forward is write-order independent)."""
    c = tokens.shape[0]
    seg = jnp.asarray(seg, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    starts = jnp.asarray(starts, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(lengths)[:-1]])
    off = jnp.arange(c, dtype=jnp.int32) - offsets[seg]
    valid_tok = (off >= 0) & (off < lengths[seg])
    positions = starts[seg] + (off if depths is None
                               else jnp.asarray(depths, jnp.int32))  # (C,)
    rows = slots[seg]                                    # (C,)
    chunk_mask = attn.packed_chunk_mask(seg, valid_tok, ancestors=ancestors)
    x = embed_tokens(cfg, params, tokens[None])          # (1, C, d)
    paged = "block_tables" in state
    if paged:
        assert block_rows is not None, "paged packed prefill needs block rows"
        scanned = {k: v for k, v in state.items() if k != "block_tables"}
        seg_tables = jnp.asarray(block_rows, jnp.int32)        # (R, nb)
    else:
        scanned = state
        n_virtual = state["k"].shape[3]      # dense padding-drop sentinel
        seg_tables = None

    def body(x, xs):
        p_l, cache_l = xs
        x, kv = layer_prefill_packed(cfg, p_l, x, cache_l, rows, seg_tables,
                                     positions, seg, starts, chunk_mask)
        return x, kv

    with jax.named_scope("layers"):
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], scanned))
    if not write:
        return state, x, ks, vs
    # ks/vs (L, KV, C, dh): one per-token write for all layers
    if paged:
        pages = attn.cache_write_packed_paged(scanned, ks, vs,
                                              seg_tables[seg],
                                              positions, valid_tok)
        return dict(pages, block_tables=state["block_tables"]), x, ks, vs
    wpos = jnp.where(valid_tok, positions, n_virtual)    # padding dropped
    return attn.cache_write_packed(state, ks, vs, rows, wpos), x, ks, vs


def prefill_packed_chunk(cfg, params, tokens, state, seg, slots, starts,
                         lengths, block_rows=None):
    """PACKED chunked prefill: run one fused C-token chunk carrying prompt
    tokens of up to R requests through the stack and scatter each token's
    K/V into ITS OWN request's resident cache.

    tokens (C,) int32 — the chunk, segments laid out contiguously in
    request order, zero-padded at the tail; seg (C,) int32 — segment id
    per token; slots (R,) batch rows; starts (R,) each segment's prefill
    progress (= its readable cache prefix AND the absolute position of its
    first chunk token); lengths (R,) tokens each segment contributes (0 =
    unused segment).  Dense states scatter through per-token (lane,
    position); a state carrying ``block_tables`` writes through
    ``block_rows`` (R, nb), each segment's reserved physical pages.  All
    of seg/slots/starts/lengths are traced data, so ONE compiled
    executable covers every packing shape of every prompt length — the
    single-segment call IS the unpacked chunk path.  Returns the updated
    state."""
    state, _, _, _ = _packed_chunk_core(cfg, params, tokens, state, seg,
                                        slots, starts, lengths,
                                        block_rows=block_rows)
    return state


def verify_packed_chunk(cfg, params, tokens, state, seg, slots, starts,
                        lengths, block_rows=None):
    """Speculative VERIFY pass: the packed-chunk forward with the language
    head kept.  Layout and cache semantics are ``prefill_packed_chunk``
    verbatim — each segment is one request's draft block (current token +
    proposed continuations) at absolute positions starts[r]..starts[r]+L-1,
    attending its own committed cache prefix plus causally within the
    block — but the post-stack activations feed final_norm + the LM head,
    so position j of each segment scores the model's next token after
    consuming draft token j.  Rejected positions need no undo: validity
    masks derived from ``pos`` hide them and the next verify block
    overwrites them in place before ``pos`` ever reaches them.  Returns
    (logits (C, vocab), hidden (C, d), new_state)."""
    state, x, _, _ = _packed_chunk_core(cfg, params, tokens, state, seg,
                                        slots, starts, lengths,
                                        block_rows=block_rows)
    with jax.named_scope("lm_head"):
        h = apply_norm(cfg, params["final_norm"], x)[0]   # (C, d)
        logits = logits_from_hidden(cfg, params, h)
    return logits, h, state


def verify_packed_tree(cfg, params, tokens, state, seg, slots, starts,
                       lengths, depths, ancestors, block_rows=None):
    """TREE speculative verify: the packed-chunk forward where each
    segment carries a candidate token TREE instead of a chain.

    Layout stays the packed-chunk contract (segments contiguous, tails
    dropped by ``lengths``) but two per-token arrays reshape the segment:
    ``depths`` (C,) — each token's depth in its tree, so its absolute
    position is starts[r] + depth (same-depth siblings SHARE a position,
    exactly as the committed sequence would) — and ``ancestors`` (C,) —
    parent pointers into the chunk (roots self-pointing), so each token
    attends its own root path instead of everything before it.  Position
    j of each node scores the model's next token after consuming that
    node's root-to-node path.

    The cache write is DEFERRED: same-depth siblings would race on one
    (lane, position) target and a rejected sibling could shadow the
    accepted token, so nothing lands here — the caller computes
    acceptance from the logits and commits ONLY the accepted root-to-leaf
    path through ``commit_packed_kv``.  Validity masks still expose just
    [0, pos), so the missing writes are unobservable within the step.
    Returns (logits (C, vocab), hidden (C, d), ks, vs) with ks/vs
    (L, KV, C, dh) the chunk's uncommitted K/V."""
    _, x, ks, vs = _packed_chunk_core(cfg, params, tokens, state, seg,
                                      slots, starts, lengths,
                                      block_rows=block_rows, depths=depths,
                                      ancestors=ancestors, write=False)
    with jax.named_scope("lm_head"):
        h = apply_norm(cfg, params["final_norm"], x)[0]   # (C, d)
        logits = logits_from_hidden(cfg, params, h)
    return logits, h, ks, vs


def commit_packed_kv(cfg, state, ks, vs, slots, seg, positions, valid,
                     block_rows=None):
    """Land a verify chunk's DEFERRED K/V (``verify_packed_tree``) into
    the resident caches: chunk token t writes its (lane, position) target
    iff ``valid[t]`` — the engine sets it True exactly for the accepted
    root-to-leaf path, whose targets are unique by construction (one node
    per depth), so the scatter is race-free.  ks/vs (L, KV, C, dh);
    slots (R,); seg (C,); positions (C,) absolute targets."""
    seg = jnp.asarray(seg, jnp.int32)
    slots = jnp.asarray(slots, jnp.int32)
    positions = jnp.asarray(positions, jnp.int32)
    valid = jnp.asarray(valid, bool)
    if "block_tables" in state:
        assert block_rows is not None, "paged tree commit needs block rows"
        scanned = {k: v for k, v in state.items() if k != "block_tables"}
        seg_tables = jnp.asarray(block_rows, jnp.int32)
        pages = attn.cache_write_packed_paged(scanned, ks, vs,
                                              seg_tables[seg],
                                              positions, valid)
        return dict(pages, block_tables=state["block_tables"])
    n_virtual = state["k"].shape[3]
    wpos = jnp.where(valid, positions, n_virtual)        # rejected dropped
    return attn.cache_write_packed(state, ks, vs, slots[seg], wpos)


def draft_tree_tokens(cfg, params, state, token, pos, width, depth):
    """Default tree self-draft, the ``draft_tokens`` fallback lifted to a
    (width, depth) tree: every branch repeats the last committed token.
    Only reached when the serving layer's shared draft cache MISSES (the
    cache is the real drafter for dense families); keeps the step total —
    a miss costs nothing and accepts whatever it happens to get right.
    token (B,) int32; returns (B, width, depth) int32."""
    b = token.shape[0]
    return jnp.broadcast_to(token[:, None, None], (b, width, depth))


def draft_tokens(cfg, params, state, token, pos, k):
    """Default self-draft: propose ``k - 1`` repeats of the last committed
    token (the degenerate n-gram drafter — zero extra forwards, zero extra
    state; acceptance pays for whatever it gets right).  Families with a
    cheaper oracle (e.g. the replay model, which drafts from its own
    trajectory) override this on ``Model.draft``.  token (B,) int32;
    returns (B, k - 1) int32 draft continuations."""
    b = token.shape[0]
    return jnp.broadcast_to(token[:, None], (b, k - 1))


# ---------------------------------------------------------------------------
# Embedding / logits

def embed_tokens(cfg, params, tokens):
    e = params["embed"].astype(cdtype(cfg))
    return jnp.take(e, tokens, axis=0)


def logits_from_hidden(cfg, params, h):
    dt = h.dtype
    if cfg.tie_embeddings:
        logits = h @ params["embed"].astype(dt).T
    else:
        logits = h @ params["lm_head"].astype(dt)
    axes = ("batch",) + (None,) * (logits.ndim - 2) + ("vocab",)
    return parallel.constrain(logits, *axes)


def project_patches(cfg, params, patch_embeds):
    p = params["projector"]
    dt = cdtype(cfg)
    h = gelu(patch_embeds.astype(dt) @ p["w1"].astype(dt) + p["b1"].astype(dt))
    return h @ p["w2"].astype(dt) + p["b2"].astype(dt)


# ---------------------------------------------------------------------------
# Full forward passes

def _scan_layers(cfg, params, x, positions, window, collect_kv: bool = True):
    ctx = parallel.current_ctx()

    def body(x, p_l):
        x, kv, aux = layer_prefill(cfg, p_l, x, positions, window)
        return x, ((kv, aux) if collect_kv else (None, aux))

    if ctx is not None and ctx.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, (kvs, auxs) = jax.lax.scan(body, x, params["layers"])
    return x, kvs, jnp.sum(auxs)


def forward(cfg, params, batch) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Training/prefill forward. Returns (logits, hidden, aux_loss).

    batch: {"tokens": (B, S_text)} + optional {"patch_embeds"} (vlm).
    """
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    prefix = []
    if cfg.arch_type == "vlm":
        prefix.append(project_patches(cfg, params, batch["patch_embeds"]))
    if cfg.n_meta_tokens:
        meta = params["meta_tokens"].astype(x.dtype)
        prefix.append(jnp.broadcast_to(meta[None], (x.shape[0],) + meta.shape))
    if prefix:
        x = jnp.concatenate(prefix + [x], axis=1)
    x = parallel.constrain(x, "batch", None, None)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, _, aux = _scan_layers(cfg, params, x, positions, cfg.sliding_window,
                             collect_kv=False)
    h = apply_norm(cfg, params["final_norm"], x)
    logits = logits_from_hidden(cfg, params, h)
    return logits, h, aux


def prefill(cfg, params, batch, cache_len: int):
    """Run the prompt, build the KV cache. Returns (cache, last_hidden, h_all)."""
    tokens = batch["tokens"]
    x = embed_tokens(cfg, params, tokens)
    if cfg.arch_type == "vlm" and "patch_embeds" in batch:
        x = jnp.concatenate([project_patches(cfg, params, batch["patch_embeds"]), x], 1)
    if cfg.n_meta_tokens:
        meta = params["meta_tokens"].astype(x.dtype)
        x = jnp.concatenate(
            [jnp.broadcast_to(meta[None], (x.shape[0],) + meta.shape), x], 1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x, kvs, _ = _scan_layers(cfg, params, x, positions, cfg.sliding_window)
    h = apply_norm(cfg, params["final_norm"], x)
    # place prefix kv into cache of length cache_len
    k, v = kvs                                    # (L,B,KV,S,dh)
    cache = attn.init_cache(cfg, b, cache_len)
    if "k_scale" in cache:
        kq, ks = attn.quantize_kv(k)
        vq, vs = attn.quantize_kv(v)
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, axis=3)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, axis=3)
        cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, 0, axis=3)
        cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, 0, axis=3)
    else:
        cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, axis=3)
        cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, axis=3)
    return cache, h[:, -1], h


def decode_step(cfg, params, token, cache, pos, *, window: Optional[int] = None,
                write_mask: Optional[jnp.ndarray] = None):
    """One-token decode. token (B,); pos int32 — scalar (whole batch at one
    shared length: static batching) or (B,) vector (continuous batching:
    every batch row sits at its own absolute position).

    With ``window`` set, the cache is a ring buffer of size window and
    ``slot = pos % window``; otherwise slot = pos.  A cache carrying
    ``block_tables`` is PAGED: per-layer leaves are page pools (P,KV,bs,dh)
    and each row reads/writes through its block-table row (the table itself
    is device state owned by the serving engine).  ``write_mask`` (B,) bool
    drops the dense K/V write for False rows (parked / mid-prefill slots in
    the chunked serving engine, whose lanes hold chunk-written prompt K/V a
    no-op decode write must not clobber); paged rows ignore it — their
    parked writes already land in the NULL page.  Returns (logits, hidden,
    cache).
    """
    b = token.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    x = embed_tokens(cfg, params, token)
    paged = "block_tables" in cache
    if paged:
        assert window is None, "paged decode has no ring-buffer SWA variant"
        bt = cache["block_tables"]
        pages = {k: v for k, v in cache.items() if k != "block_tables"}
        n_virtual = bt.shape[1] * pages["k"].shape[3]
        valid = attn.paged_valid_mask(pos, b, n_virtual)
        scanned = pages
    else:
        s_cache = cache["k"].shape[3]
        slot, valid = attn.decode_valid_mask(pos, b, s_cache, window)
        if write_mask is not None:
            # masked rows route their write out of range -> scatter drops it
            slot = jnp.where(write_mask, jnp.broadcast_to(slot, (b,)),
                             s_cache)
        bt = None
        scanned = cache
    positions = pos if pos.ndim == 1 else jnp.full((b,), pos, jnp.int32)

    def body(x, xs):
        p_l, cache_l = xs
        x, kv_new = layer_decode(cfg, p_l, x, cache_l, positions, valid,
                                 block_tables=bt)
        return x, kv_new

    with jax.named_scope("layers"):
        x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], scanned))
    if paged:
        new_cache = dict(attn.cache_write_paged(pages, ks, vs, bt, pos),
                         block_tables=bt)
    else:
        new_cache = attn.cache_write_stacked(cache, ks, vs, slot)
    with jax.named_scope("lm_head"):
        h = apply_norm(cfg, params["final_norm"], x[:, None, :])[:, 0]
        logits = logits_from_hidden(cfg, params, h)
    return logits, h, new_cache
