"""GQA attention: prefill (einsum / blockwise-flash), decode (full-cache,
sequence-sharded flash-decode, ring-buffer sliding window), int8 KV cache.

Layouts
-------
q:      (B, S, H, d_head)
k, v:   (B, S, KV, d_head)
cache:  {"k","v"}: (B, KV, S_cache, d_head)  (+ "k_scale","v_scale" for int8)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import parallel
from repro.models.common import scoped

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV cache (de)quantization

def quantize_kv(x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-(position, head) absmax int8 quantization. x: (..., d_head)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype) -> jnp.ndarray:
    return (q.astype(jnp.float32) * scale).astype(dtype)


def init_cache(cfg, batch: int, length: int, n_layers: Optional[int] = None,
               abstract: bool = False) -> Dict[str, jnp.ndarray]:
    """Stacked-layer KV cache: (L, B, KV, S, d_head)."""
    L = cfg.n_layers if n_layers is None else n_layers
    shape = (L, batch, cfg.n_kv_heads, length, cfg.d_head)
    if cfg.kv_cache_dtype == "int8":
        mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
             (lambda s, d: jnp.zeros(s, d))
        return {"k": mk(shape, jnp.int8), "v": mk(shape, jnp.int8),
                "k_scale": mk(shape[:-1] + (1,), jnp.float32),
                "v_scale": mk(shape[:-1] + (1,), jnp.float32)}
    dt = jnp.dtype(cfg.dtype)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))
    return {"k": mk(shape, dt), "v": mk(shape, dt)}


@scoped("kv_write")
def cache_write(cache_l: Dict[str, jnp.ndarray], k: jnp.ndarray, v: jnp.ndarray,
                slot: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Write one token into a per-layer cache slice (B, KV, S, dh) at ``slot``."""
    def upd(buf, val):
        # val: (B, KV, d) -> (B, KV, 1, d)
        return jax.lax.dynamic_update_slice_in_dim(buf, val[:, :, None, :], slot, axis=2)
    out = dict(cache_l)
    if "k_scale" in cache_l:
        kq, ks = quantize_kv(k)
        vq, vs = quantize_kv(v)
        out["k"] = upd(cache_l["k"], kq)
        out["v"] = upd(cache_l["v"], vq)
        out["k_scale"] = upd(cache_l["k_scale"], ks)
        out["v_scale"] = upd(cache_l["v_scale"], vs)
    else:
        out["k"] = upd(cache_l["k"], k.astype(cache_l["k"].dtype))
        out["v"] = upd(cache_l["v"], v.astype(cache_l["v"].dtype))
    return out


@scoped("kv_write")
def cache_write_stacked(cache: Dict[str, jnp.ndarray], ks: jnp.ndarray,
                        vs: jnp.ndarray, slot: jnp.ndarray
                        ) -> Dict[str, jnp.ndarray]:
    """Write one token for ALL layers at once: cache (L,B,KV,S,dh),
    ks/vs (L,B,KV,dh).  One in-place (donated) update outside the layer scan
    instead of copying the cache through scan outputs (§Perf C2).

    ``slot`` is a scalar (the whole batch writes the same position — classic
    static batching) or a (B,) vector (continuous batching: each batch row
    sits at its own sequence position and writes its own slot)."""
    slot = jnp.asarray(slot)
    if slot.ndim == 1:
        iB = jnp.arange(slot.shape[0])

        def upd(buf, val):
            # advanced indices (batch row, per-row slot) sit at axes 1 and 3;
            # jax moves them to the front, so the scattered value is
            # (B, L, KV, dh) — a per-row scatter, not a full-buffer rewrite.
            # mode="drop": rows routed out of range (parked / mid-prefill
            # slots under a write mask) simply don't write
            return buf.at[:, iB, :, slot, :].set(
                val.transpose(1, 0, 2, 3).astype(buf.dtype), mode="drop")
    else:
        def upd(buf, val):
            return jax.lax.dynamic_update_slice_in_dim(
                buf, val[:, :, :, None, :], slot, axis=3)
    out = dict(cache)
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ksc)
        out["v_scale"] = upd(cache["v_scale"], vsc)
    else:
        out["k"] = upd(cache["k"], ks.astype(cache["k"].dtype))
        out["v"] = upd(cache["v"], vs.astype(cache["v"].dtype))
    return out


@scoped("decode_attention")
def decode_valid_mask(pos: jnp.ndarray, batch: int, s_cache: int,
                      window: Optional[int] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Cache write slot + readable-entry mask for one decode step.

    ``pos`` is a scalar (every batch row at the same length — static
    batching) or a (B,) vector (continuous batching: per-row absolute
    positions).  Returns (slot, valid) with slot scalar or (B,) matching
    ``pos`` and valid (B, s_cache).

    Without a window: slot = pos, valid = [0, pos).  With a window the cache
    is a ring buffer: index i holds the most recent position p <= pos with
    p % window == i, readable iff that position exists AND is < pos (the pos
    entry is stale until the post-scan write).
    """
    pos = jnp.asarray(pos, jnp.int32)
    idxs = jnp.arange(s_cache)
    pos_col = pos[:, None] if pos.ndim == 1 else pos[None, None]
    if window is not None:
        slot = jnp.mod(pos, window)
        stored = pos_col - jnp.mod(pos_col - idxs[None, :], window)
        valid = (stored >= 0) & (stored < pos_col)
    else:
        slot = pos
        valid = idxs[None, :] < pos_col
    return slot, jnp.broadcast_to(valid, (batch, s_cache))


def cache_kv(cache_l: Dict[str, jnp.ndarray], dtype) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if "k_scale" in cache_l:
        return (dequantize_kv(cache_l["k"], cache_l["k_scale"], dtype),
                dequantize_kv(cache_l["v"], cache_l["v_scale"], dtype))
    return cache_l["k"], cache_l["v"]


# ---------------------------------------------------------------------------
# Paged KV cache: a pool of fixed-size token blocks shared by all requests.
#
# Layout: {"k","v"}: (L, P, KV, bs, d_head) — P physical pages; each batch
# row reads/writes through its block table (B, nb): virtual position j lives
# in page table[j // bs] at offset j % bs.  Page 0 is the NULL page
# (``repro.serving.kv_pool.NULL_BLOCK``): parked slots point at it so their
# no-op writes can't corrupt a reallocated page.

def init_paged_cache(cfg, num_blocks: int, block_size: int,
                     n_layers: Optional[int] = None,
                     abstract: bool = False) -> Dict[str, jnp.ndarray]:
    """Stacked-layer paged KV pool: (L, P, KV, bs, d_head)."""
    L = cfg.n_layers if n_layers is None else n_layers
    shape = (L, num_blocks, cfg.n_kv_heads, block_size, cfg.d_head)
    mk = (lambda s, d: jax.ShapeDtypeStruct(s, d)) if abstract else \
         (lambda s, d: jnp.zeros(s, d))
    if cfg.kv_cache_dtype == "int8":
        return {"k": mk(shape, jnp.int8), "v": mk(shape, jnp.int8),
                "k_scale": mk(shape[:-1] + (1,), jnp.float32),
                "v_scale": mk(shape[:-1] + (1,), jnp.float32)}
    dt = jnp.dtype(cfg.dtype)
    return {"k": mk(shape, dt), "v": mk(shape, dt)}


@scoped("kv_write")
def cache_write_paged(cache: Dict[str, jnp.ndarray], ks: jnp.ndarray,
                      vs: jnp.ndarray, block_tables: jnp.ndarray,
                      pos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Write one token for ALL layers through the block tables.

    cache k/v (L, P, KV, bs, dh); ks/vs (L, B, KV, dh); block_tables
    (B, nb) int32; ``pos`` scalar or (B,) — each row writes page
    ``table[b, pos_b // bs]`` at offset ``pos_b % bs``.  Same single
    donated-buffer scatter shape as ``cache_write_stacked``."""
    bs = cache["k"].shape[3]
    B = ks.shape[1]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    iB = jnp.arange(B)
    page = block_tables[iB, pos // bs]            # (B,) physical page ids
    off = pos % bs

    def upd(buf, val):
        # advanced indices (page, offset) at axes 1 and 3 move to the
        # front: scattered value is (B, L, KV, dh) — per-row page write
        return buf.at[:, page, :, off, :].set(
            val.transpose(1, 0, 2, 3).astype(buf.dtype))

    out = dict(cache)
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ksc)
        out["v_scale"] = upd(cache["v_scale"], vsc)
    else:
        out["k"] = upd(cache["k"], ks.astype(cache["k"].dtype))
        out["v"] = upd(cache["v"], vs.astype(cache["v"].dtype))
    return out


def chunk_write_positions(pos_start: jnp.ndarray, chunk_len: jnp.ndarray,
                          c: int, s_cache: int) -> jnp.ndarray:
    """Target positions for a C-token prefill chunk: ``pos_start + i`` for
    real tokens, ``s_cache`` (out of range — the scatter drops the write)
    for padding past ``chunk_len``."""
    i = jnp.arange(c)
    return jnp.where(i < chunk_len, jnp.asarray(pos_start, jnp.int32) + i,
                     s_cache)


@scoped("kv_write")
def cache_write_chunk(cache: Dict[str, jnp.ndarray], ks: jnp.ndarray,
                      vs: jnp.ndarray, rows: jnp.ndarray,
                      pos_start: jnp.ndarray, chunk_len: jnp.ndarray
                      ) -> Dict[str, jnp.ndarray]:
    """Write one prefill chunk's K/V for ALL layers into the ``rows`` lanes
    of a dense stacked cache.

    cache (L,B,KV,S,dh); ks/vs (L,Bc,KV,C,dh); rows (Bc,) batch lanes;
    positions [pos_start, pos_start+chunk_len) receive the chunk, padded
    chunk positions are routed out of range and dropped."""
    s_cache = cache["k"].shape[3]
    c = ks.shape[3]
    wpos = chunk_write_positions(pos_start, chunk_len, c, s_cache)
    r = jnp.asarray(rows, jnp.int32)[:, None]        # (Bc, 1)
    w = wpos[None, :]                                # (1, C)

    def upd(buf, val):
        # advanced indices at axes 1 and 3 broadcast to (Bc, C) and move to
        # the front: the scattered value is (Bc, C, L, KV, dh)
        return buf.at[:, r, :, w, :].set(
            val.transpose(1, 3, 0, 2, 4).astype(buf.dtype), mode="drop")

    out = dict(cache)
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ksc)
        out["v_scale"] = upd(cache["v_scale"], vsc)
    else:
        out["k"] = upd(cache["k"], ks.astype(cache["k"].dtype))
        out["v"] = upd(cache["v"], vs.astype(cache["v"].dtype))
    return out


@scoped("kv_write")
def cache_write_chunk_paged(cache: Dict[str, jnp.ndarray], ks: jnp.ndarray,
                            vs: jnp.ndarray, block_rows: jnp.ndarray,
                            pos_start: jnp.ndarray, chunk_len: jnp.ndarray
                            ) -> Dict[str, jnp.ndarray]:
    """Paged variant of :func:`cache_write_chunk`: virtual position
    ``pos_start + i`` of request ``b`` lands in page
    ``block_rows[b, (pos_start+i) // bs]`` at offset ``(pos_start+i) % bs``;
    padded chunk positions are routed to the NULL page (page 0 — scratch by
    construction, never allocated to a request)."""
    bs = cache["k"].shape[3]
    c = ks.shape[3]
    block_rows = jnp.asarray(block_rows, jnp.int32)  # (Bc, nb)
    bc, nb = block_rows.shape
    i = jnp.arange(c)
    vpos = jnp.asarray(pos_start, jnp.int32) + i
    blk = jnp.clip(vpos // bs, 0, nb - 1)
    off = vpos % bs
    real = (i < chunk_len)[None, :]                  # (1, C)
    page = jnp.where(real, block_rows[jnp.arange(bc)[:, None], blk[None, :]],
                     0)                              # (Bc, C); 0 = NULL page
    off_b = jnp.broadcast_to(off[None, :], (bc, c))

    def upd(buf, val):
        # advanced indices (page, offset) at axes 1 and 3 -> value (Bc, C,
        # L, KV, dh); duplicate NULL targets may race, NULL is scratch
        return buf.at[:, page, :, off_b, :].set(
            val.transpose(1, 3, 0, 2, 4).astype(buf.dtype), mode="drop")

    out = dict(cache)
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ksc)
        out["v_scale"] = upd(cache["v_scale"], vsc)
    else:
        out["k"] = upd(cache["k"], ks.astype(cache["k"].dtype))
        out["v"] = upd(cache["v"], vs.astype(cache["v"].dtype))
    return out


def gather_cache_rows(cache_l: Dict[str, jnp.ndarray], rows: jnp.ndarray
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-layer dense cache lanes for a prefill chunk: (Bc, KV, S, d) f32
    (int8 lanes dequantized)."""
    rows = jnp.asarray(rows, jnp.int32)
    k = cache_l["k"][rows].astype(jnp.float32)
    v = cache_l["v"][rows].astype(jnp.float32)
    if "k_scale" in cache_l:
        k = k * cache_l["k_scale"][rows].astype(jnp.float32)
        v = v * cache_l["v_scale"][rows].astype(jnp.float32)
    return k, v


def gather_page_rows(cache_l: Dict[str, jnp.ndarray],
                     block_tables: jnp.ndarray
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-layer paged K/V gathered through block tables into contiguous
    virtual caches: (Bc, KV, nb*bs, d) f32."""
    bt = jnp.asarray(block_tables, jnp.int32)
    bc, nb = bt.shape
    n_kv, bs = cache_l["k"].shape[1], cache_l["k"].shape[2]

    def gather(key, scale_key):
        g = cache_l[key][bt].astype(jnp.float32)     # (Bc, nb, KV, bs, d')
        if scale_key in cache_l:
            g = g * cache_l[scale_key][bt].astype(jnp.float32)
        return g.transpose(0, 2, 1, 3, 4).reshape(bc, n_kv, nb * bs, -1)

    return gather("k", "k_scale"), gather("v", "v_scale")


def _merge_kv_block(qc, o, l, m, k_blk, v_blk, mask):
    """Fold a block of keys into unnormalized online-softmax partials.

    qc (B,KV,G,C,d) f32; o (B,KV,G,C,d); l/m (B,KV,G,C); k_blk/v_blk
    (B,KV,T,d); mask (C,T) — the causal-within-chunk mask.  The chunked-
    prefill sibling of ``_merge_extra_kv`` (T keys per query row instead of
    one)."""
    d = qc.shape[-1]
    s = jnp.einsum("bkgcd,bktd->bkgct", qc, k_blk) \
        / jnp.sqrt(d).astype(jnp.float32)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    m_x = jnp.max(s, axis=-1)
    m_f = jnp.maximum(m, m_x)
    # NEG_INF is finite (-1e30): exp underflows to exactly 0, flushing the
    # garbage partials a fully-masked cache pass accumulates
    w_c = jnp.exp(m - m_f)
    p = jnp.exp(s - m_f[..., None])
    p = jnp.where(mask[None, None, None], p, 0.0)
    o = o * w_c[..., None] + jnp.einsum("bkgct,bktd->bkgcd", p, v_blk)
    l = l * w_c + jnp.sum(p, axis=-1)
    return o, l


def attn_prefill_chunk(q, k_new, v_new, cache_l: Dict[str, jnp.ndarray],
                       valid: jnp.ndarray, dtype, *, rows=None,
                       block_tables=None, impl: Optional[str] = None,
                       interpret: Optional[bool] = None) -> jnp.ndarray:
    """Chunked-prefill attention: a C-token query chunk of each request
    attends to its already-written cache positions plus causally within the
    chunk.

    q (Bc, C, H, d); k_new/v_new (Bc, C, KV, d) — the chunk's own K/V (not
    yet in the cache); cache_l — per-layer dense cache (Bfull, KV, S, dh)
    read through ``rows`` (Bc,), or paged pools (P, KV, bs, dh) read
    through ``block_tables`` (Bc, nb); valid (Bc, S_virtual) marks readable
    cache positions ([0, pos_start) — stale/unwritten entries masked).
    Padded chunk positions (beyond the real chunk length) produce garbage
    rows whose K/V writes are dropped downstream; causality keeps real
    queries from attending padded keys.  Returns (Bc, C, H, d).

    The paged path has two impls mirroring ``attn_decode_paged``:
    ``jnp`` gathers pages and runs one full softmax over [cache | chunk]
    (numerically closest to full prefill), ``pallas`` runs the q-block > 1
    ``paged_flash_prefill_chunk`` kernel over the pages and folds the
    within-chunk block into its unnormalized partials.
    """
    b, c, h, d = q.shape
    n_kv = k_new.shape[2]
    g = h // n_kv
    qc = q.reshape(b, c, n_kv, g, d).transpose(0, 2, 3, 1, 4) \
        .astype(jnp.float32)                         # (B, KV, G, C, d)
    kb = k_new.transpose(0, 2, 1, 3).astype(jnp.float32)   # (B, KV, C, d)
    vb = v_new.transpose(0, 2, 1, 3).astype(jnp.float32)
    causal = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    paged = block_tables is not None
    impl = resolve_paged_impl(impl) if paged else "jnp"
    if paged and impl == "pallas":
        from repro.kernels import ops as K           # deferred: no cycle
        interp = K.resolve_interpret(interpret)
        o, l, m = K.paged_flash_prefill_chunk(
            q.astype(jnp.float32), cache_l["k"], cache_l["v"], block_tables,
            valid, cache_l.get("k_scale"), cache_l.get("v_scale"),
            interpret=interp)
        o, l = _merge_kv_block(qc, o, l, m, kb, vb, causal)
        out = o / jnp.maximum(l, 1e-30)[..., None]
    else:
        if paged:
            k_c, v_c = gather_page_rows(cache_l, block_tables)
        else:
            k_c, v_c = gather_cache_rows(cache_l, rows)
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
        sc_c = jnp.einsum("bkgcd,bksd->bkgcs", qc, k_c) * scale
        sc_c = jnp.where(valid[:, None, None, None, :], sc_c, NEG_INF)
        sc_n = jnp.einsum("bkgcd,bktd->bkgct", qc, kb) * scale
        sc_n = jnp.where(causal[None, None, None], sc_n, NEG_INF)
        # ONE softmax over [cache | chunk] — the same full-row softmax
        # shape as attn_prefill_einsum, so chunked == full prefill up to
        # reduction order (exactly, for non-quantized caches)
        p = jax.nn.softmax(jnp.concatenate([sc_c, sc_n], axis=-1), axis=-1)
        s_len = k_c.shape[2]
        out = jnp.einsum("bkgcs,bksd->bkgcd", p[..., :s_len], v_c) \
            + jnp.einsum("bkgct,bktd->bkgcd", p[..., s_len:], vb)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, d).astype(dtype)


def packed_chunk_mask(seg: jnp.ndarray, valid_tok: jnp.ndarray,
                      ancestors: Optional[jnp.ndarray] = None
                      ) -> jnp.ndarray:
    """Block-diagonal mask for a PACKED chunk's within-chunk keys.

    Without ``ancestors`` (chunked prefill, linear verify): token i may
    attend chunk token j iff both belong to the same segment (request),
    j precedes i in the chunk (segments are laid out contiguously in
    request order, so this is exactly per-request causality) and j is a
    real token (padding never serves as a key).

    With ``ancestors`` (C,) — per-token parent pointers into the chunk,
    root tokens pointing at THEMSELVES — each segment's tokens form a
    candidate TREE instead of a chain (tree speculative decode): token i
    may attend chunk token j iff j lies on i's root path (i itself, its
    parent, its parent's parent, ...).  The closure is computed by
    following parent pointers to their fixpoint, so the width-1 tree
    (ancestors[i] = i - 1 within each segment) reproduces the causal
    chain mask bit for bit.  seg (C,), valid_tok (C,) -> (C, C)."""
    seg = jnp.asarray(seg, jnp.int32)
    c = seg.shape[0]
    i = jnp.arange(c)
    base = ((seg[:, None] == seg[None, :])
            & jnp.asarray(valid_tok, bool)[None, :])
    if ancestors is None:
        return base & (i[None, :] <= i[:, None])
    anc = jnp.asarray(ancestors, jnp.int32)

    def walk(_, carry):
        cur, reach = carry
        cur = anc[cur]
        return cur, reach | (cur[:, None] == i[None, :])

    _, reach = jax.lax.fori_loop(
        0, c, walk, (i, i[:, None] == i[None, :]))
    return base & reach


def _merge_packed_block(qg, o, l, m, k_new, v_new, mask):
    """Fold a packed chunk's own keys into per-token unnormalized partials.

    qg (C,KV,G,d) f32; o (C,KV,G,d); l/m (C,KV,G); k_new/v_new (C,KV,d) —
    the chunk's freshly-projected K/V (not yet in any cache); mask (C,C)
    the block-diagonal chunk mask.  The packed sibling of
    ``_merge_kv_block``: every token is its own query row with its own
    key-visibility row.  Tokens whose cache pass was fully masked (a
    prompt head with nothing written yet) carry m = NEG_INF partials which
    ``exp(m - m_f)`` flushes to exact zeros here."""
    d = qg.shape[-1]
    kb = k_new.transpose(1, 0, 2).astype(jnp.float32)      # (KV, C, d)
    vb = v_new.transpose(1, 0, 2).astype(jnp.float32)
    s = jnp.einsum("ckgd,ktd->ckgt", qg, kb) \
        / jnp.sqrt(d).astype(jnp.float32)
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    m_x = jnp.max(s, axis=-1)
    m_f = jnp.maximum(m, m_x)
    w_c = jnp.exp(m - m_f)
    p = jnp.exp(s - m_f[..., None])
    p = jnp.where(mask[:, None, None, :], p, 0.0)
    o = o * w_c[..., None] + jnp.einsum("ckgt,ktd->ckgd", p, vb)
    l = l * w_c + jnp.sum(p, axis=-1)
    return o, l


def attn_prefill_packed(q, k_new, v_new, cache_l: Dict[str, jnp.ndarray],
                        seg: jnp.ndarray, seg_starts: jnp.ndarray,
                        chunk_mask: jnp.ndarray, dtype, *, rows=None,
                        seg_tables=None, impl: Optional[str] = None,
                        interpret: Optional[bool] = None) -> jnp.ndarray:
    """Packed multi-request chunk attention: C chunk tokens belonging to up
    to R requests ("segments") each attend THEIR OWN request's
    already-written cache positions plus, under the block-diagonal
    ``chunk_mask``, the chunk tokens of their own segment that precede
    them.  Tokens of different requests never attend each other — the
    cross-request structure is block-diagonal end to end.

    q (C, H, d); k_new/v_new (C, KV, d) — the chunk's own K/V (not yet in
    the cache); seg (C,) segment id per token; seg_starts (R,) each
    segment's prefill progress (its readable-cache prefix); cache_l —
    per-layer dense cache (Bfull, KV, S, dh) read through ``rows`` (C,)
    per-token batch lanes, or paged pools (P, KV, bs, dh) read through
    ``seg_tables`` (R, nb) per-segment block-table rows.  Returns
    (C, H, d).

    The paged path mirrors ``attn_prefill_chunk``: ``jnp`` gathers pages
    and runs ONE softmax over [cache | chunk] per token (numerically the
    full-prefill shape), ``pallas`` runs ``paged_flash_packed_chunk``
    (each page DMA'd once per segment for the whole chunk) and folds the
    within-chunk block into its unnormalized partials."""
    c, h, d = q.shape
    n_kv = k_new.shape[1]
    g = h // n_kv
    qg = q.reshape(c, n_kv, g, d).astype(jnp.float32)
    kb = k_new.transpose(1, 0, 2).astype(jnp.float32)      # (KV, C, d)
    vb = v_new.transpose(1, 0, 2).astype(jnp.float32)
    seg = jnp.asarray(seg, jnp.int32)
    seg_starts = jnp.asarray(seg_starts, jnp.int32)
    paged = seg_tables is not None
    impl = resolve_paged_impl(impl) if paged else "jnp"
    if paged and impl == "pallas":
        from repro.kernels import ops as K           # deferred: no cycle
        interp = K.resolve_interpret(interpret)
        bs = cache_l["k"].shape[2]
        n_virtual = seg_tables.shape[1] * bs
        seg_valid = jnp.arange(n_virtual)[None, :] < seg_starts[:, None]
        o, l, m = K.paged_flash_packed_chunk(
            q.astype(jnp.float32), cache_l["k"], cache_l["v"], seg,
            seg_tables, seg_valid, cache_l.get("k_scale"),
            cache_l.get("v_scale"), interpret=interp)
        o, l = _merge_packed_block(qg, o, l, m, k_new, v_new, chunk_mask)
        out = o / jnp.maximum(l, 1e-30)[..., None]
    else:
        if paged:
            k_c, v_c = gather_page_rows(cache_l, seg_tables[seg])
        else:
            k_c, v_c = gather_cache_rows(cache_l, rows)
        valid = jnp.arange(k_c.shape[2])[None, :] < seg_starts[seg][:, None]
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
        sc_c = jnp.einsum("ckgd,cksd->ckgs", qg, k_c) * scale
        sc_c = jnp.where(valid[:, None, None, :], sc_c, NEG_INF)
        sc_n = jnp.einsum("ckgd,ktd->ckgt", qg, kb) * scale
        sc_n = jnp.where(chunk_mask[:, None, None, :], sc_n, NEG_INF)
        # ONE softmax over [cache | chunk] per token — the same full-row
        # softmax shape as attn_prefill_chunk, so packed == unpacked ==
        # full prefill up to reduction order
        p = jax.nn.softmax(jnp.concatenate([sc_c, sc_n], axis=-1), axis=-1)
        s_len = k_c.shape[2]
        out = jnp.einsum("ckgs,cksd->ckgd", p[..., :s_len], v_c) \
            + jnp.einsum("ckgt,ktd->ckgd", p[..., s_len:], vb)
    return out.reshape(c, h, d).astype(dtype)


@scoped("kv_write")
def cache_write_packed(cache: Dict[str, jnp.ndarray], ks: jnp.ndarray,
                       vs: jnp.ndarray, rows: jnp.ndarray,
                       wpos: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Write a PACKED chunk's K/V for ALL layers into a dense stacked
    cache: every chunk token targets its own (batch lane, position).

    cache (L,B,KV,S,dh); ks/vs (L,KV,C,dh); rows (C,) per-token batch
    lanes; wpos (C,) per-token target positions — padding tokens are
    routed out of range (>= S) and dropped by the scatter."""
    rows = jnp.asarray(rows, jnp.int32)
    wpos = jnp.asarray(wpos, jnp.int32)

    def upd(buf, val):
        # advanced indices (lane, position) at axes 1 and 3 move to the
        # front: the scattered value is (C, L, KV, dh)
        return buf.at[:, rows, :, wpos, :].set(
            val.transpose(2, 0, 1, 3).astype(buf.dtype), mode="drop")

    out = dict(cache)
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ksc)
        out["v_scale"] = upd(cache["v_scale"], vsc)
    else:
        out["k"] = upd(cache["k"], ks.astype(cache["k"].dtype))
        out["v"] = upd(cache["v"], vs.astype(cache["v"].dtype))
    return out


@scoped("kv_write")
def cache_write_packed_paged(cache: Dict[str, jnp.ndarray], ks: jnp.ndarray,
                             vs: jnp.ndarray, tok_tables: jnp.ndarray,
                             wpos: jnp.ndarray, valid_tok: jnp.ndarray
                             ) -> Dict[str, jnp.ndarray]:
    """Paged variant of :func:`cache_write_packed`: chunk token t lands in
    page ``tok_tables[t, wpos_t // bs]`` at offset ``wpos_t % bs``;
    padding tokens are routed to the NULL page (page 0 — scratch by
    construction, never allocated to a request).

    cache k/v (L,P,KV,bs,dh); ks/vs (L,KV,C,dh); tok_tables (C, nb)
    per-token block-table rows; wpos (C,) virtual positions; valid_tok
    (C,) marks real tokens."""
    bs = cache["k"].shape[3]
    c = ks.shape[2]
    tok_tables = jnp.asarray(tok_tables, jnp.int32)
    nb = tok_tables.shape[1]
    wpos = jnp.asarray(wpos, jnp.int32)
    blk = jnp.clip(wpos // bs, 0, nb - 1)
    off = wpos % bs
    page = jnp.where(jnp.asarray(valid_tok, bool),
                     tok_tables[jnp.arange(c), blk], 0)   # 0 = NULL page

    def upd(buf, val):
        # advanced indices (page, offset) at axes 1 and 3 -> value (C, L,
        # KV, dh); duplicate NULL targets may race, NULL is scratch
        return buf.at[:, page, :, off, :].set(
            val.transpose(2, 0, 1, 3).astype(buf.dtype), mode="drop")

    out = dict(cache)
    if "k_scale" in cache:
        kq, ksc = quantize_kv(ks)
        vq, vsc = quantize_kv(vs)
        out["k"] = upd(cache["k"], kq)
        out["v"] = upd(cache["v"], vq)
        out["k_scale"] = upd(cache["k_scale"], ksc)
        out["v_scale"] = upd(cache["v_scale"], vsc)
    else:
        out["k"] = upd(cache["k"], ks.astype(cache["k"].dtype))
        out["v"] = upd(cache["v"], vs.astype(cache["v"].dtype))
    return out


def prefill_to_pages(pages: Dict[str, jnp.ndarray],
                     prefill_cache: Dict[str, jnp.ndarray],
                     block_row: jnp.ndarray, n_blocks: int
                     ) -> Dict[str, jnp.ndarray]:
    """Scatter ONE request's prefilled dense cache into its pages.

    ``prefill_cache`` leaves are (L, 1, KV, S_pad, dh) with S_pad a multiple
    of the page size; the first ``n_blocks`` entries of ``block_row`` (nb,)
    receive the prompt K/V, page by page.  int8 prefills carry their scales
    through unchanged (same quantization as the dense path)."""
    bs = pages["k"].shape[3]
    out = dict(pages)
    for key in pages:
        src = prefill_cache[key]                  # (L, 1, KV, S_pad, d')
        L, _, KV, s_pad, dl = src.shape
        src = src.reshape(L, KV, s_pad // bs, bs, dl)[:, :, :n_blocks]
        src = src.transpose(0, 2, 1, 3, 4)        # (L, nb, KV, bs, d')
        out[key] = out[key].at[:, block_row[:n_blocks]].set(
            src.astype(out[key].dtype))
    return out


def copy_pages(pages: Dict[str, jnp.ndarray], src: jnp.ndarray,
               dst: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Copy physical pages ``src`` -> ``dst`` (1-D index arrays) across all
    layers — the copy-on-write step when a new sharer takes a private copy
    of a donor's partially-filled tail page."""
    return {key: buf.at[:, dst].set(buf[:, src]) for key, buf in pages.items()}


@scoped("decode_attention")
def paged_valid_mask(pos: jnp.ndarray, batch: int, n_virtual: int
                     ) -> jnp.ndarray:
    """Readable virtual positions for a paged decode step: [0, pos) per row.

    Masked-off entries also cover NULL/stale table entries: a position is
    only ever readable after this request wrote it (same stale-KV argument
    as the dense per-slot cache)."""
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (batch,))
    return jnp.arange(n_virtual)[None, :] < pos[:, None]


@scoped("decode_attention")
def attn_decode_paged(q, cache_l: Dict[str, jnp.ndarray],
                      block_tables: jnp.ndarray, valid: jnp.ndarray,
                      dtype, extra_kv=None, *,
                      impl: Optional[str] = None,
                      interpret: Optional[bool] = None) -> jnp.ndarray:
    """Decode attention through a block table.  q (B,H,d); cache_l per-layer
    pages {"k","v": (P,KV,bs,d)} READ-ONLY; valid (B, nb*bs); extra_kv the
    current token's (k, v) each (B,KV,d).

    ``impl="jnp"`` gathers pages with a jnp take and reuses the dense
    online-softmax (bit-identical to the dense path when nb*bs equals the
    dense cache length); ``impl="pallas"`` runs the paged flash-decode
    kernel (the TPU hot path — pages are DMA'd through the scalar-prefetched
    block table, never materialized contiguously).  Default comes from
    ``REPRO_PAGED_ATTN`` (jnp off-TPU, pallas on TPU).
    """
    b, h, d = q.shape
    impl = resolve_paged_impl(impl)
    qg = q.reshape(b, cache_l["k"].shape[1], h // cache_l["k"].shape[1], d
                   ).astype(jnp.float32)
    if impl == "pallas":
        from repro.kernels import ops as K           # deferred: no cycle
        interp = K.resolve_interpret(interpret)
        o, l, m = K.paged_flash_decode(
            q.astype(jnp.float32), cache_l["k"], cache_l["v"], block_tables,
            valid, cache_l.get("k_scale"), cache_l.get("v_scale"),
            interpret=interp, return_partials=True)
    elif impl == "jnp":
        n_kv = cache_l["k"].shape[1]
        bs = cache_l["k"].shape[2]
        nb = block_tables.shape[1]
        bt = jnp.asarray(block_tables, jnp.int32)

        def gather(key, scale_key):
            g = cache_l[key][bt]                     # (B, nb, KV, bs, d')
            if scale_key in cache_l:
                g = (g.astype(jnp.float32)
                     * cache_l[scale_key][bt].astype(jnp.float32)
                     ).astype(jnp.bfloat16)          # bf16 dequant (§Perf C3)
            return g.transpose(0, 2, 1, 3, 4).reshape(b, n_kv, nb * bs, -1)

        k = gather("k", "k_scale")
        v = gather("v", "v_scale")
        o, l, m = _decode_partial(qg, k, v, valid)
    else:
        raise ValueError(f"unknown paged attention impl {impl!r} "
                         "(expected 'jnp' or 'pallas')")
    o, l = _merge_extra_kv(qg, o, l, m, extra_kv, d)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, d).astype(dtype)


def default_paged_impl() -> str:
    """jnp gather off-TPU, the Pallas paged kernel on TPU;
    ``REPRO_PAGED_ATTN=jnp|pallas`` overrides (parity tests force both)."""
    import os
    forced = os.environ.get("REPRO_PAGED_ATTN")
    if forced:
        return forced
    import jax as _jax
    return "pallas" if _jax.default_backend() == "tpu" else "jnp"


def resolve_paged_impl(impl: Optional[str] = None) -> str:
    """The paged attention impl a call site uses: the explicit value, else
    ``default_paged_impl()``.  Raises on a TPU backend if that comes out
    "jnp" — the chip always reads pages through the Pallas kernels, never
    the gather that materializes every row's cache."""
    impl = impl or default_paged_impl()
    import jax as _jax
    if impl == "jnp" and _jax.default_backend() == "tpu":
        raise RuntimeError(
            "the jnp page gather was requested on a TPU backend (explicit "
            "impl='jnp' or REPRO_PAGED_ATTN=jnp); unset it — the chip runs "
            "the paged Pallas kernels")
    return impl


# ---------------------------------------------------------------------------
# Prefill attention

def _gqa_split(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B, S, H, d) -> (B, S, KV, G, d)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def attn_prefill_einsum(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0) -> jnp.ndarray:
    """Reference O(S^2)-memory attention. q (B,Sq,H,d); k,v (B,Sk,KV,d)."""
    b, sq, h, d = q.shape
    n_kv = k.shape[2]
    qg = _gqa_split(q, n_kv)
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / jnp.sqrt(d).astype(jnp.float32)
    qpos = jnp.arange(sq) + q_offset
    kpos = jnp.arange(k.shape[1])
    mask = jnp.ones((sq, k.shape[1]), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def attn_prefill_blockwise(q, k, v, *, causal: bool = True,
                           window: Optional[int] = None,
                           q_block: int = 512, kv_block: int = 512,
                           differentiable: bool = False) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure JAX (O(S*block) memory).

    Sequential lax.scan over Q blocks; inner fori_loop over KV blocks with a
    dynamic upper bound so causally-dead blocks are skipped (same FLOPs as a
    TPU flash kernel).  This is the scalable path used in the dry-run; the
    Pallas kernel in repro/kernels/flash_attention.py is the TPU hot path.
    """
    b, s, h, d = q.shape
    n_kv = k.shape[2]
    g = h // n_kv
    assert s % q_block == 0 and s % kv_block == 0, (s, q_block, kv_block)
    nq, nk = s // q_block, s // kv_block
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    kT = k.astype(jnp.float32).transpose(0, 2, 3, 1)   # (B,KV,d,S)
    vT = v.astype(jnp.float32).transpose(0, 2, 1, 3)   # (B,KV,S,d)

    def q_step(_, qi):
        qb = jax.lax.dynamic_slice_in_dim(q, qi * q_block, q_block, axis=1)
        qb = _gqa_split(qb.astype(jnp.float32), n_kv) * scale  # (B,qb,KV,G,d)
        qb = qb.transpose(0, 2, 3, 1, 4)                       # (B,KV,G,qb,d)
        qpos = qi * q_block + jnp.arange(q_block)

        def kv_step(ki, carry):
            m, l, acc = carry
            kb = jax.lax.dynamic_slice_in_dim(kT, ki * kv_block, kv_block, axis=3)
            vb = jax.lax.dynamic_slice_in_dim(vT, ki * kv_block, kv_block, axis=2)
            sc = jnp.einsum("bkgqd,bkds->bkgqs", qb, kb)
            kpos = ki * kv_block + jnp.arange(kv_block)
            mask = jnp.ones((q_block, kv_block), bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            sc = jnp.where(mask[None, None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum("bkgqs,bksd->bkgqd", p, vb)
            return m_new, l_new, acc_new

        m0 = jnp.full((b, n_kv, g, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, n_kv, g, q_block), jnp.float32)
        a0 = jnp.zeros((b, n_kv, g, q_block, d), jnp.float32)
        if differentiable:
            # static-trip scan over ALL kv blocks (masked): reverse-mode safe.
            # Each block is rematerialized in the backward pass (otherwise the
            # scan stores every (bq x bk) probability block — the memory the
            # flash formulation exists to avoid).
            def kv_scan(carry, ki):
                return kv_step(ki, carry), None
            kv_scan = jax.checkpoint(kv_scan, prevent_cse=False)
            (m, l, acc), _ = jax.lax.scan(kv_scan, (m0, l0, a0), jnp.arange(nk))
        else:
            # dynamic bounds skip causally-dead blocks (flash-kernel FLOPs)
            if causal:
                hi = (qi * q_block + q_block + kv_block - 1) // kv_block
                hi = jnp.minimum(hi, nk)
            else:
                hi = nk
            lo = 0
            if window is not None:
                lo = jnp.maximum(qi * q_block - (window - 1), 0) // kv_block
            m, l, acc = jax.lax.fori_loop(lo, hi, kv_step, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, q_block, h, d)
        return None, out.astype(q.dtype)

    _, blocks = jax.lax.scan(q_step, None, jnp.arange(nq))
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d)


def attn_prefill(q, k, v, *, causal: bool = True, window: Optional[int] = None):
    impl = parallel.attn_impl()
    if impl == "blockwise":
        qb = min(512, q.shape[1])
        kb = min(512, k.shape[1])
        if q.shape[1] % qb == 0 and k.shape[1] % kb == 0:
            # training (remat on) needs the reverse-mode-safe static scan;
            # pure prefill keeps the dynamic-bound block skipping.
            return attn_prefill_blockwise(
                q, k, v, causal=causal, window=window, q_block=qb, kv_block=kb,
                differentiable=parallel.remat_enabled())
    return attn_prefill_einsum(q, k, v, causal=causal, window=window)


# ---------------------------------------------------------------------------
# Decode attention (single query against a READ-ONLY cache + current token)
#
# The cache never flows through the layer scan as an output: each layer
# attends to the (stale-slot-masked) cache plus the freshly-projected
# (k, v) of the current token passed as ``extra_kv``; the single in-place
# cache write for all layers happens outside the scan (donated buffer).
# This removes the full-cache copy per decode step (§Perf iteration C2).

def _decode_partial(qg, k, v, valid):
    """Unnormalized online-softmax pieces over the cache.
    Returns (o_un (B,KV,G,d), l (B,KV,G), m (B,KV,G)).

    k/v may be bf16 (int8 caches are dequantized to bf16 to halve the
    transient copy — §Perf C3; the Pallas flash_decode kernel dequantizes
    per VMEM block on TPU so no HBM-sized temp exists at all there);
    contractions accumulate in f32."""
    d = qg.shape[-1]
    sc = jnp.einsum("bkgd,bksd->bkgs", qg.astype(k.dtype), k,
                    preferred_element_type=jnp.float32)
    sc = sc / jnp.sqrt(d).astype(jnp.float32)
    sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
    m = jnp.max(sc, axis=-1)
    p = jnp.exp(sc - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)   # guard exp(-inf - -inf)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o, l, m


def _decode_core(qg, k, v, valid) -> jnp.ndarray:
    o, l, m = _decode_partial(qg, k, v, valid)
    return o / jnp.maximum(l, 1e-30)[..., None]


def _merge_extra_kv(qg, o, l, m, extra_kv, d):
    """Fold the current token's (k, v) column into unnormalized online-
    softmax partials (o, l, m).  Shared by the dense and paged paths."""
    if extra_kv is None:
        return o, l
    k_x, v_x = extra_kv
    k_x = k_x.astype(jnp.float32)
    v_x = v_x.astype(jnp.float32)
    s_x = jnp.einsum("bkgd,bkd->bkg", qg, k_x) / jnp.sqrt(d).astype(jnp.float32)
    m_f = jnp.maximum(m, s_x)
    w_c = jnp.where(jnp.isfinite(m), jnp.exp(m - m_f), 0.0)
    w_x = jnp.exp(s_x - m_f)
    o = o * w_c[..., None] + w_x[..., None] * v_x[:, :, None, :]
    l = l * w_c + w_x
    return o, l


@scoped("decode_attention")
def attn_decode(q, cache_l, valid, dtype, extra_kv=None) -> jnp.ndarray:
    """q (B,H,d); cache_l per-layer dict (B,KV,S,d) READ-ONLY; valid (B,S);
    extra_kv: optional (k_new, v_new) each (B,KV,d) — the current token."""
    b, h, d = q.shape
    k, v = cache_kv(cache_l, jnp.bfloat16)   # bf16 dequant (§Perf C3)
    n_kv = k.shape[1]
    qg = q.reshape(b, n_kv, h // n_kv, d).astype(jnp.float32)
    ctx = parallel.current_ctx()
    seq_shardable = (ctx is not None and
                     k.shape[2] % ctx.mesh.shape[ctx.model_axis] == 0)
    if ctx is not None and ctx.flash_decode and seq_shardable:
        o, l, m = _flash_decode_sharded(ctx, qg, k, v, valid)
    else:
        o, l, m = _decode_partial(qg, k, v, valid)
    o, l = _merge_extra_kv(qg, o, l, m, extra_kv, d)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, d).astype(dtype)


def _flash_decode_sharded(ctx, qg, k, v, valid):
    """Sequence-sharded flash-decode: KV cache sharded over the model axis on
    the sequence dim; each shard computes a partial online softmax which is
    combined with pmax/psum (one collective round instead of gathering the
    cache).  Returns unnormalized (o, l, m) so the caller can merge the
    current token's column."""
    mesh = ctx.mesh
    ax = ctx.model_axis
    dspec = ctx.rules.get("batch")

    def shard_fn(qg, k, v, valid):
        o_loc, l_loc, m_loc = _decode_partial(qg, k, v, valid)
        m_glb = jax.lax.pmax(m_loc, ax)
        scale = jnp.where(jnp.isfinite(m_loc), jnp.exp(m_loc - m_glb), 0.0)
        l_glb = jax.lax.psum(l_loc * scale, ax)
        o_glb = jax.lax.psum(o_loc * scale[..., None], ax)
        return o_glb, l_glb, m_glb

    from jax.sharding import PartitionSpec as P
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(dspec), P(dspec, None, ax), P(dspec, None, ax), P(dspec, ax)),
        out_specs=(P(dspec), P(dspec), P(dspec)),
        check_vma=False,
    )(qg, k, v, valid)
