"""Flash-decode — single-query attention over a long KV cache (Pallas TPU).

One new token attends to a cache of S positions: grid = (B, KV, S/BS) with
the sequence chunk innermost, online-softmax running stats in VMEM.  The
whole GQA group (G = H/KV query heads) is processed per program so the KV
block is read once per group (bandwidth-bound op — the roofline term this
kernel optimizes).  A validity mask supports ring-buffer SWA caches and
partially-filled caches.

q (B, H, d); k, v (B, KV, S, d); valid (B, S) -> out (B, H, d)

``paged_flash_decode`` is the paged-KV variant: K/V live in a pool of
fixed-size token blocks (pages) shared by all requests, and each batch row
reads *through its block table*, a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``).  One program per row covers every kv
head and walks compute blocks of many pages, DMA'd by hand from the pools
in HBM and double-buffered; it stops after the row's last valid position.
int8-KV pages carry per-(position, head) scales, applied in VMEM.

``paged_flash_prefill_chunk`` is the q-block > 1 paged kernel: one
(row, kv head, page) per grid step, whose index map dereferences
``table[b, block]`` to pick the page the next DMA fetches; the C queries
of a prefill chunk share each page DMA (Sarathi-style chunked prefill —
the serving engine's unified token-budget step), emitting unnormalized
partials the caller merges with the causal within-chunk block.

``paged_flash_packed_chunk`` is the PACKED variant: one fused chunk carries
tokens of up to R different requests (the tail of one prompt piggybacked
with the head of the next).  Cross-request isolation is block-diagonal by
construction — each request's tokens read the pages through their OWN
block-table row with their own validity prefix, and the within-chunk keys
are gated by the caller's block-diagonal chunk mask
(``attention.packed_chunk_mask``), so tokens of different requests never
attend each other anywhere in the fused chunk.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_mask(valid, bs: int):
    """(B, S) validity -> (B, S/bs, 1, bs) int32, so each program's mask
    block is (1, bs): the full extent of the array's last two dims, which
    Mosaic accepts for any ``bs`` (a (1, bs) block of a 2-D (B, S) array
    is not a multiple of the (8, 128) tile unless ``bs`` is)."""
    b, s = valid.shape
    return valid.astype(jnp.int32).reshape(b, s // bs, 1, bs)


def _kernel(q_ref, k_ref, v_ref, valid_ref, o_ref, m_s, l_s, acc_s, *,
            bs: int, n_s: int, g: int, scale: float):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0, :, :] * scale                     # (G, d)
    k = k_ref[0, 0, :, :]                             # (bs, d)
    v = v_ref[0, 0, :, :]
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)   # (G, bs)
    ok = valid_ref[0, 0, :, :] > 0                    # (1, bs)
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(si == n_s - 1)
    def _fin():
        o_ref[0, 0, :, :] = (acc_s[...] /
                          jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def flash_decode(q, k, v, valid, *, bs: int = 512, interpret: bool = True):
    b, h, d = q.shape
    _, n_kv, s_len, _ = k.shape
    assert h % n_kv == 0
    g = h // n_kv
    bs = min(bs, s_len)
    assert s_len % bs == 0, (s_len, bs)
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, n_kv, g, d)
    kernel = functools.partial(_kernel, bs=bs, n_s=s_len // bs, g=g, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b, n_kv, s_len // bs),
        in_specs=[
            pl.BlockSpec((1, 1, g, d), lambda b_, kv, si: (b_, kv, 0, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b_, kv, si: (b_, kv, si, 0)),
            pl.BlockSpec((1, 1, bs, d), lambda b_, kv, si: (b_, kv, si, 0)),
            pl.BlockSpec((1, 1, 1, bs), lambda b_, kv, si: (b_, si, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, d), lambda b_, kv, si: (b_, kv, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, g, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
        interpret=interpret,
    )(qg, k, v, _block_mask(valid, bs))
    return out.reshape(b, h, d)


# ---------------------------------------------------------------------------
# Paged chunk attention: one page per grid step, through the block table


def _paged_kernel(tables_ref, q_ref, k_ref, v_ref, valid_ref, *rest,
                  n_b: int, quantized: bool, scale: float):
    """One (batch row, kv head, table entry) program.  The page this program
    sees was selected by the index map via ``tables_ref[b, bi]`` — the
    kernel body itself is table-oblivious online softmax.  Emits the
    UNNORMALIZED (acc, l, m) triple so the caller can merge the current
    token's column (``extra_kv``) before normalizing, exactly like the
    dense ``_decode_partial`` path.

    The query block is (R, d) with R = G*C rows of a C-token chunk: the
    body is row-count oblivious."""
    if quantized:
        ks_ref, vs_ref, o_ref, l_ref, m_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, l_ref, m_ref, m_s, l_s, acc_s = rest
    bi = pl.program_id(2)

    @pl.when(bi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0, :, :] * scale                     # (G, d)
    k = k_ref[0, 0, :, :]                             # (bs, d)
    v = v_ref[0, 0, :, :]
    if quantized:
        # per-(position, head) absmax scales: dequantize this page in VMEM
        k = k.astype(jnp.float32) * ks_ref[0, 0, :, :]
        v = v.astype(jnp.float32) * vs_ref[0, 0, :, :]
    s = jnp.dot(q, k.astype(q.dtype).T, preferred_element_type=jnp.float32)
    ok = valid_ref[0, 0, :, :] > 0                    # (1, bs)
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_s[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_s[...] = acc_s[...] * corr + jnp.dot(
        p.astype(jnp.float32), v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(bi == n_b - 1)
    def _fin():
        o_ref[0, 0, :, :] = acc_s[...]
        l_ref[0, 0, :, :] = l_s[...]
        m_ref[0, 0, :, :] = m_s[...]


def _paged_attend(qg, k_pages, v_pages, block_tables, valid,
                  k_scale_pages, v_scale_pages, *, interpret: bool):
    """Launcher of the chunk kernels: online-softmax attention of an
    (R, d) query block per (batch row, kv head) against that row's pages,
    gathered through the scalar-prefetched block table.  R = G*C for a
    C-token chunk.  qg (B, KV, R, d) -> unnormalized (o (B,KV,R,d),
    l (B,KV,R), m (B,KV,R))."""
    b, n_kv, r, d = qg.shape
    _, _, bs, _ = k_pages.shape
    nb = block_tables.shape[1]
    assert valid.shape == (b, nb * bs), (valid.shape, b, nb, bs)
    quantized = k_scale_pages is not None
    assert quantized == (v_scale_pages is not None)
    scale = 1.0 / (d ** 0.5)

    # index maps receive the scalar-prefetch block table last: the page a
    # program DMAs is table[b, bi] — this indirection IS paged attention
    page_spec = pl.BlockSpec(
        (1, 1, bs, d), lambda b_, kv, bi, tbl: (tbl[b_, bi], kv, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1, r, d), lambda b_, kv, bi, tbl: (b_, kv, 0, 0)),
        page_spec,
        page_spec,
        pl.BlockSpec((1, 1, 1, bs), lambda b_, kv, bi, tbl: (b_, bi, 0, 0)),
    ]
    operands = [qg, k_pages, v_pages, _block_mask(valid, bs)]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, 1, bs, 1), lambda b_, kv, bi, tbl: (tbl[b_, bi], kv, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale_pages, v_scale_pages]

    kernel = functools.partial(_paged_kernel, n_b=nb, quantized=quantized,
                               scale=scale)
    stat_spec = pl.BlockSpec((1, 1, r, 1),
                             lambda b_, kv, bi, tbl: (b_, kv, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n_kv, nb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, 1, r, d),
                         lambda b_, kv, bi, tbl: (b_, kv, 0, 0)),
            stat_spec,
            stat_spec,
        ],
        scratch_shapes=[
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, 1), jnp.float32),
            pltpu.VMEM((r, d), jnp.float32),
        ],
    )
    o_un, l, m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, r, d), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, r, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, r, 1), jnp.float32),
        ],
        interpret=interpret,
    )(block_tables.astype(jnp.int32), *operands)
    return o_un, l[..., 0], m[..., 0]


# ---------------------------------------------------------------------------
# Paged decode: every kv head and many pages per step, live blocks only

DECODE_BLOCK_TOKENS = 1024      # cache positions per compute block (PERF.md)
DECODE_BUFFER_BYTES = 4 << 20   # VMEM for the 2 x 2 K/V page buffers
LANES = 128                     # TPU lane width: a page DMA's minor dim


def _lane_parts(d: int, bs: int) -> int:
    """Cache positions per 128-lane row of a page: ``128 // d`` where ``d``
    divides 128 and the page's ``bs / r`` rows stay whole 8-row tiles
    (Mosaic refuses a 16- or 8-bit DMA slice of fewer), else 1."""
    r = LANES // d if d < LANES and LANES % d == 0 else 1
    return r if bs % (8 * r) == 0 else 1


def _page_lanes(d: int, bs: int) -> int:
    """Minor dim of a page row in the kernel: r * d, or d padded to whole
    128-lane rows."""
    r = _lane_parts(d, bs)
    return r * d if r > 1 else -(-d // LANES) * LANES


def decode_pages_per_block(bs: int, nb: int, n_kv: int, d: int,
                           itemsize: int) -> int:
    """Pages per compute block of ``paged_flash_decode``:
    ``DECODE_BLOCK_TOKENS`` worth of ``bs``-token pages, no more than the
    ``nb`` entries of a block-table row nor than fit four page buffers
    (K and V, double-buffered) in ``DECODE_BUFFER_BYTES``; at least one."""
    page = n_kv * bs // _lane_parts(d, bs) * _page_lanes(d, bs) * itemsize
    return max(1, min(DECODE_BLOCK_TOKENS // bs, nb,
                      DECODE_BUFFER_BYTES // (4 * page)))


def decode_block_counts(pos, bs: int, nb: int, n_kv: int, d: int,
                        itemsize: int) -> Tuple[int, int]:
    """(live, launched) compute blocks of one ``paged_flash_decode`` call
    whose rows read [0, pos): host arithmetic, no device read.  A row at
    ``pos`` 0 (parked, mid-prefill) decodes nothing and counts in neither;
    every other row has ``ceil(nb / N)`` blocks, of which the first
    ``ceil(pos / (N * bs))`` run."""
    n = decode_pages_per_block(bs, nb, n_kv, d, itemsize)
    t = n * bs
    rows = np.asarray(pos, np.int64)
    rows = rows[rows > 0]
    return int(((rows + t - 1) // t).sum()), int(rows.size * -(-nb // n))


def _paged_decode_kernel(tables_ref, live_ref, q_ref, valid_ref, *rest,
                         n_pages: int, n_kv: int, parts: int, d: int,
                         quantized: bool, scale: float):
    """One batch row: every kv head, its live compute blocks only.

    Block i is ``n_pages`` pages.  A page is one (KV, bs / r, 128) slab
    holding r = ``parts`` cache positions per lane row; the slabs are
    DMA'd from the HBM pools through ``tables_ref[b, i * n_pages + j]``
    into slot ``i % 2`` of the VMEM buffers, head by head in rows
    [j bs / r, (j + 1) bs / r), while block i - 1 computes.  The first
    ``live_ref[b]`` blocks run; the rest issue no copy and no compute.
    ``valid`` masks every position of a live block.

    Query row c R + g (R = 8-row-aligned G) holds query g in lanes
    [c d, (c + 1) d) and zeros elsewhere, so it scores the positions in
    lane part c and keeps softmax stats of its own.  After the last block
    the r parts of each query merge here into UNNORMALIZED (o, l, m); a
    row with no live block keeps m = NEG_INF, l = 0, o = 0.  int8 pages
    are upcast in VMEM and each position's scale multiplies its score and
    its value weight."""
    if quantized:
        (ks_ref, vs_ref, k_hbm, v_hbm, o_ref, l_ref, m_ref,
         k_buf, v_buf, sem, acc_s, l_s, m_s) = rest
    else:
        (k_hbm, v_hbm, o_ref, l_ref, m_ref,
         k_buf, v_buf, sem, acc_s, l_s, m_s) = rest
    b = pl.program_id(0)
    n_live = live_ref[b]
    rows = q_ref.shape[2]
    part_rows = rows // parts

    def copies(blk, slot):
        out = []
        w = k_hbm.shape[2]
        for j in range(n_pages):
            page = tables_ref[b, blk * n_pages + j]
            rows_j = pl.ds(j * w, w)
            out += [pltpu.make_async_copy(k_hbm.at[page],
                                          k_buf.at[slot, :, rows_j],
                                          sem.at[slot, 0]),
                    pltpu.make_async_copy(v_hbm.at[page],
                                          v_buf.at[slot, :, rows_j],
                                          sem.at[slot, 1])]
        return out

    def by_part(x):              # (r, L) -> (r R, L): row c R + g <- x[c]
        out = jnp.broadcast_to(x[0:1], (rows, x.shape[1]))
        row = jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        for c in range(1, parts):
            out = jnp.where(row >= c * part_rows, x[c:c + 1], out)
        return out

    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(n_live > 0)
    def _first():
        for c in copies(0, 0):
            c.start()

    def block(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_live)
        def _next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        ok = by_part(valid_ref[0, i]) > 0                     # (rR, T / r)
        for h in range(n_kv):
            q = q_ref[0, h] * scale                           # (rR, 128)
            s = jax.lax.dot_general(q, k_buf[slot, h].astype(jnp.float32),
                                    (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quantized:
                s = s * by_part(ks_ref[0, h, i])
            s = jnp.where(ok, s, NEG_INF)
            m_prev = m_s[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_s[h] = l_s[h] * corr + jnp.sum(p, axis=1, keepdims=True)
            if quantized:
                p = p * by_part(vs_ref[0, h, i])
            acc_s[h] = acc_s[h] * corr + jnp.dot(
                p, v_buf[slot, h].astype(jnp.float32),
                preferred_element_type=jnp.float32)
            m_s[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_live, block, 0)

    # merge the lane parts: part c's values sit in lanes [c d, (c + 1) d).
    # Here, not in XLA after the call: that merge read wrong numbers on a
    # TPU v5e (PERF.md, Findings).
    part = [slice(c * part_rows, (c + 1) * part_rows) for c in range(parts)]
    for h in range(n_kv):
        m = m_s[h, part[0]]
        for c in range(1, parts):
            m = jnp.maximum(m, m_s[h, part[c]])
        o = jnp.zeros_like(acc_s[h, part[0]])
        l = jnp.zeros_like(m)
        for c in range(parts):
            w = jnp.exp(m_s[h, part[c]] - m)
            acc = acc_s[h, part[c]]
            if c:
                acc = pltpu.roll(acc, LANES - c * d, 1)
            o, l = o + w * acc, l + w * l_s[h, part[c]]
        o_ref[0, h] = o
        l_ref[0, h] = l
        m_ref[0, h] = m


@functools.partial(jax.jit, static_argnames=("interpret", "return_partials"))
def paged_flash_decode(q, k_pages, v_pages, block_tables, valid,
                       k_scale_pages=None, v_scale_pages=None, *,
                       interpret: bool = True,
                       return_partials: bool = False):
    """Single-query attention where each batch row gathers its K/V pages
    through its block table.

    q            (B, H, d)
    k/v_pages    (P, KV, bs, d)   — the whole pool, pages shared by rows
    block_tables (B, nb) int32    — physical page id per virtual block
    valid        (B, nb * bs)     — readable virtual positions (masks both
                                    unwritten tail positions and any NULL /
                                    stale table entries)
    k/v_scale_pages (P, KV, bs, 1) f32 — int8 dequant scales (both or none)

    One program per batch row covers every kv head: a page is one
    (KV, bs, d) slab, fetched whole.  The row walks compute blocks of
    ``decode_pages_per_block`` pages, double-buffered through manual DMAs
    from the pools left in HBM, and stops after its last valid position:
    later blocks are neither copied nor computed.  A page DMA needs a minor
    dim of whole 128-lane rows, so the pools are viewed as
    (P, KV, bs / r, 128) with r = 128 / d positions per row (d = 64: two),
    or zero-padded to 128 lanes where d does not divide 128.

    -> out (B, H, d), or with ``return_partials`` the unnormalized online-
    softmax triple (o_un (B,KV,G,d), l (B,KV,G), m (B,KV,G)) so the caller
    can fold in the current token's (k, v) before normalizing.
    """
    b, h, d = q.shape
    _, n_kv, bs, _ = k_pages.shape
    assert h % n_kv == 0
    g = h // n_kv
    nb = block_tables.shape[1]
    assert valid.shape == (b, nb * bs), (valid.shape, b, nb, bs)
    quantized = k_scale_pages is not None
    assert quantized == (v_scale_pages is not None)
    n_pages = decode_pages_per_block(bs, nb, n_kv, d, k_pages.dtype.itemsize)
    n_blk = -(-nb // n_pages)
    t = n_pages * bs
    r = _lane_parts(d, bs)
    lanes = _page_lanes(d, bs)
    part_rows = -(-g // 8) * 8

    def lane_rows(x):          # (..., T) -> (..., r, T / r): lane part first
        x = x.reshape(x.shape[:-1] + (t // r, r))
        return jnp.swapaxes(x, -1, -2)

    def pool(pages):           # (P, KV, bs, d) -> (P, KV, bs / r, lanes)
        if r > 1:
            return pages.reshape(pages.shape[0], n_kv, bs // r, lanes)
        return jnp.pad(pages, ((0, 0),) * 3 + ((0, lanes - d),))

    # past the table's end the last block reads page 0, masked like any
    # dead entry; a row's live blocks end at its last valid position
    tables = jnp.pad(block_tables.astype(jnp.int32),
                     ((0, 0), (0, n_blk * n_pages - nb)))
    valid = jnp.pad(valid.astype(bool), ((0, 0), (0, n_blk * t - nb * bs)))
    idx = jnp.arange(n_blk * t, dtype=jnp.int32)
    last = jnp.max(jnp.where(valid, idx, -1), axis=1)
    live = jnp.where(last >= 0, last // t + 1, 0).astype(jnp.int32)
    # query row c R + g: query g in lane part c, zeros elsewhere
    qg = q.astype(jnp.float32).reshape(b, n_kv, g, d)
    qg = jnp.concatenate(
        [jnp.pad(qg, ((0, 0), (0, 0), (0, part_rows - g),
                      (c * d, lanes - (c + 1) * d))) for c in range(r)],
        axis=2)

    def row(*shape):
        return pl.BlockSpec((1,) + shape,
                            lambda b_, tbl, lv: (b_,) + (0,) * len(shape))
    in_specs = [row(n_kv, r * part_rows, lanes), row(n_blk, r, t // r)]
    operands = [qg, lane_rows(valid.astype(jnp.int32).reshape(b, n_blk, t))]
    if quantized:
        def scale_rows(sp):    # (P, KV, bs, 1) -> (B, KV, n_blk, r, T / r)
            sp = sp[tables][..., 0]                     # (B, nb', KV, bs)
            return lane_rows(sp.transpose(0, 2, 1, 3)
                             .reshape(b, n_kv, n_blk, t))
        in_specs += [row(n_kv, n_blk, r, t // r)] * 2
        operands += [scale_rows(k_scale_pages), scale_rows(v_scale_pages)]
    in_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
    operands += [pool(k_pages), pool(v_pages)]
    stat = row(n_kv, part_rows, 1)
    kernel = functools.partial(_paged_decode_kernel, n_pages=n_pages,
                               n_kv=n_kv, parts=r, d=d, quantized=quantized,
                               scale=1.0 / (d ** 0.5))
    buf = (2, n_kv, t // r, lanes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=in_specs,
        out_specs=[row(n_kv, part_rows, lanes), stat, stat],
        scratch_shapes=[
            pltpu.VMEM(buf, k_pages.dtype),
            pltpu.VMEM(buf, v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((n_kv, r * part_rows, lanes), jnp.float32),
            pltpu.VMEM((n_kv, r * part_rows, 1), jnp.float32),
            pltpu.VMEM((n_kv, r * part_rows, 1), jnp.float32),
        ],
    )
    o, l, m = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, n_kv, part_rows, lanes), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, part_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, n_kv, part_rows, 1), jnp.float32),
        ],
        interpret=interpret,
        name="paged_flash_decode",
    )(tables, live, *operands)
    o_un, l, m = o[:, :, :g, :d], l[:, :, :g, 0], m[:, :, :g, 0]
    if return_partials:
        return o_un, l, m
    out = o_un / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, h, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_prefill_chunk(q, k_pages, v_pages, block_tables, valid,
                              k_scale_pages=None, v_scale_pages=None, *,
                              interpret: bool = True):
    """Chunked-prefill attention over the pages: ``paged_flash_decode``
    extended to a q-block > 1 — all C chunk queries of a request ride ONE
    program per (row, kv head, page), so each K/V page is DMA'd once for
    the whole chunk instead of once per token.

    q (B, C, H, d) — the query chunk; every chunk query attends the same
    readable cache positions ``valid`` (B, nb*bs) = [0, pos_start), so the
    per-key mask is shared across the q-block (causal-within-chunk is the
    caller's merge step, the chunk's K/V not being in pages yet).

    -> UNNORMALIZED (o (B,KV,G,C,d), l (B,KV,G,C), m (B,KV,G,C)): the
    caller folds in the within-chunk causal block (``_merge_kv_block``)
    before normalizing — the same partials contract as the decode kernel's
    ``extra_kv`` merge.
    """
    b, c, h, d = q.shape
    n_kv = k_pages.shape[1]
    assert h % n_kv == 0
    g = h // n_kv
    # (B, C, H, d) -> (B, KV, G*C, d): the kernel sees one (G*C, d) q-block
    qg = q.reshape(b, c, n_kv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b, n_kv, g * c, d)
    o_un, l, m = _paged_attend(qg, k_pages, v_pages, block_tables, valid,
                               k_scale_pages, v_scale_pages,
                               interpret=interpret)
    return (o_un.reshape(b, n_kv, g, c, d), l.reshape(b, n_kv, g, c),
            m.reshape(b, n_kv, g, c))


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_flash_packed_chunk(q, k_pages, v_pages, seg, seg_tables, seg_valid,
                             k_scale_pages=None, v_scale_pages=None, *,
                             interpret: bool = True):
    """Packed multi-request chunk attention over the pages.

    One fused C-token chunk carries tokens of up to R requests ("segments"
    — e.g. the tail of one prompt packed with the head of the next).  Each
    segment r owns block-table row ``seg_tables[r]`` and validity prefix
    ``seg_valid[r]`` ([0, its prefill progress)); ``seg[i]`` names the
    segment token i belongs to.  The launch keeps the chunked-prefill
    kernel's page economics: ONE q-block of all C chunk queries rides each
    (segment, kv head, page) program, so a page is DMA'd once per chunk,
    not once per token — then each token keeps only the partials of ITS
    segment's pass.  Cross-request cache isolation is block-diagonal by
    construction (a token can only ever see its own request's pages); the
    within-chunk block (keys not yet in pages) is the caller's merge under
    ``attention.packed_chunk_mask`` — block-diagonal causal for chunked
    prefill / linear speculative verify, or the per-token ANCESTOR mask
    when the segment carries a speculative token tree.  The kernel itself
    is ancestor-oblivious on purpose: every tree node shares its slot's
    committed cache prefix [0, pos) verbatim (``seg_valid`` is per
    segment, not per token), so the tree shape only ever reaches the
    caller-side within-chunk merge, never the page loop.

    q (C, H, d); seg (C,) int32 in [0, R); seg_tables (R, nb) int32;
    seg_valid (R, nb * bs) bool; k/v_scale_pages (P, KV, bs, 1) or None.

    -> UNNORMALIZED per-token partials (o (C, KV, G, d), l (C, KV, G),
    m (C, KV, G)) — same contract as ``paged_flash_decode``'s partials, so
    the caller folds the within-chunk block exactly like ``extra_kv``.
    Tokens of a segment with an all-False validity row (a prompt head with
    no cache yet) come back with m = NEG_INF partials, which the merge
    flushes to exact zeros.
    """
    c, h, d = q.shape
    n_kv = k_pages.shape[1]
    assert h % n_kv == 0
    g = h // n_kv
    r = seg_tables.shape[0]
    # every segment's program sees the FULL C-token q-block (page DMA'd
    # once per segment); (C,H,d) -> (R, KV, G*C, d)
    qg = q.reshape(c, n_kv, g, d).transpose(1, 2, 0, 3).reshape(n_kv, g * c, d)
    qg = jnp.broadcast_to(qg[None], (r, n_kv, g * c, d))
    o_un, l, m = _paged_attend(qg, k_pages, v_pages, seg_tables, seg_valid,
                               k_scale_pages, v_scale_pages,
                               interpret=interpret)
    o_un = o_un.reshape(r, n_kv, g, c, d)
    l = l.reshape(r, n_kv, g, c)
    m = m.reshape(r, n_kv, g, c)
    # token i keeps the partials of its own segment's pass
    seg = jnp.asarray(seg, jnp.int32)
    tok = jnp.arange(c)
    return o_un[seg, :, :, tok], l[seg, :, :, tok], m[seg, :, :, tok]
