"""Per-kernel allclose tests: Pallas (interpret=True on CPU) vs pure-jnp
oracle, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (flash_attention, flash_decode, make_unroll_kernel,
                           paged_flash_decode, paged_flash_prefill_chunk,
                           ttt_probe_scan, wkv_scan)
from repro.kernels import ref as R
from repro.core.probe import ProbeConfig
from repro.core import ttt


# ---------------------------------------------------------------------------
# TTT probe fused scan

@pytest.mark.parametrize("n,t,f", [(2, 16, 128), (3, 40, 256), (1, 130, 128)])
def test_ttt_probe_scan_matches_ref(n, t, f):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    zq = jax.random.normal(ks[0], (n, t, f))
    zk = jax.random.normal(ks[1], (n, t, f))
    c = (jax.random.uniform(ks[2], (n, t)) > 0.5).astype(jnp.float32)
    m = jnp.ones((n, t))
    w0 = jax.random.normal(ks[3], (f,)) / np.sqrt(f)
    b0 = jnp.asarray(0.3)
    eta = jnp.asarray(0.01)
    s, wf, bf = ttt_probe_scan(zq, zk, c, m, w0, b0, eta, t_chunk=32)
    s_r, wf_r, bf_r = R.ttt_probe_ref(zq, zk, c, m, w0, b0, eta)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_r), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(wf), np.asarray(wf_r), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(bf), np.asarray(bf_r), rtol=2e-4, atol=2e-5)


def test_ttt_probe_scan_respects_mask():
    n, t, f = 2, 24, 128
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    zq = jax.random.normal(ks[0], (n, t, f))
    m = jnp.concatenate([jnp.ones((n, 12)), jnp.zeros((n, 12))], axis=1)
    w0 = jax.random.normal(ks[1], (f,)) / np.sqrt(f)
    _, wf, _ = ttt_probe_scan(zq, zq, jnp.zeros((n, t)), m, w0,
                              jnp.asarray(0.0), jnp.asarray(0.05), t_chunk=8)
    _, wf_r, _ = R.ttt_probe_ref(zq, zq, jnp.zeros((n, t)), m, w0,
                                 jnp.asarray(0.0), jnp.asarray(0.05))
    np.testing.assert_allclose(np.asarray(wf), np.asarray(wf_r), rtol=1e-4)


def test_ttt_kernel_plugs_into_core_unroll():
    """The kernel is a drop-in for the core inner loop at deployment."""
    pc = ProbeConfig(d_phi=128)
    from repro.core.probe import init_outer
    theta = init_outer(pc, jax.random.PRNGKey(0))
    phis = jax.random.normal(jax.random.PRNGKey(1), (3, 20, 128))
    mask = jnp.ones((3, 20))
    s_core = ttt.deployed_scores(pc, theta, phis, mask)
    s_kern = ttt.deployed_scores(pc, theta, phis, mask,
                                 kernel=make_unroll_kernel(t_chunk=16))
    np.testing.assert_allclose(np.asarray(s_core), np.asarray(s_kern),
                               rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Flash attention (prefill)

@pytest.mark.parametrize("b,sq,sk,h,kv,d", [
    (1, 128, 128, 4, 4, 64),     # MHA
    (2, 128, 128, 8, 2, 64),     # GQA
    (1, 256, 256, 4, 1, 128),    # MQA, larger head
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, sq, sk, h, kv, d, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, sk, kv, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, sk, kv, d)).astype(dtype)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64)
    ref = R.flash_attention_ref(q, k, v, causal=True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64))
    k = jax.random.normal(ks[1], (1, 128, 4, 64))
    v = jax.random.normal(ks[2], (1, 128, 4, 64))
    out = flash_attention(q, k, v, causal=True, window=32, bq=32, bk=32)
    ref = R.flash_attention_ref(q, k, v, causal=True, window=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Flash decode

@pytest.mark.parametrize("b,h,kv,s,d", [
    (2, 8, 8, 512, 64), (2, 8, 2, 1024, 64), (1, 16, 4, 2048, 128)])
def test_flash_decode_matches_ref(b, h, kv, s, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    # partially filled cache: positions >= fill are invalid
    fill = s // 2 + 3
    valid = jnp.broadcast_to(jnp.arange(s) < fill, (b, s))
    out = flash_decode(q, k, v, valid, bs=256)
    ref = R.flash_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_decode_ragged_valid():
    """Per-row validity (ring buffers) is honored."""
    b, h, kv, s, d = 3, 4, 4, 256, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    valid = jax.random.uniform(jax.random.PRNGKey(5), (b, s)) > 0.4
    valid = valid.at[:, 0].set(True)
    out = flash_decode(q, k, v, valid, bs=64)
    ref = R.flash_decode_ref(q, k, v, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Paged flash decode (block-table gather through scalar prefetch)

def _paged_rows(case, b, bs, p, nb, n_pages, rng):
    """(tables, valid) of one scenario; ``n_pages`` pages per compute
    block, so a block holds T = n_pages * bs positions.

    prefix  random tables, each row at its own length
    edges   rows of 0, 1, T and T + 1 positions; entries past a row's
            length point at the NULL page 0
    holes   masks with holes: a span across a block boundary, all but the
            last live block, the first position
    stale   entries past a row's length point at other rows' pages or at
            the NULL page"""
    t = n_pages * bs
    span = np.arange(nb * bs)
    own = rng.permutation(np.arange(1, p))[:b * nb].reshape(b, nb)
    if case == "prefix":
        pos = np.array([((i + 1) * nb * bs) // (b + 1) + 1 for i in range(b)])
        return (rng.randint(0, p, (b, nb)),
                span[None] < pos[:, None])
    if case == "holes":
        valid = np.stack([span < 3 * t, (span >= 2 * t + 3) & (span < 3 * t),
                          (span > 0) & (span < t + 5)][:b])
        valid[0, t // 2:t + t // 2] = False
        return own, valid
    lens = {"edges": [0, 1, t, t + 1], "stale": [t + 3, 2 * t + 1, 5]}[case]
    lens = np.array((lens * b)[:b])
    used = -(-lens // bs)
    tables = own.copy()
    for i in range(b):
        dead = np.arange(used[i], nb)
        if case == "stale":
            tables[i, dead[::2]] = own[(i + 1) % b, dead[::2]]
            tables[i, dead[1::2]] = 0
        else:
            tables[i, dead] = 0
    return tables, span[None] < lens[:, None]


@pytest.mark.parametrize("b,h,kv,d,bs,p,nb,case", [
    # MHA; GQA with small pages; MQA-ish with a larger head
    pytest.param(2, 8, 8, 64, 16, 24, 6, "prefix", id="2-8-8-64-16-24-6"),
    pytest.param(3, 8, 2, 64, 8, 16, 4, "prefix", id="3-8-2-64-8-16-4"),
    pytest.param(1, 16, 4, 128, 32, 12, 8, "prefix", id="1-16-4-128-32-12-8"),
    # G = 3 (15 / 5 heads), as served.  64-token pages: a compute block is
    # 16 pages (12 in float32, by the buffer cap), and 35 entries make
    # three blocks, the last one short
    pytest.param(4, 15, 5, 64, 64, 141, 35, "edges", id="g3-edges"),
    # a table of fewer entries than a block's pages: one block of them all
    pytest.param(2, 15, 5, 64, 16, 12, 3, "stale", id="g3-nb-lt-n"),
    pytest.param(3, 15, 5, 64, 64, 141, 35, "holes", id="g3-holes"),
    pytest.param(3, 15, 5, 64, 64, 141, 35, "stale", id="g3-stale"),
    # four positions per lane row (d 32), and d padded to 128 lanes (d 80)
    pytest.param(3, 4, 2, 32, 64, 141, 35, "holes", id="d32-holes"),
    pytest.param(4, 6, 2, 80, 64, 141, 35, "edges", id="d80-edges"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, "int8"])
def test_paged_flash_decode_matches_ref(b, h, kv, d, bs, p, nb, case, dtype):
    """The many-page, all-heads kernel equals the gathered-pages oracle:
    blocks past a row's last valid position are skipped, masks inside
    live blocks stay exact, and no masked table entry reaches a result."""
    from repro.kernels.decode_attention import decode_pages_per_block
    from repro.models.attention import quantize_kv
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    pages = [jax.random.normal(k, (p, kv, bs, d)) for k in ks[1:]]
    scales = ()
    if dtype == "int8":
        (k_pages, ksc), (v_pages, vsc) = map(quantize_kv, pages)
        scales, tol = (ksc, vsc), 2e-4
    else:
        k_pages, v_pages = (x.astype(dtype) for x in pages)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    q = jax.random.normal(ks[0], (b, h, d)).astype(
        jnp.float32 if dtype == "int8" else dtype)
    itemsize = jnp.dtype(jnp.int8 if dtype == "int8" else dtype).itemsize
    n_pages = decode_pages_per_block(bs, nb, kv, d, itemsize)
    tables, valid = _paged_rows(case, b, bs, p, nb, n_pages,
                                np.random.RandomState(nb))
    tables, valid = jnp.asarray(tables, jnp.int32), jnp.asarray(valid)
    o, l, m = paged_flash_decode(q, k_pages, v_pages, tables, valid, *scales,
                                 return_partials=True)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).reshape(b, h, d)
    ref = R.paged_decode_ref(q, k_pages, v_pages, tables, valid, *scales)
    np.testing.assert_allclose(np.asarray(out.astype(q.dtype), np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)
    # a row with no valid position keeps m = NEG_INF, l = 0, o = 0
    empty = ~np.asarray(valid).any(axis=1)
    assert (np.asarray(m)[empty] <= -1e29).all()
    assert not np.asarray(l)[empty].any() and not np.asarray(o)[empty].any()


def test_paged_flash_decode_int8_kv():
    """int8 pages dequantize per VMEM block inside the kernel."""
    from repro.models.attention import quantize_kv
    b, h, kv, d, bs, p, nb = 2, 8, 4, 64, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(ks[0], (b, h, d))
    kq, ksc = quantize_kv(jax.random.normal(ks[1], (p, kv, bs, d)))
    vq, vsc = quantize_kv(jax.random.normal(ks[2], (p, kv, bs, d)))
    tables = jax.random.randint(ks[3], (b, nb), 0, p)
    valid = jnp.arange(nb * bs)[None, :] < jnp.asarray([[13], [29]])
    out = paged_flash_decode(q, kq, vq, tables, valid, ksc, vsc)
    ref = R.paged_decode_ref(q, kq, vq, tables, valid, ksc, vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Paged prefill chunk (q-block > 1 extension of the paged kernel)

@pytest.mark.parametrize("b,c,h,kv,d,bs,p,nb", [
    (2, 4, 8, 8, 64, 16, 24, 6),   # MHA
    (3, 6, 8, 2, 64, 8, 16, 4),    # GQA, small pages
    (1, 16, 16, 4, 128, 32, 12, 8),  # chunk wider than a page
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_prefill_chunk_matches_ref(b, c, h, kv, d, bs, p, nb, dtype):
    """The q-block > 1 kernel's unnormalized partials equal the gathered-
    pages oracle for every chunk query row."""
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (b, c, h, d)).astype(dtype)
    k_pages = jax.random.normal(ks[1], (p, kv, bs, d)).astype(dtype)
    v_pages = jax.random.normal(ks[2], (p, kv, bs, d)).astype(dtype)
    tables = jax.random.randint(ks[3], (b, nb), 0, p)
    # mid-prefill: each request resumed at its own progress (>= 1 so the
    # kernel/oracle l-garbage-flush corner stays out of the raw partials)
    pos = jnp.asarray([((i + 1) * nb * bs) // (b + 1) + 1 for i in range(b)])
    valid = jnp.arange(nb * bs)[None, :] < pos[:, None]
    o, l, m = paged_flash_prefill_chunk(q, k_pages, v_pages, tables, valid)
    o_r, l_r, m_r = R.paged_prefill_chunk_ref(q, k_pages, v_pages, tables,
                                              valid)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    for a, r in ((o, o_r), (l, l_r), (m, m_r)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(r, np.float32),
                                   rtol=tol, atol=tol)


def test_paged_prefill_chunk_int8_kv():
    from repro.models.attention import quantize_kv
    b, c, h, kv, d, bs, p, nb = 2, 5, 8, 4, 64, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.normal(ks[0], (b, c, h, d))
    kq, ksc = quantize_kv(jax.random.normal(ks[1], (p, kv, bs, d)))
    vq, vsc = quantize_kv(jax.random.normal(ks[2], (p, kv, bs, d)))
    tables = jax.random.randint(ks[3], (b, nb), 0, p)
    valid = jnp.arange(nb * bs)[None, :] < jnp.asarray([[13], [29]])
    o, l, m = paged_flash_prefill_chunk(q, kq, vq, tables, valid, ksc, vsc)
    o_r, l_r, m_r = R.paged_prefill_chunk_ref(q, kq, vq, tables, valid,
                                              ksc, vsc)
    for a, r in ((o, o_r), (l, l_r), (m, m_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def test_prefill_chunk_q_block_one_equals_decode_kernel():
    """A C=1 chunk IS a decode step without the extra_kv column: the
    q-block > 1 kernel must reproduce paged_flash_decode's partials."""
    b, h, kv, d, bs, p, nb = 2, 8, 4, 64, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(15), 4)
    q = jax.random.normal(ks[0], (b, h, d))
    k_pages = jax.random.normal(ks[1], (p, kv, bs, d))
    v_pages = jax.random.normal(ks[2], (p, kv, bs, d))
    tables = jax.random.randint(ks[3], (b, nb), 0, p)
    valid = jnp.arange(nb * bs)[None, :] < jnp.asarray([[9], [22]])
    o_d, l_d, m_d = paged_flash_decode(q, k_pages, v_pages, tables, valid,
                                       return_partials=True)
    o_c, l_c, m_c = paged_flash_prefill_chunk(q[:, None], k_pages, v_pages,
                                              tables, valid)
    np.testing.assert_allclose(np.asarray(o_c[:, :, :, 0]), np.asarray(o_d),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(l_c[..., 0]), np.asarray(l_d),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(m_c[..., 0]), np.asarray(m_d),
                               rtol=2e-5, atol=2e-5)


def test_chunked_prefill_attention_pallas_matches_jnp():
    """End-to-end chunk attention (kernel partials + within-chunk causal
    merge) equals the jnp concat-softmax path, including the fully-masked
    first chunk (pos_start=0)."""
    from repro.models import attention as A
    b, c, h, kv, d, bs, p, nb = 2, 6, 8, 4, 64, 8, 16, 4
    ks = jax.random.split(jax.random.PRNGKey(17), 5)
    q = jax.random.normal(ks[0], (b, c, h, d))
    k_new = jax.random.normal(ks[1], (b, c, kv, d))
    v_new = jax.random.normal(ks[2], (b, c, kv, d))
    cache_l = {"k": jax.random.normal(ks[3], (p, kv, bs, d)),
               "v": jax.random.normal(ks[4], (p, kv, bs, d))}
    tables = jax.random.randint(jax.random.PRNGKey(18), (b, nb), 0, p)
    for pos_start in (0, 7):
        valid = jnp.broadcast_to(jnp.arange(nb * bs)[None, :] < pos_start,
                                 (b, nb * bs))
        o_j = A.attn_prefill_chunk(q, k_new, v_new, cache_l, valid,
                                   jnp.float32, block_tables=tables,
                                   impl="jnp")
        o_p = A.attn_prefill_chunk(q, k_new, v_new, cache_l, valid,
                                   jnp.float32, block_tables=tables,
                                   impl="pallas", interpret=True)
        assert np.isfinite(np.asarray(o_p)).all()
        np.testing.assert_allclose(np.asarray(o_j), np.asarray(o_p),
                                   rtol=2e-5, atol=2e-5)


def test_paged_matches_dense_flash_decode_when_contiguous():
    """An identity block table makes paged attention literally the dense
    cache read: both kernels must agree."""
    b, h, kv, d, bs, nb = 2, 4, 4, 64, 64, 4
    s = nb * bs
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, h, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    valid = jnp.broadcast_to(jnp.arange(s) < s - 17, (b, s))
    dense = flash_decode(q, k, v, valid, bs=bs)
    # pages for row b occupy ids [b*nb, (b+1)*nb): (B*nb, KV, bs, d)
    pages_k = k.reshape(b, kv, nb, bs, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b * nb, kv, bs, d)
    pages_v = v.reshape(b, kv, nb, bs, d).transpose(0, 2, 1, 3, 4) \
        .reshape(b * nb, kv, bs, d)
    tables = jnp.arange(b * nb, dtype=jnp.int32).reshape(b, nb)
    paged = paged_flash_decode(q, pages_k, pages_v, tables, valid)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# RWKV6 WKV scan

@pytest.mark.parametrize("b,t,h,d", [(1, 32, 2, 32), (2, 100, 4, 64)])
def test_wkv_scan_matches_ref(b, t, h, d):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    r = jax.random.normal(ks[0], (b, t, h, d)) * 0.5
    k = jax.random.normal(ks[1], (b, t, h, d)) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, d)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, d)))  # decay in (0,1)
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    s0 = jnp.zeros((b, h, d, d))
    out, sf = wkv_scan(r, k, v, w, u, s0, ct=16)
    out_r, sf_r = R.wkv_scan_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sf_r),
                               rtol=1e-4, atol=1e-4)


def test_wkv_scan_state_carry():
    """Chunked state carry: running two halves sequentially == one pass."""
    b, t, h, d = 1, 64, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    r = jax.random.normal(ks[0], (b, t, h, d)) * 0.5
    k = jax.random.normal(ks[1], (b, t, h, d)) * 0.5
    v = jax.random.normal(ks[2], (b, t, h, d)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, d)))
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    s0 = jnp.zeros((b, h, d, d))
    out_full, sf_full = wkv_scan(r, k, v, w, u, s0, ct=16)
    o1, s1 = wkv_scan(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, s0, ct=16)
    o2, s2 = wkv_scan(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s1, ct=16)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([o1, o2], 1)),
                               np.asarray(out_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(sf_full),
                               rtol=1e-4, atol=1e-4)
