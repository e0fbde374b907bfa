"""Numerical consistency across execution paths (the bugs these catch:
rope/position errors, cache indexing, ring-slot arithmetic, token-shift and
SSM state carry, blockwise-softmax accumulation).

1. prefill(prompt) + decode_step*(k) logits == teacher-forced forward logits
   at the same positions, per architecture family.
2. blockwise flash attention == einsum attention at the model level.
3. int8 KV cache decode stays close to the bf16/f32 cache decode.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import InputShape, get_config
from repro.models import build
from repro.models.attention import (attn_prefill_blockwise,
                                    attn_prefill_einsum)

PROMPT, GEN = 12, 6


def _greedy_reference(model, params, tokens_full, batch_extra):
    """Teacher-forced forward over the full sequence -> logits (B,S,V)."""
    cfg = model.cfg
    batch = {"tokens": tokens_full, **batch_extra}
    logits, hidden, _ = model.forward(cfg, params, batch)
    return np.asarray(logits, np.float32)


@pytest.mark.parametrize("arch", ["smollm_360m", "qwen15_32b", "rwkv6_1b6",
                                  "hymba_1b5", "granite_moe_1b"])
def test_prefill_decode_matches_forward(arch):
    cfg = get_config(arch).reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B = 2
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(rng, (B, PROMPT + GEN), 0, cfg.vocab_size)
    extra = {}
    ref = _greedy_reference(model, params, tokens, extra)

    cache_len = cfg.n_meta_tokens + PROMPT + GEN + 2
    state, last_h, _ = model.prefill(cfg, params,
                                     {"tokens": tokens[:, :PROMPT], **extra},
                                     cache_len)
    # decode the remaining tokens teacher-forced, compare logits
    prefix = cfg.n_meta_tokens  # meta tokens shift absolute positions
    _, window = model.decode_geometry(InputShape("d", cache_len, B, "decode"))
    for i in range(GEN):
        pos = jnp.asarray(prefix + PROMPT + i, jnp.int32)
        tok = tokens[:, PROMPT + i]
        logits, hidden, state = model.decode_step(cfg, params, tok, state, pos,
                                                  window=window)
        got = np.asarray(logits, np.float32)
        # forward() prepends the meta tokens, so the teacher-forced logits
        # for token PROMPT+i sit at sequence index prefix + PROMPT + i
        want = ref[:, prefix + PROMPT + i, :]
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2,
                                   err_msg=f"{arch} step {i}")


def test_whisper_prefill_decode_matches_forward():
    cfg = get_config("whisper_tiny").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 8
    rng = jax.random.PRNGKey(1)
    tokens = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    frames = jax.random.normal(rng, (B, cfg.frontend.n_tokens, cfg.d_model)) * 0.02
    ref = _greedy_reference(model, params, tokens, {"frames": frames})
    state, _, _ = model.prefill(cfg, params, {"frames": frames}, S + 2)
    for i in range(S):
        pos = jnp.asarray(i, jnp.int32)
        logits, _, state = model.decode_step(cfg, params, tokens[:, i], state,
                                             pos)
        np.testing.assert_allclose(np.asarray(logits, np.float32),
                                   ref[:, i, :], rtol=2e-2, atol=2e-2,
                                   err_msg=f"whisper step {i}")


@pytest.mark.parametrize("causal,window", [(True, None), (True, 24)])
def test_blockwise_matches_einsum_model_level(causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b, s, h, kv, d = 2, 64, 4, 2, 32
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kv, d))
    v = jax.random.normal(ks[2], (b, s, kv, d))
    ref = attn_prefill_einsum(q, k, v, causal=causal, window=window)
    for diff in (False, True):
        out = attn_prefill_blockwise(q, k, v, causal=causal, window=window,
                                     q_block=16, kv_block=16,
                                     differentiable=diff)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_int8_cache_close_to_fp_cache():
    cfg = get_config("smollm_360m").reduced()
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    model, model8 = build(cfg), build(cfg8)
    params = model.init(jax.random.PRNGKey(0))
    B = 2
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, PROMPT), 0,
                                cfg.vocab_size)
    outs = []
    for m, c in ((model, cfg), (model8, cfg8)):
        state, _, _ = m.prefill(c, params, {"tokens": tokens}, PROMPT + 4)
        tok = jnp.zeros((B,), jnp.int32)
        for i in range(3):
            logits, _, state = m.decode_step(c, params, tok, state,
                                             jnp.asarray(PROMPT + i, jnp.int32))
            tok = jnp.argmax(logits[:, :c.vocab_size], -1).astype(jnp.int32)
        outs.append(np.asarray(logits, np.float32))
    # int8 quantization error should stay small relative to logit scale
    scale = np.abs(outs[0]).mean()
    err = np.abs(outs[0] - outs[1]).mean()
    assert err < 0.15 * scale, (err, scale)


def test_ring_buffer_matches_full_cache_within_window():
    """With seq shorter than the window, ring-buffer decode == full-cache."""
    cfg = get_config("smollm_360m").reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, W = 2, 16
    tokens = jax.random.randint(jax.random.PRNGKey(1), (B, 8), 0,
                                cfg.vocab_size)
    # full cache
    s_full, _, _ = model.prefill(cfg, params, {"tokens": tokens}, 32)
    # ring cache of size W (pad prefill cache into a ring: use decode only)
    s_ring, _, _ = model.prefill(cfg, params, {"tokens": tokens}, W)
    tok = jnp.zeros((B,), jnp.int32)
    for i in range(4):
        pos = jnp.asarray(8 + i, jnp.int32)
        lf, _, s_full = model.decode_step(cfg, params, tok, s_full, pos)
        lr, _, s_ring = model.decode_step(cfg, params, tok, s_ring, pos,
                                          window=W)
        np.testing.assert_allclose(np.asarray(lf, np.float32),
                                   np.asarray(lr, np.float32),
                                   rtol=2e-3, atol=2e-3, err_msg=f"step {i}")
        tok = jnp.argmax(lf[:, :cfg.vocab_size], -1).astype(jnp.int32)


def test_smoke_logit_check_passes_at_reduced_size(monkeypatch):
    """chip_smoke.py's logits phase at the reduced config, kernels
    interpreted: prefill + decode through the dense cache, and packed
    chunks + paged decode through the Pallas kernels, each within the
    stated bound of the float32 forward pass."""
    monkeypatch.setenv("REPRO_PAGED_ATTN", "pallas")
    import chip_smoke
    chip_smoke.check_logits(get_config("smollm-360m").reduced(), seed=0)
