"""Fused TTT-probe kernels — Pallas TPU.

Two entry points share one inner formula (``repro.core.probe.score_then_update``):

* ``ttt_probe_scan`` / ``ttt_probe_batched`` — the OFFLINE scan over whole
  trajectories (Algorithm 2 lines 8-16, meta-eval / LTT calibration).  The
  recurrence is sequential in T, so the kernel exploits the TPU grid's
  sequential-iteration order: grid = (N, T/T_CHUNK); the fast weights (W, b)
  live in VMEM scratch and persist across the T-chunks of one trajectory
  while phi-chunks stream HBM->VMEM.  ``ttt_probe_batched`` is the
  vector-state generalization: every trajectory starts from its OWN (W_i,
  b_i) — the chunked multi-step building block for multi-token serving.
* ``serving_probe_step`` — the SERVING hot path: one batched decode step for
  all engine slots, fusing score-then-update with the rolling-window
  smoothing and the calibrated threshold test (the full per-step deployed
  procedure).  The per-slot state (W, b, ring, counters) stays in VMEM for
  the step; the engine jit donates the buffers so XLA updates them in place.

The deployed procedure — decode + probe + threshold — is exactly what gets
LTT-calibrated, so the serving engine routes through these kernels instead
of re-implementing the probe (the PR-1 jnp path survives only as the parity
oracle in ``repro.kernels.ref``).  ``interpret=True`` (the CPU-CI default,
see ``repro.kernels.ops.default_interpret``) executes the same kernel bodies
as plain jax ops.

Layouts (f = feature dim; pad to a multiple of 128 for compiled TPU):
    zq, zk : (N, T, f)   score / update views of the step features
    c      : (N, T)      inner labels (zeros at deployment)
    m      : (N, T)      validity mask (freezes updates on padding)
    -> scores (N, T), W_final (N, f), b_final (N, 1)
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import probe as P

DEFAULT_T_CHUNK = 128


def _kernel(zq_ref, zk_ref, c_ref, m_ref, w0_ref, b0_ref, eta_ref,
            scores_ref, wf_ref, bf_ref, w_s, b_s, *, t_chunk: int,
            n_chunks: int):
    t_idx = pl.program_id(1)

    @pl.when(t_idx == 0)
    def _init():
        w_s[...] = w0_ref[...]
        b_s[0, 0] = b0_ref[0, 0]

    eta = eta_ref[0]

    def step(i, _):
        w = w_s[...]                                  # (1, f)
        b = b_s[0, 0]
        zq = zq_ref[0, i, :][None, :]                 # (1, f)
        zk = zk_ref[0, i, :][None, :]
        s, w_new, b_new = P.score_then_update(w, b, zq, zk, c_ref[0, i],
                                              m_ref[0, i], eta)
        scores_ref[0, i] = s[0]
        w_s[...] = w_new
        b_s[0, 0] = b_new[0]
        return 0

    jax.lax.fori_loop(0, t_chunk, step, 0)

    @pl.when(t_idx == n_chunks - 1)
    def _fin():
        wf_ref[...] = w_s[...]
        bf_ref[0, 0] = b_s[0, 0]


@functools.partial(jax.jit, static_argnames=("t_chunk", "interpret"))
def ttt_probe_batched(zq, zk, c, m, w0, b0, eta, *,
                      t_chunk: int = DEFAULT_T_CHUNK, interpret: bool = True):
    """Chunked multi-step scan with a VECTOR per-trajectory initial state.

    zq/zk (N, T, f); c/m (N, T); w0 (N, f); b0 (N,); eta scalar.
    Returns (scores (N, T), w_final (N, f), b_final (N,)).  Running two
    chunks back to back with the carried (w, b) equals one longer scan —
    the building block for multi-token serving steps.
    """
    n, t, f = zq.shape
    t_chunk = min(t_chunk, t)
    if t % t_chunk:
        pad = t_chunk - t % t_chunk
        zq = jnp.pad(zq, ((0, 0), (0, pad), (0, 0)))
        zk = jnp.pad(zk, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad)))
        m = jnp.pad(m, ((0, 0), (0, pad)))
    t_pad = zq.shape[1]
    n_chunks = t_pad // t_chunk
    f32 = jnp.float32
    kernel = functools.partial(_kernel, t_chunk=t_chunk, n_chunks=n_chunks)
    scores, wf, bf = pl.pallas_call(
        kernel,
        grid=(n, n_chunks),
        in_specs=[
            pl.BlockSpec((1, t_chunk, f), lambda i, j: (i, j, 0)),   # zq
            pl.BlockSpec((1, t_chunk, f), lambda i, j: (i, j, 0)),   # zk
            pl.BlockSpec((1, t_chunk), lambda i, j: (i, j)),         # c
            pl.BlockSpec((1, t_chunk), lambda i, j: (i, j)),         # m
            pl.BlockSpec((1, f), lambda i, j: (i, 0)),               # w0
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),               # b0
            pl.BlockSpec(memory_space=pltpu.SMEM),                   # eta
        ],
        out_specs=[
            pl.BlockSpec((1, t_chunk), lambda i, j: (i, j)),         # scores
            pl.BlockSpec((1, f), lambda i, j: (i, 0)),               # w_final
            pl.BlockSpec((1, 1), lambda i, j: (i, 0)),               # b_final
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, t_pad), f32),
            jax.ShapeDtypeStruct((n, f), f32),
            jax.ShapeDtypeStruct((n, 1), f32),
        ],
        scratch_shapes=[pltpu.VMEM((1, f), f32), pltpu.VMEM((1, 1), f32)],
        interpret=interpret,
    )(zq.astype(f32), zk.astype(f32), c.astype(f32), m.astype(f32),
      w0.astype(f32), b0.reshape(n, 1).astype(f32),
      eta.reshape(1).astype(f32))
    return scores[:, :t], wf, bf[:, 0]


@functools.partial(jax.jit, static_argnames=("t_chunk", "interpret"))
def ttt_probe_scan(zq, zk, c, m, w0, b0, eta, *, t_chunk: int = DEFAULT_T_CHUNK,
                   interpret: bool = True):
    """Offline scan with a SHARED initial state (the meta-learned (W0, b0)).

    zq/zk (N, T, f) f32; c/m (N, T) f32; w0 (f,); b0, eta scalars.
    Returns (scores (N, T), w_final (N, f), b_final (N,)).  Thin broadcast
    over ``ttt_probe_batched`` — one kernel implementation serves both the
    offline calibration path and the per-slot serving path.
    """
    n = zq.shape[0]
    w0 = jnp.broadcast_to(w0.astype(jnp.float32)[None, :], (n, w0.shape[0]))
    b0 = jnp.broadcast_to(jnp.asarray(b0, jnp.float32).reshape(()), (n,))
    return ttt_probe_batched(zq, zk, c, m, w0, b0, jnp.asarray(eta),
                             t_chunk=t_chunk, interpret=interpret)


def make_unroll_kernel(t_chunk: int = DEFAULT_T_CHUNK, interpret: bool = True):
    """Adapter with the signature repro.core.ttt.inner_unroll expects:
    (zq, zk, c, m, W0, b0, eta) -> (scores, W_f, b_f) for ONE trajectory."""
    def kern(zq, zk, c, m, w0, b0, eta):
        s, wf, bf = ttt_probe_scan(zq[None], zk[None], c[None], m[None],
                                   w0, jnp.asarray(b0), jnp.asarray(eta),
                                   t_chunk=t_chunk, interpret=interpret)
        return s[0], wf[0], bf[0]
    return kern


# ---------------------------------------------------------------------------
# Serving hot path: batched single step, fused with smoothing + threshold


class ProbeStepOut(NamedTuple):
    """One fused serving step's per-slot observations + updated state."""
    s: jnp.ndarray           # (B,) raw probe score this token
    W: jnp.ndarray           # (B, f) fast weights after the step
    b: jnp.ndarray           # (B,)
    ring: jnp.ndarray        # (B, window) rolling raw-score window
    n_scores: jnp.ndarray    # (B,) int32 scores emitted since admission
    smoothed: jnp.ndarray    # (B,) rolling-mean score
    stopped: jnp.ndarray     # (B,) bool — calibrated threshold crossed
    stop_step: jnp.ndarray   # (B,) int32 reasoning step at stop (-1 active)


def _serving_kernel(zq_ref, zk_ref, bnd_ref, w_ref, b_ref, ring_ref, n_ref,
                    stopped_ref, step_ref, eta_ref, lam_ref,
                    s_out, w_out, b_out, ring_out, n_out, sm_out,
                    stopped_out, step_out, *, burn_in: int):
    zq, zk, w = zq_ref[...], zk_ref[...], w_ref[...]
    b = b_ref[...][:, 0]
    stopped = stopped_ref[...][:, 0] > 0.5
    # a stopped slot is frozen compute: no boundary, no update, no scores
    bnd = jnp.where(stopped, 0.0, bnd_ref[...][:, 0])   # f32 0/1
    n0 = n_ref[...][:, 0]
    step0 = step_ref[...][:, 0]
    eta, lam = eta_ref[0], lam_ref[0]

    # score-then-update (single shared formula); the update is masked to
    # boundary tokens — a stop firing THIS step is rolled back below so the
    # stopping step leaves the fast weights untouched (Algorithm 2 order)
    s, w_upd, b_upd = P.score_then_update(w, b, zq, zk, 0.0, bnd, eta)

    bnd_b = bnd > 0.5
    ring = jnp.where(bnd_b[:, None],
                     jnp.concatenate([ring_ref[...][:, 1:], s[:, None]],
                                     axis=1),
                     ring_ref[...])
    n = n0 + bnd_b.astype(jnp.int32)
    win = ring.shape[1]
    denom = jnp.minimum(n, win).astype(jnp.float32)
    smoothed = jnp.where(n > 0, jnp.sum(ring, axis=1) / jnp.maximum(denom, 1.0),
                         0.0)
    # threshold test (Algorithm 2 line 11), after the burn-in
    stop_now = bnd_b & (smoothed >= lam) & (n > burn_in)
    stopped_new = stopped | stop_now
    step_new = jnp.where(stop_now & (step0 < 0), n, step0)

    s_out[...] = s[:, None]
    w_out[...] = jnp.where(stop_now[:, None], w, w_upd)
    b_out[...] = jnp.where(stop_now, b, b_upd)[:, None]
    ring_out[...] = ring
    n_out[...] = n[:, None]
    sm_out[...] = smoothed[:, None]
    stopped_out[...] = stopped_new.astype(jnp.float32)[:, None]
    step_out[...] = step_new[:, None]


class SpecProbeOut(NamedTuple):
    """A masked multi-token (speculative verify) probe step's outputs.

    The per-token sequences let the scheduler replay the chain on the host:
    token t of slot i emitted a score iff ``n_seq[i, t]`` exceeds the count
    before it, and ``smoothed_seq[i, t]`` is that score's rolling mean."""
    s: jnp.ndarray            # (B, T) raw probe score per verify token
    smoothed_seq: jnp.ndarray  # (B, T) rolling mean AFTER each token
    n_seq: jnp.ndarray        # (B, T) int32 scores emitted AFTER each token
    W: jnp.ndarray            # (B, f) final fast weights
    b: jnp.ndarray            # (B,)
    ring: jnp.ndarray         # (B, window)
    n_scores: jnp.ndarray     # (B,) int32
    smoothed: jnp.ndarray     # (B,)
    stopped: jnp.ndarray      # (B,) bool
    stop_step: jnp.ndarray    # (B,) int32


def _spec_kernel(zq_ref, zk_ref, bnd_ref, acc_ref, w_ref, b_ref, ring_ref,
                 n_ref, stopped_ref, step_ref, eta_ref, lam_ref,
                 s_out, sm_seq_out, n_seq_out, w_out, b_out, ring_out,
                 n_out, sm_out, stopped_out, step_out, *, burn_in: int,
                 t_total: int):
    """T chained serving-probe steps with a per-slot accepted-length mask.

    Token t of slot i participates iff ``t < accept[i]`` (and its boundary
    flag is set); each participating token runs EXACTLY the
    ``_serving_kernel`` per-token math, so the chain is bit-identical to
    ``accept[i]`` sequential one-token steps.  Rejected-draft tokens
    (t >= accept) leave every piece of state untouched."""
    eta, lam = eta_ref[0], lam_ref[0]
    acc = acc_ref[...][:, 0]                           # (B,) int32

    def body(t, carry):
        w, b, ring, n0, stopped_f, step0 = carry
        zq = zq_ref[pl.ds(t, 1), :, :][0]
        zk = zk_ref[pl.ds(t, 1), :, :][0]
        bnd_in = bnd_ref[pl.ds(t, 1), :][0]
        stopped = stopped_f > 0.5
        # the accepted-length mask composes with the frozen-stop mask: a
        # rejected draft position or a slot stopped earlier IN THIS CHAIN
        # contributes no boundary, no update, no score emission
        mask = (t < acc).astype(jnp.float32)
        bnd = jnp.where(stopped, 0.0, bnd_in * mask)
        s, w_upd, b_upd = P.score_then_update(w, b, zq, zk, 0.0, bnd, eta)
        bnd_b = bnd > 0.5
        ring_new = jnp.where(bnd_b[:, None],
                             jnp.concatenate([ring[:, 1:], s[:, None]],
                                             axis=1),
                             ring)
        n = n0 + bnd_b.astype(jnp.int32)
        win = ring_new.shape[1]
        denom = jnp.minimum(n, win).astype(jnp.float32)
        smoothed = jnp.where(n > 0,
                             jnp.sum(ring_new, axis=1)
                             / jnp.maximum(denom, 1.0), 0.0)
        stop_now = bnd_b & (smoothed >= lam) & (n > burn_in)
        stopped_new = stopped | stop_now
        step_new = jnp.where(stop_now & (step0 < 0), n, step0)
        s_out[pl.ds(t, 1), :] = s[None]
        sm_seq_out[pl.ds(t, 1), :] = smoothed[None]
        n_seq_out[pl.ds(t, 1), :] = n[None]
        return (jnp.where(stop_now[:, None], w, w_upd),
                jnp.where(stop_now, b, b_upd),
                ring_new, n, stopped_new.astype(jnp.float32), step_new)

    carry = (w_ref[...], b_ref[...][:, 0], ring_ref[...], n_ref[...][:, 0],
             stopped_ref[...][:, 0], step_ref[...][:, 0])
    w, b, ring, n, stopped_f, step = jax.lax.fori_loop(0, t_total, body,
                                                       carry)
    # the smoothed score is derived state (always recomputed from the
    # ring), so the post-chain recompute equals the last in-chain value
    denom = jnp.minimum(n, ring.shape[1]).astype(jnp.float32)
    smoothed = jnp.where(n > 0,
                         jnp.sum(ring, axis=1) / jnp.maximum(denom, 1.0),
                         0.0)
    w_out[...] = w
    b_out[...] = b[:, None]
    ring_out[...] = ring
    n_out[...] = n[:, None]
    sm_out[...] = smoothed[:, None]
    stopped_out[...] = stopped_f[:, None]
    step_out[...] = step[:, None]


@functools.partial(jax.jit, static_argnames=("burn_in", "interpret"))
def serving_probe_spec_step(zq, zk, boundary, accept, W, b, ring, n_scores,
                            stopped, stop_step, eta, lam, *, burn_in: int,
                            interpret: bool = True) -> SpecProbeOut:
    """Masked multi-token serving probe: T chained per-token steps in ONE
    kernel call, gated by a per-slot accepted length.

    zq/zk (B, T, f) per-token feature views (token t's features already
    reflect the hidden-state pooling up to t); boundary (B, T) the raw
    reasoning-step-crossing flags; accept (B,) int32 — slot i processes
    only its first ``accept[i]`` tokens (the verifier's accepted prefix),
    so the probe scores ONLY accepted tokens.  State args and semantics
    are exactly :func:`serving_probe_step`'s, chained: running this over
    (zq, zk) with accept[i] = a is bit-identical to ``a`` sequential
    one-token ``serving_probe_step`` calls for slot i (the spec-decode
    acceptance invariant; held to
    ``repro.kernels.ref.serving_probe_spec_step_ref``).
    A stop firing mid-chain freezes that slot for the remaining tokens —
    same frozen-slot rule as the one-token kernel, applied within the
    chain."""
    batch, t_total, f = zq.shape
    f32, i32 = jnp.float32, jnp.int32
    f_pad = f if interpret else -(-f // 128) * 128
    if f_pad != f:
        zq = jnp.pad(zq.astype(f32), ((0, 0), (0, 0), (0, f_pad - f)))
        zk = jnp.pad(zk.astype(f32), ((0, 0), (0, 0), (0, f_pad - f)))
        W = jnp.pad(W.astype(f32), ((0, 0), (0, f_pad - f)))
    win = ring.shape[1]
    col = lambda a, dt: a.reshape(batch, 1).astype(dt)
    kernel = functools.partial(_spec_kernel, burn_in=burn_in,
                               t_total=t_total)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)        # whole-array block
    s, sm_seq, n_seq, w_new, b_new, ring_new, n_new, sm_new, stopped_new, \
        step_new = pl.pallas_call(
            kernel,
            in_specs=[vmem] * 10 + [
                pl.BlockSpec(memory_space=pltpu.SMEM),          # eta
                pl.BlockSpec(memory_space=pltpu.SMEM)],         # lam
            out_specs=[vmem] * 10,
            out_shape=[
                jax.ShapeDtypeStruct((t_total, batch), f32),    # s
                jax.ShapeDtypeStruct((t_total, batch), f32),    # smoothed
                jax.ShapeDtypeStruct((t_total, batch), i32),    # n_scores
                jax.ShapeDtypeStruct((batch, f_pad), f32),
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, win), f32),
                jax.ShapeDtypeStruct((batch, 1), i32),
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, 1), i32),
            ],
            interpret=interpret,
        )(zq.astype(f32).transpose(1, 0, 2), zk.astype(f32).transpose(1, 0, 2),
          jnp.asarray(boundary, f32).T, col(accept, i32), W.astype(f32),
          col(b, f32), ring.astype(f32), col(n_scores, i32),
          col(stopped, f32), col(stop_step, i32),
          jnp.asarray(eta, f32).reshape(1), jnp.asarray(lam, f32).reshape(1))
    return SpecProbeOut(
        s=s.T, smoothed_seq=sm_seq.T, n_seq=n_seq.T,
        W=w_new[:, :f], b=b_new[:, 0], ring=ring_new,
        n_scores=n_new[:, 0], smoothed=sm_new[:, 0],
        stopped=stopped_new[:, 0] > 0.5, stop_step=step_new[:, 0])


@functools.partial(jax.jit, static_argnames=("burn_in", "interpret"))
def serving_probe_step(zq, zk, boundary, W, b, ring, n_scores,
                       stopped, stop_step, eta, lam, *, burn_in: int,
                       interpret: bool = True) -> ProbeStepOut:
    """One fused serving step for ALL engine slots (vector per-slot state).

    zq/zk (B, f) feature views of the running step embedding; boundary (B,)
    bool marks slots finishing a reasoning step this token; (W, b, ring,
    n_scores, stopped, stop_step) is the per-slot probe state (the smoothed
    score is derived output only — always recomputed from the ring).  Fuses
    score-then-update, rolling smoothing and the calibrated threshold test —
    the complete per-token deployed procedure of Algorithm 2.  Equivalent to
    the PR-1 jnp path (``repro.kernels.ref.serving_probe_step_ref``), which
    the parity suite holds it to.

    For compiled TPU mode the feature axis is zero-padded to a multiple of
    128 lanes (zero features never score or update, so padding is exact);
    interpret mode runs the block unpadded so CPU CI is bit-identical to the
    jnp oracle.
    """
    batch, f = zq.shape
    f32, i32 = jnp.float32, jnp.int32
    f_pad = f if interpret else -(-f // 128) * 128
    if f_pad != f:
        pad = ((0, 0), (0, f_pad - f))
        zq, zk, W = (jnp.pad(a.astype(f32), pad) for a in (zq, zk, W))
    win = ring.shape[1]
    col = lambda a, dt: a.reshape(batch, 1).astype(dt)
    kernel = functools.partial(_serving_kernel, burn_in=burn_in)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)        # whole-array block
    s, w_new, b_new, ring_new, n_new, sm_new, stopped_new, step_new = \
        pl.pallas_call(
            kernel,
            in_specs=[vmem] * 9 + [
                pl.BlockSpec(memory_space=pltpu.SMEM),          # eta
                pl.BlockSpec(memory_space=pltpu.SMEM)],         # lam
            out_specs=[vmem] * 8,
            out_shape=[
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, f_pad), f32),
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, win), f32),
                jax.ShapeDtypeStruct((batch, 1), i32),
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, 1), f32),
                jax.ShapeDtypeStruct((batch, 1), i32),
            ],
            interpret=interpret,
        )(zq.astype(f32), zk.astype(f32), col(boundary, f32), W.astype(f32),
          col(b, f32), ring.astype(f32), col(n_scores, i32),
          col(stopped, f32), col(stop_step, i32),
          jnp.asarray(eta, f32).reshape(1), jnp.asarray(lam, f32).reshape(1))
    return ProbeStepOut(
        s=s[:, 0], W=w_new[:, :f], b=b_new[:, 0], ring=ring_new,
        n_scores=n_new[:, 0], smoothed=sm_new[:, 0],
        stopped=stopped_new[:, 0] > 0.5, stop_step=step_new[:, 0])
