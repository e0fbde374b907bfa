"""Batched serving engine with ORCA risk-controlled early stopping.

The paper's technique is a first-class serving feature here: ``serve_step``
fuses one decode step of the base model with the ORCA probe — step-embedding
accumulation (mean-pooled hidden states over ``tokens_per_step`` tokens),
score-then-update fast-weight dynamics (Algorithm 2 lines 8-16), rolling
smoothing and the calibrated threshold test.

Two execution modes share the fused step:

* ``ServingEngine.serve`` — the legacy static batch: prefill once, run until
  every sequence stops.  Stopped sequences freeze in place and burn their
  slot as no-op compute.  Kept as the baseline (and a deprecation shim) for
  ``repro.serving.scheduler.OrcaScheduler``.
* ``ContinuousServingEngine`` — slot-level admit / release / step: each batch
  row ("slot") carries its own request at its own sequence position (vector
  ``pos``), its own per-slot prefill-injected KV cache and its own freshly
  reset probe fast-weight state.  The moment ORCA stops a sequence its slot
  is evicted and refilled from the waiting queue — calibrated early stopping
  becomes the capacity mechanism, not just shorter trajectories.

With ``chunk_tokens=N`` the continuous engine's fused step becomes the
UNIFIED token-budget step (Sarathi-style chunked prefill): prompt prefill is
no longer an admission-time, batch-1, per-prompt-length-compiled event but
schedulable work — each iteration decodes every slot AND processes up to N
prompt tokens of one mid-prefill request (``begin_prefill`` -> per-step
``ChunkWork`` -> ``finish_prefill``), all inside ONE fixed-shape executable.
Mid-prefill slots ride along as parked no-op rows: the probe's boundary gate
never touches their state and their no-op K/V write is masked (dense) or
NULL-paged (paged), so chunking changes *when* prefill work happens, never
*what* the probe sees.

This same ``serve_step`` is what the decode-shape dry-runs lower to the
production mesh: the deployed procedure (model + adaptation + stopping) is
exactly what gets calibrated, per the paper's validity argument.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import probe as P
from repro.core import stopping as S
from repro.core.probe import ProbeConfig
from repro.kernels import ops as K
from repro.kernels import ref as KR
from repro.kernels.decode_attention import decode_block_counts
from repro.kernels.ttt_probe import ProbeStepOut as KernelOut
from repro.kernels.ttt_probe import SpecProbeOut, serving_probe_step
from repro.models import attention as A
from repro.models.common import scoped
from repro.models.registry import Model
from repro.serving.config import ServeConfig
from repro.serving.kv_pool import NULL_BLOCK, blocks_needed, pad_row
from repro.serving.tracing import StepRecorder


class ProbeState(NamedTuple):
    """Vectorized fast-weight + smoothing state for a batch of sequences."""
    W: jnp.ndarray          # (B, f)
    b: jnp.ndarray          # (B,)
    hid_sum: jnp.ndarray    # (B, d_phi) accumulating the current step
    tok_count: jnp.ndarray  # (B,) tokens into the current step
    ring: jnp.ndarray       # (B, window) last raw scores
    n_scores: jnp.ndarray   # (B,) number of scores emitted
    smoothed: jnp.ndarray   # (B,) current smoothed score
    stopped: jnp.ndarray    # (B,) bool
    stop_step: jnp.ndarray  # (B,) reasoning step at which stopped (-1 active)


def init_probe_state(pc: ProbeConfig, theta, batch: int,
                     d_phi: int) -> ProbeState:
    f = pc.feat_dim
    return ProbeState(
        W=jnp.broadcast_to(theta["W0"], (batch, f)).astype(jnp.float32),
        b=jnp.broadcast_to(theta["b0"], (batch,)).astype(jnp.float32),
        hid_sum=jnp.zeros((batch, d_phi), jnp.float32),
        tok_count=jnp.zeros((batch,), jnp.int32),
        ring=jnp.zeros((batch, pc.smooth_window), jnp.float32),
        n_scores=jnp.zeros((batch,), jnp.int32),
        smoothed=jnp.zeros((batch,), jnp.float32),
        stopped=jnp.zeros((batch,), bool),
        stop_step=jnp.full((batch,), -1, jnp.int32),
    )


def reset_probe_slot(pc: ProbeConfig, theta, st: ProbeState, slot,
                     active: bool = True) -> ProbeState:
    """Reset ONE row of a batched ProbeState.

    ``active=True`` (admission): the slot gets a fresh single-request state —
    fast weights back to (W0, b0), empty smoothing ring, zero counters — so
    its score trajectory is identical to a fresh single-request run.
    ``active=False`` (eviction / empty slot): same reset but parked with
    ``stopped=True``, which makes the fused step treat the row as no-op
    compute (no fast-weight updates, token held constant).
    """
    one = init_probe_state(pc, theta, 1, st.hid_sum.shape[-1])
    if not active:
        one = one._replace(stopped=jnp.ones((1,), bool))
    slot = jnp.asarray(slot, jnp.int32)
    return ProbeState(*[
        jax.lax.dynamic_update_slice_in_dim(full, part.astype(full.dtype),
                                            slot, axis=0)
        for full, part in zip(st, one)])


def write_probe_slot(st: ProbeState, slot, rows: ProbeState) -> ProbeState:
    """Write ONE row of a batched ProbeState from saved per-leaf rows.

    The restore half of preemption: ``rows`` holds one batch-axis-free row
    per leaf (exactly what ``Spill`` captured at preempt time), written back
    with the same dynamic-update-slice the reset path uses — so a restored
    slot's probe state is bit-identical to the moment it was spilled.
    """
    slot = jnp.asarray(slot, jnp.int32)
    return ProbeState(*[
        jax.lax.dynamic_update_slice_in_dim(
            full, part[None].astype(full.dtype), slot, axis=0)
        for full, part in zip(st, rows)])


def inject_prefill(model: Model, params, state, batch_one: Dict[str, jnp.ndarray],
                   slot, cache_len: int):
    """Prefill ONE request (batch 1) and write its decode state into batch
    row ``slot`` of a running engine state.

    Every decode-state leaf across the model zoo is (L, B, ...) — stacked
    layers first, batch second — so the injection is a uniform
    dynamic-update-slice on axis 1.  Stale KV from the slot's previous
    occupant beyond the new prompt is never readable: the per-slot ``valid``
    mask only exposes [0, pos) and each position is overwritten before pos
    reaches it.
    """
    sub, _, _ = model.prefill(model.cfg, params, batch_one, cache_len)
    slot = jnp.asarray(slot, jnp.int32)
    return jax.tree.map(
        lambda full, one: jax.lax.dynamic_update_slice_in_dim(
            full, one.astype(full.dtype), slot, axis=1),
        state, sub)


class ChunkSeg(NamedTuple):
    """One request's contribution to a (possibly packed) prefill chunk:
    prompt positions [start, start + length) of the request resident in
    batch row ``slot``."""
    slot: int
    tokens: np.ndarray               # (S,) the FULL prompt token ids
    start: int
    length: int
    row: Optional[np.ndarray] = None  # paged: the request's physical pages


class ChunkWork(NamedTuple):
    """Host-side descriptor of one fused prefill chunk for the unified
    step: up to ``engine.max_pack`` segments of DIFFERENT requests packed
    back to back (Sarathi-style piggybacking — the tail of one prompt
    rides with the head of the next), block-diagonally isolated on device.
    A single-segment chunk is exactly the unpacked PR-4 chunk."""
    segs: Tuple[ChunkSeg, ...]

    @classmethod
    def single(cls, slot: int, tokens: np.ndarray, start: int, length: int,
               row: Optional[np.ndarray] = None) -> "ChunkWork":
        """One-request chunk (the unpacked composer shape)."""
        return cls(segs=(ChunkSeg(slot, tokens, start, length, row),))

    @property
    def total_tokens(self) -> int:
        return sum(s.length for s in self.segs)


def chunk_supported(model: Model, inputs: Dict[str, jnp.ndarray]) -> bool:
    """A prompt can be prefilled in chunks iff the family exposes
    ``prefill_chunk`` and the prompt is pure text with no hidden prefix —
    vlm patches, learned meta tokens and audio frontends prefill their
    non-token prefix in one shot, so those requests keep the admission-time
    ``model.prefill`` path."""
    mcfg = model.cfg
    return (model.prefill_chunk is not None
            and set(inputs) == {"tokens"}
            and mcfg.arch_type != "audio"
            and not (getattr(mcfg, "n_meta_tokens", 0) or 0))


@functools.lru_cache(maxsize=None)
def _chunk_prefill_fn(prefill_chunk, mcfg):
    """One jitted chunk executable per (family, config) — repeated
    ``serve()``/``extract_trajectories`` calls must not recompile, the same
    contract as the engines' step functions."""
    return jax.jit(functools.partial(prefill_chunk, mcfg),
                   donate_argnums=2)     # the state is rebuilt in place


def chunked_prefill(model: Model, params, batch: Dict[str, jnp.ndarray],
                    cache_len: int, *, chunk_tokens: Optional[int] = None):
    """Build a decode state for ``batch`` — the ONE prompt-prefill helper
    behind ``ServingEngine.serve``, ``extract_trajectories`` and the
    offline shims.

    ``chunk_tokens=None`` (or unsupported inputs) runs one full-prompt
    ``model.prefill`` — the legacy path, bit-identical to before.
    Otherwise the prompt runs through fixed-shape ``chunk_tokens``-wide
    ``model.prefill_chunk`` calls with traced start/length, so ONE compiled
    executable covers every prompt length (the unbounded per-length compile
    cache was §ISSUE-4's satellite fix).  Returns the decode state."""
    mcfg = model.cfg
    if not chunk_tokens or not chunk_supported(model, batch):
        state, _, _ = model.prefill(mcfg, params, batch, cache_len)
        return state
    tokens = np.asarray(batch["tokens"])
    b, s = tokens.shape
    c = int(chunk_tokens)
    state = model.init_decode_state(b, cache_len)
    rows = jnp.arange(b, dtype=jnp.int32)
    fn = _chunk_prefill_fn(model.prefill_chunk, mcfg)
    for start in range(0, s, c):
        n = min(c, s - start)
        buf = np.zeros((b, c), np.int32)
        buf[:, :n] = tokens[:, start:start + n]
        state = fn(params, jnp.asarray(buf), state, rows,
                   jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32))
    return state


@scoped("orca/probe")
def probe_update(pc: ProbeConfig, theta, st: ProbeState, hidden: jnp.ndarray,
                 lam: float, tokens_per_step: int, burn_in: int, *,
                 probe_impl: str = "kernel",
                 interpret: Optional[bool] = None) -> ProbeState:
    """Accumulate one token's hidden state; at step boundaries run the fused
    score-then-update + smoothing + threshold step.

    The probe math itself lives in ONE place — the Pallas kernel module
    (``repro.kernels.ttt_probe.serving_probe_step``) — so the served
    procedure is the same code the calibration path exercises.
    ``probe_impl="ref"`` swaps in the pre-refactor jnp oracle
    (``repro.kernels.ref.serving_probe_step_ref``) for parity tests and the
    before/after throughput benchmark.
    """
    hid_sum = st.hid_sum + hidden.astype(jnp.float32)
    tok_count = st.tok_count + 1
    boundary = (tok_count >= tokens_per_step) & ~st.stopped
    eta = P.inner_lr(pc, theta)
    lam = jnp.asarray(lam, jnp.float32)

    def _features():
        # step-embedding pooling: running mean of the step's hidden states
        phi = hid_sum / jnp.maximum(tok_count, 1)[:, None]
        return P.features(pc, theta, phi)

    if probe_impl == "kernel":
        interp = K.resolve_interpret(interpret)

        def _probe(_):
            zq, zk = _features()
            return serving_probe_step(zq, zk, boundary, st.W, st.b, st.ring,
                                      st.n_scores, st.stopped,
                                      st.stop_step, eta, lam,
                                      burn_in=int(burn_in), interpret=interp)

        def _skip(_):
            return KernelOut(jnp.zeros_like(st.b), st.W, st.b, st.ring,
                             st.n_scores, st.smoothed, st.stopped,
                             st.stop_step)

        # mid-step tokens (and fully-frozen batches) provably don't change
        # probe state — only pooling runs, the kernel dispatch is skipped
        out = jax.lax.cond(jnp.any(boundary), _probe, _skip, None)
    elif probe_impl == "ref":
        # the PR-1 path, faithfully: full probe math on every token
        zq, zk = _features()
        out = KR.serving_probe_step_ref(zq, zk, boundary, st.W, st.b, st.ring,
                                        st.n_scores, st.stopped,
                                        st.stop_step, eta, lam,
                                        burn_in=int(burn_in))
    else:
        raise ValueError(f"unknown probe_impl {probe_impl!r} "
                         "(expected 'kernel' or 'ref')")
    # reset accumulators at boundaries
    hid_sum = jnp.where(boundary[:, None], 0.0, hid_sum)
    tok_count = jnp.where(boundary, 0, tok_count)
    return ProbeState(out.W, out.b, hid_sum, tok_count, out.ring,
                      out.n_scores, out.smoothed, out.stopped, out.stop_step)


@scoped("orca/probe")
def probe_update_spec(pc: ProbeConfig, theta, st: ProbeState,
                      hidden_seq: jnp.ndarray, accept: jnp.ndarray,
                      lam: float, tokens_per_step: int, burn_in: int, *,
                      probe_impl: str = "kernel",
                      interpret: Optional[bool] = None
                      ) -> Tuple[ProbeState, jnp.ndarray, jnp.ndarray]:
    """Multi-token probe advance for speculative decode: consume the T
    verify positions' hidden states of every slot, but let only the first
    ``accept[i]`` tokens of slot i touch probe state — the chain is
    bit-identical to ``accept[i]`` sequential ``probe_update`` calls.

    The per-token pooling (hid_sum / tok_count accumulate-and-reset) is
    unrolled here at trace time — T is the static ``spec_tokens`` knob —
    producing the (B, T) feature/boundary sequences; the stateful
    score-then-update / smoothing / threshold chain then runs in ONE fused
    masked kernel (``serving_probe_spec_step``), dispatched under the same
    any-boundary ``lax.cond`` gate as the one-token path.  Tokens past an
    IN-CHAIN stop are suppressed inside the kernel by its carried stopped
    flag (the scheduler truncates collection at the stop and releases the
    slot, so the pooled accumulators' post-stop drift is unobservable).

    Returns (ProbeState, smoothed_seq (B, T), n_seq (B, T)) — per-token
    smoothed score and cumulative score count for multi-score collection:
    token t of slot i emitted a score iff n_seq[i, t] exceeds the count
    before it.
    """
    t_total = hidden_seq.shape[1]
    eta = P.inner_lr(pc, theta)
    lam_ = jnp.asarray(lam, jnp.float32)
    accept = jnp.asarray(accept, jnp.int32)
    hid_sum, tok_count = st.hid_sum, st.tok_count
    zqs, zks, bnds = [], [], []
    for t in range(t_total):
        m = t < accept
        hid_sum = jnp.where(m[:, None],
                            hid_sum + hidden_seq[:, t].astype(jnp.float32),
                            hid_sum)
        tok_count = jnp.where(m, tok_count + 1, tok_count)
        bnd = m & (tok_count >= tokens_per_step)
        phi = hid_sum / jnp.maximum(tok_count, 1)[:, None]
        zq, zk = P.features(pc, theta, phi)
        zqs.append(zq)
        zks.append(zk)
        bnds.append(bnd)
        hid_sum = jnp.where(bnd[:, None], 0.0, hid_sum)
        tok_count = jnp.where(bnd, 0, tok_count)
    zq = jnp.stack(zqs, axis=1)
    zk = jnp.stack(zks, axis=1)
    boundary = jnp.stack(bnds, axis=1)
    if probe_impl == "kernel":
        interp = K.resolve_interpret(interpret)

        def _probe(_):
            return K.serving_probe_spec_step(
                zq, zk, boundary, accept, st.W, st.b, st.ring, st.n_scores,
                st.stopped, st.stop_step, eta, lam_, burn_in=int(burn_in),
                interpret=interp)

        def _skip(_):
            rep = lambda a: jnp.repeat(a[:, None], t_total, axis=1)
            return SpecProbeOut(s=rep(jnp.zeros_like(st.b)),
                                smoothed_seq=rep(st.smoothed),
                                n_seq=rep(st.n_scores), W=st.W, b=st.b,
                                ring=st.ring, n_scores=st.n_scores,
                                smoothed=st.smoothed, stopped=st.stopped,
                                stop_step=st.stop_step)

        out = jax.lax.cond(jnp.any(boundary), _probe, _skip, None)
    elif probe_impl == "ref":
        out = KR.serving_probe_spec_step_ref(
            zq, zk, boundary, accept, st.W, st.b, st.ring, st.n_scores,
            st.stopped, st.stop_step, eta, lam_, burn_in=int(burn_in))
    else:
        raise ValueError(f"unknown probe_impl {probe_impl!r} "
                         "(expected 'kernel' or 'ref')")
    new_st = ProbeState(out.W, out.b, hid_sum, tok_count, out.ring,
                        out.n_scores, out.smoothed, out.stopped,
                        out.stop_step)
    return new_st, out.smoothed_seq, out.n_seq


@scoped("orca/chunk_prefill")
def _chunk_prefill(model: Model, params, chunk: Dict[str, jnp.ndarray],
                   cache):
    """The unified step's packed prefill chunk, when it carries one; an
    inactive chunk hands the cache back untouched."""
    def run_chunk(cache):
        return model.prefill_packed(model.cfg, params, chunk["tokens"], cache,
                                    chunk["seg"], chunk["slots"],
                                    chunk["starts"], chunk["lengths"],
                                    chunk.get("rows"))

    return jax.lax.cond(chunk["active"], run_chunk, lambda c: c, cache)


# The unified ServeConfig (repro.serving.config) replaced the step-level
# dataclass that lived here through PR 7; re-exported so every existing
# ``from repro.serving.engine import ServeConfig`` keeps working.  The
# engines below read only the fused-step fields (tokens_per_step,
# max_new_tokens, lam, burn_in, greedy).


def make_serve_step(model: Model, pc: ProbeConfig, cfg: ServeConfig,
                    window: Optional[int] = None, *,
                    probe_impl: str = "kernel",
                    interpret: Optional[bool] = None,
                    chunk_tokens: int = 0,
                    mask_stopped_writes: bool = False,
                    spec_tokens: int = 0,
                    spec_tree: Optional[Tuple[int, int]] = None):
    """Build the fused decode+ORCA step:
    (params, theta, token, cache, pos, probe_state) ->
    (next_token, cache, probe_state).

    One jitted step fuses decode attention, step-embedding pooling, the
    Pallas probe score-then-update, smoothing and the threshold test for all
    slots; engines jit it with the KV cache and probe state donated so XLA
    updates them in place.

    With ``chunk_tokens > 0`` the step becomes the UNIFIED token-budget
    step (Sarathi-style chunked prefill): it takes a 7th argument ``chunk``
    — a fixed-shape descriptor of up to ``chunk_tokens`` pending prompt
    tokens belonging to up to ``max_pack`` mid-prefill requests (a PACKED
    chunk: the tail of one prompt piggybacked with the head of the next,
    block-diagonally isolated) — and runs ``model.prefill_packed`` for
    them before the decode of every slot, all in one executable whatever
    the prompt lengths or packing.  A single-segment chunk is the unpacked
    PR-4 path; segment count, lengths and positions are all traced data,
    so packed and unpacked serving share ONE executable.  Mid-prefill
    slots ride the decode as parked no-op rows (probe ``stopped=True`` —
    the boundary gate already keeps the probe kernel off them) and, with
    ``mask_stopped_writes``, their dense no-op K/V write is dropped so it
    can never clobber chunk-written prompt K/V (paged parked rows already
    write the NULL page).

    With ``spec_tokens = k > 0`` the decode half becomes DRAFT-VERIFY
    speculative decode riding the same packed-chunk machinery: the step
    takes a trailing ``spec`` descriptor — ``{"lens": (n_slots,)}``, each
    RUNNING slot's verify-block length in [0, k], drawn by the scheduler
    from the same token budget the prefill share uses — drafts k-1
    continuations per slot via ``model.draft``, runs one packed verify
    chunk (``model.verify_packed`` — ``prefill_packed`` with the LM head
    kept) whose segment r is slot r's [current token, drafts...] at
    positions pos..pos+len-1, computes each slot's accepted prefix, and
    advances that slot by ``gen`` in [1, len] committed tokens per step
    (0 for parked rows).  Rejected K/V writes need no undo: validity masks
    expose only [0, pos) and the next verify block overwrites them before
    ``pos`` reaches them.  The probe consumes ONLY accepted tokens through
    ``probe_update_spec``.  ``lens`` is traced data, so every draft-length
    mix shares the ONE executable; the step returns a 4th element —
    {"gen", "seq", "seq_scores", "seq_n"} for multi-token collection.
    The ``spec`` descriptor additionally carries host drafts —
    ``drafts`` (n_slots, k-1) plus a per-slot ``have`` mask — supplied by
    the scheduler's shared draft cache; slots with ``have=False`` fall
    back to ``model.draft`` (all-False is bit-identical to PR 9).

    With ``spec_tree = (W, D)`` the verify segment generalizes from a
    chain to a token TREE: W independent draft chains of depth D hang off
    the root (BFS comb layout — node ``1 + j*W + b`` is branch b at depth
    j+1, parent ``i - W`` or the root), so ``k = 1 + W*D`` nodes per slot
    claim the same token budget.  The packed forward swaps block-causal
    for the per-token ANCESTOR mask (``model.verify_tree``), K/V writes
    are DEFERRED (same-depth siblings share a position), acceptance picks
    the longest root-to-leaf path whose every node matches the model's
    output after its parent — a linear chain by construction, so the SAME
    masked probe kernel consumes it and stops stay byte-identical to
    one-token decode — and only that path's K/V lands via
    ``model.commit_kv``.  ``drafts`` becomes (n_slots, W, D); per-slot
    ``lens`` in [0, k] count-truncates the tree breadth-first (prefix-
    closed: a truncated tree is still a tree).  ``spec_tree`` overrides
    ``spec_tokens``; W = 1 reproduces the linear path bit-for-bit."""
    mcfg = model.cfg

    def decode_probe(params, theta, token, cache, pos, st: ProbeState):
        write_mask = ~st.stopped if mask_stopped_writes else None
        logits, hidden, cache = model.decode_step(mcfg, params, token, cache,
                                                  pos, window=window,
                                                  write_mask=write_mask)
        prev_stopped = st.stopped
        st = probe_update(pc, theta, st, hidden, cfg.lam,
                          cfg.tokens_per_step, cfg.burn_in,
                          probe_impl=probe_impl, interpret=interpret)
        with jax.named_scope("orca/lm_head"):
            nxt = jnp.argmax(logits[:, :mcfg.vocab_size],
                             axis=-1).astype(jnp.int32)
            # the step on which the stop FIRES still emits its genuinely
            # decoded token; only already-frozen sequences repeat (no-op
            # compute slot)
            nxt = jnp.where(prev_stopped, token, nxt)
        return nxt, cache, st

    if spec_tree is not None:
        tw, td = int(spec_tree[0]), int(spec_tree[1])
        assert tw >= 1 and td >= 1, spec_tree
        assert model.supports_tree, \
            f"{mcfg.name}: no tree speculative decode for this family"
        assert window is None, "speculative decode has no SWA ring buffer"
        kk = 1 + tw * td
        # static BFS comb tables: node 0 = root; node 1 + j*W + b = branch
        # b at depth j+1, parent one level up on the SAME branch (the root
        # for j = 0).  Index order == BFS order, so per-slot count
        # truncation by ``lens`` keeps parents (prefix-closed).
        par_np = np.zeros((kk,), np.int32)
        dep_np = np.zeros((kk,), np.int32)
        for j in range(td):
            for b_ in range(tw):
                i = 1 + j * tw + b_
                dep_np[i] = j + 1
                par_np[i] = 0 if j == 0 else i - tw
        par_l = jnp.asarray(par_np)
        dep_l = jnp.asarray(dep_np)

        @scoped("orca/verify")
        def tree_verify(params, theta, token, cache, pos, st: ProbeState,
                        lens, drafts_in, have):
            bsz = token.shape[0]
            c = bsz * kk
            lens = jnp.where(st.stopped, 0, jnp.asarray(lens, jnp.int32))
            pos = jnp.asarray(pos, jnp.int32)
            # drafts: shared-cache hits from the host where available, the
            # model family's own tree drafter elsewhere — traced data, one
            # executable across every hit/miss mix
            dev = model.draft_tree(mcfg, params, cache, token, pos, tw, td)
            drafts = jnp.where(jnp.asarray(have, bool)[:, None, None],
                               jnp.asarray(drafts_in, jnp.int32),
                               jnp.asarray(dev, jnp.int32))
            # BFS layout: blk[:, 1 + j*W + b] = drafts[:, b, j]
            blk = jnp.concatenate(
                [token[:, None],
                 drafts.transpose(0, 2, 1).reshape(bsz, tw * td)], axis=1)
            offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(lens)[:-1]])
            jj = jnp.arange(kk, dtype=jnp.int32)[None, :]
            dst = jnp.where(jj < lens[:, None], offs[:, None] + jj, c)
            flat = dst.reshape(-1)

            def scat(src):
                return jnp.zeros((c,), jnp.int32).at[flat].set(
                    src.reshape(-1), mode="drop")

            toks_c = scat(blk)
            seg_c = scat(jnp.broadcast_to(
                jnp.arange(bsz, dtype=jnp.int32)[:, None], (bsz, kk)))
            dep_c = scat(jnp.broadcast_to(dep_l[None, :], (bsz, kk)))
            # global parent pointers: the root points at ITSELF (par 0 ->
            # offs + 0); dropped tail tokens default to 0, masked by length
            anc_c = scat(offs[:, None] + par_l[None, :])
            rows_arg = cache["block_tables"] if "block_tables" in cache \
                else None
            logits, hidden, ks, vs = model.verify_tree(
                mcfg, params, toks_c, cache, seg_c,
                jnp.arange(bsz, dtype=jnp.int32), pos, lens, dep_c, anc_c,
                rows_arg)
            out_c = jnp.argmax(logits[:, :mcfg.vocab_size],
                               axis=-1).astype(jnp.int32)
            gdx = jnp.clip(dst, 0, c - 1)
            out_blk = out_c[gdx]                          # (B, kk)
            # per-node acceptance, rooted: node i survives iff its parent
            # did AND it equals the model's output after its parent —
            # unrolled over the static kk at trace time
            accs = [lens > 0]
            for i in range(1, kk):
                p = int(par_np[i])
                accs.append(accs[p] & (i < lens)
                            & (blk[:, i] == out_blk[:, p]))
            acc_m = jnp.stack(accs, axis=1)               # (B, kk) bool
            plen = jnp.where(acc_m, dep_l[None, :] + 1, 0)
            g = jnp.max(plen, axis=1)                     # path len incl root
            best = jnp.argmax(plen, axis=1).astype(jnp.int32)
            # root-first path via the ancestor walk from ``best``: entry d
            # is best's ancestor at distance dep[best] - d (clamped — the
            # tail repeats ``best``, masked by d < g everywhere below)
            curs = [best]
            for _ in range(td):
                curs.append(par_l[curs[-1]])
            curs = jnp.stack(curs, axis=1)                # (B, D+1)
            dd = jnp.arange(td + 1, dtype=jnp.int32)
            walk = jnp.clip(dep_l[best][:, None] - dd[None, :], 0, td)
            path = jnp.take_along_axis(curs, walk, axis=1)
            pdx = jnp.clip(offs[:, None] + path, 0, c - 1)
            seq = out_c[pdx]                              # (B, D+1)
            hid_path = hidden[pdx]                        # (B, D+1, d)
            # the accepted path IS a linear chain: the PR-9 masked spec
            # probe consumes it unchanged, so stops are byte-identical to
            # sequential one-token decode
            st, sm_seq, n_seq = probe_update_spec(
                pc, theta, st, hid_path, g, cfg.lam, cfg.tokens_per_step,
                cfg.burn_in, probe_impl=probe_impl, interpret=interpret)
            # commit ONLY the accepted path's deferred K/V — one node per
            # depth, unique (lane, position) targets, race-free scatter
            on_path = jnp.any(
                (path[:, :, None] == jnp.arange(kk)[None, None, :])
                & (dd[None, :, None] < g[:, None, None]), axis=1)
            valid_c = jnp.zeros((c,), bool).at[flat].set(
                on_path.reshape(-1), mode="drop")
            pos_c = scat(pos[:, None] + dep_l[None, :])
            cache = model.commit_kv(mcfg, cache, ks, vs,
                                    jnp.arange(bsz, dtype=jnp.int32),
                                    seg_c, pos_c, valid_c, rows_arg)
            nxt = jnp.where(
                g > 0,
                jnp.take_along_axis(
                    seq, jnp.clip(g - 1, 0, td)[:, None], axis=1)[:, 0],
                token)
            extras = {"gen": g, "seq": seq, "seq_scores": sm_seq,
                      "seq_n": n_seq}
            return nxt, cache, st, extras

        if not chunk_tokens:
            @scoped("orca/step")
            def tree_step(params, theta, token, cache, pos, st: ProbeState,
                          spec: Dict[str, jnp.ndarray]):
                return tree_verify(params, theta, token, cache, pos, st,
                                   spec["lens"], spec["drafts"],
                                   spec["have"])
            return tree_step

        @scoped("orca/step")
        def unified_tree_step(params, theta, token, cache, pos,
                              st: ProbeState, chunk: Dict[str, jnp.ndarray],
                              spec: Dict[str, jnp.ndarray]):
            cache = _chunk_prefill(model, params, chunk, cache)
            return tree_verify(params, theta, token, cache, pos, st,
                               spec["lens"], spec["drafts"], spec["have"])

        return unified_tree_step

    if spec_tokens:
        assert spec_tokens >= 2, "spec_tokens < 2 is one-token decode"
        assert model.supports_spec, \
            f"{mcfg.name}: no speculative decode for this family"
        assert window is None, "speculative decode has no SWA ring buffer"
        kk = int(spec_tokens)

        @scoped("orca/verify")
        def spec_verify(params, theta, token, cache, pos,
                        st: ProbeState, lens, drafts_in, have):
            bsz = token.shape[0]
            c = bsz * kk
            # parked rows contribute nothing: no writes (the one-token
            # path's mask_stopped_writes contract), no probe, no advance
            lens = jnp.where(st.stopped, 0, jnp.asarray(lens, jnp.int32))
            pos = jnp.asarray(pos, jnp.int32)
            # host drafts (shared draft cache) where ``have``, the model
            # family's own drafter elsewhere — all-False is bit-identical
            # to the pre-cache path
            dev = model.draft(mcfg, params, cache, token, pos, kk)
            drafts = jnp.where(jnp.asarray(have, bool)[:, None],
                               jnp.asarray(drafts_in, jnp.int32),
                               jnp.asarray(dev, jnp.int32))
            blk = jnp.concatenate([token[:, None], drafts], axis=1)
            # segments laid out contiguously in slot order (the packed-chunk
            # layout contract); slots past their length scatter to the
            # dropped tail and tail tokens keep seg 0, invalid by length
            offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(lens)[:-1]])
            jj = jnp.arange(kk, dtype=jnp.int32)[None, :]
            dst = jnp.where(jj < lens[:, None], offs[:, None] + jj, c)
            flat = dst.reshape(-1)
            toks_c = jnp.zeros((c,), jnp.int32).at[flat].set(
                blk.reshape(-1), mode="drop")
            seg_src = jnp.broadcast_to(
                jnp.arange(bsz, dtype=jnp.int32)[:, None], (bsz, kk))
            seg_c = jnp.zeros((c,), jnp.int32).at[flat].set(
                seg_src.reshape(-1), mode="drop")
            rows_arg = cache["block_tables"] if "block_tables" in cache \
                else None
            logits, hidden, cache = model.verify_packed(
                mcfg, params, toks_c, cache, seg_c,
                jnp.arange(bsz, dtype=jnp.int32), pos, lens, rows_arg)
            out_c = jnp.argmax(logits[:, :mcfg.vocab_size],
                               axis=-1).astype(jnp.int32)
            gdx = jnp.clip(dst, 0, c - 1)
            out_blk = out_c[gdx]                          # (B, kk)
            hid_blk = hidden[gdx]                         # (B, kk, d)
            # accepted prefix: draft j+1 survives iff it equals the model's
            # output after consuming draft j; the first miss is replaced by
            # the model's own token, so gen = accepted drafts + 1
            ok = (blk[:, 1:] == out_blk[:, :-1]) \
                & (jj[:, :kk - 1] + 1 < lens[:, None])
            n_acc = jnp.sum(jnp.cumprod(ok.astype(jnp.int32), axis=1), axis=1)
            g = jnp.where(lens > 0, n_acc + 1, 0)
            st, sm_seq, n_seq = probe_update_spec(
                pc, theta, st, hid_blk, g, cfg.lam, cfg.tokens_per_step,
                cfg.burn_in, probe_impl=probe_impl, interpret=interpret)
            nxt = jnp.where(
                g > 0,
                jnp.take_along_axis(
                    out_blk, jnp.clip(g - 1, 0, kk - 1)[:, None],
                    axis=1)[:, 0],
                token)
            extras = {"gen": g, "seq": out_blk, "seq_scores": sm_seq,
                      "seq_n": n_seq}
            return nxt, cache, st, extras

        if not chunk_tokens:
            @scoped("orca/step")
            def spec_step(params, theta, token, cache, pos, st: ProbeState,
                          spec: Dict[str, jnp.ndarray]):
                return spec_verify(params, theta, token, cache, pos, st,
                                   spec["lens"], spec["drafts"],
                                   spec["have"])
            return spec_step

        @scoped("orca/step")
        def unified_spec_step(params, theta, token, cache, pos,
                              st: ProbeState, chunk: Dict[str, jnp.ndarray],
                              spec: Dict[str, jnp.ndarray]):
            cache = _chunk_prefill(model, params, chunk, cache)
            return spec_verify(params, theta, token, cache, pos, st,
                               spec["lens"], spec["drafts"], spec["have"])

        return unified_spec_step

    if not chunk_tokens:
        @scoped("orca/step")
        def serve_step(params, theta, token, cache, pos, st: ProbeState):
            return decode_probe(params, theta, token, cache, pos, st)
        return serve_step

    assert model.prefill_packed is not None, \
        f"{mcfg.name}: no packed chunked prefill for this family"

    @scoped("orca/step")
    def unified_step(params, theta, token, cache, pos, st: ProbeState,
                     chunk: Dict[str, jnp.ndarray]):
        # prefill work first, decode after: order is immaterial (the chunk
        # slot is parked, other slots never read its lane) but keeps the
        # trace linear
        cache = _chunk_prefill(model, params, chunk, cache)
        return decode_probe(params, theta, token, cache, pos, st)

    return unified_step


# serve_step arg indices donated by the engines' jitted hot loop: the KV
# cache (3) and the probe state (5) are consumed and re-emitted every step
_SERVE_STEP_DONATE = (3, 5)


@dataclasses.dataclass
class ServeResult:
    tokens: np.ndarray        # (B, n_decode_iters) tokens actually decoded
    stop_step: np.ndarray     # (B,) reasoning step at stop (-1 = budget)
    steps_run: np.ndarray     # (B,) reasoning steps actually executed
    savings: float
    scores: np.ndarray        # (B, n_steps) smoothed score at each step


class ServingEngine:
    """Minimal batched server: prefill once, loop the fused serve_step.

    DEPRECATED as a serving path: stopped sequences keep occupying their
    batch slot as no-op compute until the slowest sequence finishes.  Use
    ``repro.serving.OrcaScheduler`` (continuous batching with ORCA-stop
    eviction) for throughput; this class remains as the static-batch
    baseline it is benchmarked against (``benchmarks/serving_throughput``)
    and for callers that bring a pre-built batch."""

    def __init__(self, model: Model, params, pc: ProbeConfig, theta,
                 cfg: ServeConfig, *, probe_impl: str = "kernel",
                 interpret: Optional[bool] = None,
                 chunk_tokens: Optional[int] = None):
        self.model, self.params, self.pc, self.theta, self.cfg = \
            model, params, pc, theta, cfg
        # prompt prefill routes through the shared chunked helper: None ->
        # one full model.prefill call (legacy, bit-identical); an int ->
        # fixed-shape chunks, one executable across prompt lengths
        self.chunk_tokens = chunk_tokens
        # one jitted step for the engine's lifetime: repeated serve() calls
        # (e.g. group loops in the throughput benchmark) must not recompile
        self._step_fn = jax.jit(
            make_serve_step(model, pc, cfg, probe_impl=probe_impl,
                            interpret=interpret),
            donate_argnums=_SERVE_STEP_DONATE)

    def serve(self, batch: Dict[str, jnp.ndarray], prompt_len: int,
              cache_len: Optional[int] = None) -> ServeResult:
        warnings.warn(
            "ServingEngine.serve is deprecated as a serving path (stopped "
            "sequences occupy their slot as no-op compute until the slowest "
            "finishes); serve through repro.serving.OrcaScheduler / "
            "repro.api.engine for continuous batching — this class remains "
            "only as the static-batch baseline",
            DeprecationWarning, stacklevel=2)
        model, cfg = self.model, self.cfg
        mcfg = model.cfg
        B = next(iter(batch.values())).shape[0]
        pre = prefix_len(mcfg, batch, prompt_len)
        cache_len = cache_len or (pre + cfg.max_new_tokens)
        state = chunked_prefill(model, self.params, batch, cache_len,
                                chunk_tokens=self.chunk_tokens)
        step_fn = self._step_fn
        st = init_probe_state(self.pc, self.theta, B, mcfg.d_model)
        token = jnp.zeros((B,), jnp.int32)
        toks, scores, phis = [], [], []
        pos0 = pre if mcfg.arch_type != "audio" else 0
        # host-side watermark (st's buffers are donated to the next step)
        last_max_n = 0
        for i in range(cfg.max_new_tokens):
            pos = jnp.asarray(pos0 + i, jnp.int32)
            token, state, st = step_fn(self.params, self.theta, token, state,
                                       pos, st)
            toks.append(np.asarray(token))
            max_n = int(np.asarray(jnp.max(st.n_scores)))
            if max_n > last_max_n:
                scores.append(np.asarray(st.smoothed))
                last_max_n = max_n
            if bool(np.asarray(jnp.all(st.stopped))):
                break
        stop_step = np.asarray(st.stop_step)
        steps_run = np.where(stop_step >= 0, stop_step,
                             np.asarray(st.n_scores))
        total = max(cfg.max_new_tokens // cfg.tokens_per_step, 1)
        savings = float(np.mean(S.step_savings(steps_run, total)))
        return ServeResult(
            tokens=np.stack(toks, axis=1) if toks else np.zeros((B, 0), np.int32),
            stop_step=stop_step, steps_run=steps_run, savings=savings,
            scores=np.stack(scores, axis=1) if scores else np.zeros((B, 0)))


@dataclasses.dataclass
class StaticQueueResult:
    """Aggregate of serving a request queue in fixed static-batch groups."""
    stop_step: np.ndarray        # (N,) per request
    steps_run: np.ndarray        # (N,)
    scores: List[np.ndarray]     # per request, (n_steps,)
    engine_steps: int            # total fused decode steps across groups
    active_slot_steps: int       # slot-steps before each sequence stopped
    total_slot_steps: int        # engine_steps x group width
    wall_time_s: float


def serve_queue_static(engine: ServingEngine, batch: Dict[str, jnp.ndarray],
                       prompt_len: int, n_slots: int) -> StaticQueueResult:
    """Serve a queue in fixed groups of ``n_slots`` through the deprecated
    static-batch path (no eviction: each group runs until its slowest
    member finishes).  The baseline both ``launch/serve.py`` and
    ``benchmarks/serving_throughput.py`` compare the scheduler against."""
    import time
    n = next(iter(batch.values())).shape[0]
    stop_steps, steps_run, scores = [], [], []
    engine_steps = active = total = 0
    t0 = time.perf_counter()
    for lo in range(0, n, n_slots):
        group = {k: v[lo:lo + n_slots] for k, v in batch.items()}
        with warnings.catch_warnings():
            # this helper IS the sanctioned baseline use of the deprecated
            # path — don't spam its own deprecation per group
            warnings.simplefilter("ignore", DeprecationWarning)
            res = engine.serve(group, prompt_len=prompt_len)
        iters = res.tokens.shape[1]
        b = group["tokens"].shape[0] if "tokens" in group else \
            next(iter(group.values())).shape[0]
        engine_steps += iters
        total += iters * b
        # a slot is useful until its sequence stops; frozen after
        active += int(np.minimum(
            res.steps_run * engine.cfg.tokens_per_step, iters).sum())
        stop_steps.extend(res.stop_step.tolist())
        steps_run.extend(res.steps_run.tolist())
        scores.extend(res.scores[i] for i in range(res.scores.shape[0]))
    return StaticQueueResult(
        stop_step=np.array(stop_steps), steps_run=np.array(steps_run),
        scores=scores, engine_steps=engine_steps, active_slot_steps=active,
        total_slot_steps=total, wall_time_s=time.perf_counter() - t0)


def extract_trajectories(model: Model, params, batch, prompt_len: int,
                         max_new_tokens: int, tokens_per_step: int,
                         cache_len: Optional[int] = None,
                         chunk_tokens: Optional[int] = None):
    """Run the model WITHOUT stopping and harvest step embeddings phi_t —
    the trajectory source for meta-training probes on a real model.
    Prompt prefill routes through the shared ``chunked_prefill`` helper
    (``chunk_tokens=None`` keeps the legacy one-shot prefill)."""
    mcfg = model.cfg
    B = next(iter(batch.values())).shape[0]
    pre = prefix_len(mcfg, batch, prompt_len)
    cache_len = cache_len or (pre + max_new_tokens)
    state = chunked_prefill(model, params, batch, cache_len,
                            chunk_tokens=chunk_tokens)
    token = jnp.zeros((B,), jnp.int32)
    step_fn = jax.jit(functools.partial(model.decode_step, mcfg))
    pos0 = pre if mcfg.arch_type != "audio" else 0
    phis, acc, cnt = [], jnp.zeros((B, mcfg.d_model), jnp.float32), 0
    tokens = []
    for i in range(max_new_tokens):
        pos = jnp.asarray(pos0 + i, jnp.int32)
        logits, hidden, state = step_fn(params, token, state, pos)
        token = jnp.argmax(logits[:, :mcfg.vocab_size], -1).astype(jnp.int32)
        tokens.append(np.asarray(token))
        acc = acc + hidden.astype(jnp.float32)
        cnt += 1
        if cnt == tokens_per_step:
            phis.append(np.asarray(acc / cnt))
            acc, cnt = jnp.zeros_like(acc), 0
    return (np.stack(phis, axis=1) if phis else np.zeros((B, 0, mcfg.d_model)),
            np.stack(tokens, axis=1))


# ---------------------------------------------------------------------------
# Continuous batching: slot-level engine


class SlotStepView(NamedTuple):
    """Host-visible per-slot observation after one fused engine step.

    The four trailing fields are ONLY populated by speculative steps
    (``spec_tokens > 0``); one-token steps leave them None, keeping the
    non-spec view byte-identical to before."""
    tokens: np.ndarray      # (n_slots,) token decoded this step
    stopped: np.ndarray     # (n_slots,) bool — ORCA threshold crossed
    stop_step: np.ndarray   # (n_slots,) reasoning step at stop (-1 active)
    n_scores: np.ndarray    # (n_slots,) scores emitted since admission
    smoothed: np.ndarray    # (n_slots,) current smoothed score
    gen: Optional[np.ndarray] = None         # (n_slots,) tokens committed
    seq: Optional[np.ndarray] = None         # (n_slots, k) committed tokens
    seq_scores: Optional[np.ndarray] = None  # (n_slots, k) smoothed / token
    seq_n: Optional[np.ndarray] = None       # (n_slots, k) n_scores / token


def prefix_len(mcfg, batch_one: Dict[str, jnp.ndarray],
               prompt_len: int) -> int:
    """Sequence length ``model.prefill`` will actually run for one request
    (text prompt + vlm patch prefix + learned meta tokens)."""
    n = prompt_len
    if mcfg.arch_type == "vlm" and "patch_embeds" in batch_one:
        n += mcfg.frontend.n_tokens
    n += getattr(mcfg, "n_meta_tokens", 0) or 0
    return n


@dataclasses.dataclass
class Spill:
    """Everything a preempted request needs to resume byte-identically,
    copied to host RAM (the tiered-offload target: HBM pages -> host).

    The per-request TTT calibrator (W_i, b_i, smoothing ring, counters)
    *is* the request's identity — restoring it exactly, together with the
    KV it conditions on and the position it decodes from, is what makes a
    preempted-then-resumed request stop on the same reasoning step as an
    undisturbed one.
    """
    probe: Tuple[np.ndarray, ...]   # one batch-axis-free row per ProbeState leaf
    token: int                      # last decoded token (decode input)
    pos: int                        # sequence position to resume from
    armed: bool                     # True: was RUNNING; False: mid-prefill
    prompt_len: int = 0             # prefill progress bookkeeping (host side)
    # paged: host copies of the victim's pages, (L, max_blocks, ...) per leaf
    pages: Optional[Dict[str, np.ndarray]] = None
    n_blocks: int = 0               # physical blocks the pages cover
    # dense: host copy of the slot's full decode-state lane (axis-1 slice)
    lane: Optional[object] = None

    @property
    def nbytes(self) -> int:
        """Host RAM this spill occupies (KV payload only)."""
        leaves = (list(self.pages.values()) if self.pages is not None
                  else jax.tree.leaves(self.lane) if self.lane is not None
                  else [])
        return int(sum(np.asarray(x).nbytes for x in leaves))


class ContinuousServingEngine:
    """Fixed-shape batch of ``n_slots`` whose rows live independent lives.

    The jax surgery behind continuous batching, kept deliberately small:

    * ``pos`` is a per-slot vector — every model family's ``decode_step``
      accepts (B,) positions (per-row valid masks + per-row cache scatter).
    * ``admit`` prefills ONE request (batch 1) and dynamic-update-slices its
      decode state into the slot (batch axis 1 in every leaf), then resets
      that slot's probe fast weights to (W0, b0) — the slot's score
      trajectory is exactly a fresh single-request run.
    * ``release`` parks the slot (probe ``stopped=True``): the fused step
      treats it as no-op until the scheduler refills it.

    With ``paged=True`` (model families exposing ``init_paged_state``) the
    KV cache is a pool of fixed-size pages instead of one max-length lane
    per slot: ``admit`` takes the request's physical block row (reserved by
    the scheduler from ``repro.serving.kv_pool.BlockPool``), writes prefill
    K/V page-by-page through it — or, on a prefix hit, skips prefill
    entirely and just copies the donor's partial tail page — and
    ``release`` points the slot's table row at the NULL page so a parked
    slot's no-op write can never corrupt a reallocated page.

    The scheduler (``repro.serving.scheduler.OrcaScheduler``) owns queues,
    request lifecycles, the block pool and metrics; this class owns device
    state only.  ``step`` records its host spans and device->host reads in
    ``recorder`` (the scheduler passes its own).
    """

    def __init__(self, model: Model, params, pc: ProbeConfig, theta,
                 cfg: ServeConfig, n_slots: int, cache_len: int,
                 window: Optional[int] = None, *, probe_impl: str = "kernel",
                 interpret: Optional[bool] = None, paged: bool = False,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 pack_max: int = 4, spec_tokens: Optional[int] = None,
                 spec_tree: Optional[Tuple[int, int]] = None,
                 recorder: Optional[StepRecorder] = None):
        self.model, self.params, self.pc, self.theta, self.cfg = \
            model, params, pc, theta, cfg
        mcfg = model.cfg
        self.recorder = recorder if recorder is not None else StepRecorder()
        self.paged = bool(paged)
        if self.paged:
            assert model.supports_paged, \
                f"{mcfg.name}: no paged cache layout for this family"
            assert window is None, "paged serving has no SWA ring buffer"
            self.block_size = int(block_size)
            self.max_blocks = blocks_needed(cache_len, block_size)
            cache_len = self.max_blocks * self.block_size
            self.num_blocks = int(num_blocks or
                                  (n_slots * self.max_blocks + 1))
            self.state = model.init_paged_state(
                n_slots, self.num_blocks, self.block_size, self.max_blocks)
        else:
            self.state = model.init_decode_state(n_slots, cache_len)
        self.n_slots, self.cache_len = n_slots, cache_len
        # chunked prefill: the fused step becomes the unified token-budget
        # step (decode every slot + up to chunk_tokens of prompt work,
        # PACKED across up to max_pack mid-prefill requests) — ONE
        # executable regardless of prompt length or packing shape
        self.chunk_tokens = int(chunk_tokens or 0)
        self.max_pack = max(min(int(pack_max), self.chunk_tokens), 1) \
            if self.chunk_tokens else 0
        if self.chunk_tokens:
            assert window is None, "chunked prefill has no SWA ring buffer"
            assert model.supports_chunked, \
                f"{mcfg.name}: no chunked prefill for this family"
        # speculative draft-verify decode: every RUNNING slot may ride the
        # packed verify chunk with up to spec_tokens tokens per step; lens
        # are traced per-step data, so ONE executable covers every mix.
        # spec_tree=(W,D) is the TREE generalization: 1 + W*D candidate
        # NODES per slot claim the budget (``spec_tokens`` becomes that
        # node count — the scheduler's per-slot unit either way)
        self.spec_tree = (tuple(int(x) for x in spec_tree) if spec_tree
                          else None)
        self.spec_tokens = int(spec_tokens or 0)
        if self.spec_tree:
            assert not self.spec_tokens, \
                "spec_tree and spec_tokens are mutually exclusive"
            assert model.supports_tree, \
                f"{mcfg.name}: no tree speculative decode for this family"
            self.spec_tokens = 1 + self.spec_tree[0] * self.spec_tree[1]
        elif self.spec_tokens:
            assert model.supports_spec, \
                f"{mcfg.name}: no speculative decode for this family"
        # the paged decode kernel's compute blocks, counted per step from
        # the positions the step uploads (``attn_blocks_live``,
        # ``attn_blocks``); speculative steps decode in the verify chunk
        self._block_counts = None
        if (self.paged and not self.spec_tokens
                and A.resolve_paged_impl() == "pallas"):
            k = self.state["k"]                     # (L, P, KV, bs, d)
            self._block_counts = functools.partial(
                decode_block_counts, bs=self.block_size, nb=self.max_blocks,
                n_kv=k.shape[2], d=k.shape[-1], itemsize=k.dtype.itemsize)
        st = init_probe_state(pc, theta, n_slots, mcfg.d_model)
        self.st = st._replace(stopped=jnp.ones((n_slots,), bool))
        self.token = jnp.zeros((n_slots,), jnp.int32)
        self.pos = np.zeros((n_slots,), np.int32)
        self._step_fn = jax.jit(
            make_serve_step(model, pc, cfg, window=window,
                            probe_impl=probe_impl, interpret=interpret,
                            chunk_tokens=self.chunk_tokens,
                            mask_stopped_writes=bool(self.chunk_tokens),
                            spec_tokens=(0 if self.spec_tree
                                         else self.spec_tokens),
                            spec_tree=self.spec_tree),
            donate_argnums=_SERVE_STEP_DONATE)
        if self.spec_tokens:
            if self.spec_tree:
                w_, d_ = self.spec_tree
                zero_drafts = jnp.zeros((n_slots, w_, d_), jnp.int32)
            else:
                zero_drafts = jnp.zeros((n_slots, self.spec_tokens - 1),
                                        jnp.int32)
            self._null_spec = {"lens": jnp.zeros((n_slots,), jnp.int32),
                               "drafts": zero_drafts,
                               "have": jnp.zeros((n_slots,), bool)}
        if self.chunk_tokens:
            r = self.max_pack
            null = {"tokens": jnp.zeros((self.chunk_tokens,), jnp.int32),
                    "seg": jnp.zeros((self.chunk_tokens,), jnp.int32),
                    "slots": jnp.zeros((r,), jnp.int32),
                    "starts": jnp.zeros((r,), jnp.int32),
                    "lengths": jnp.zeros((r,), jnp.int32),
                    "active": jnp.zeros((), bool)}
            if self.paged:
                null["rows"] = jnp.full((r, self.max_blocks), NULL_BLOCK,
                                        jnp.int32)
            self._null_chunk = null
        if self.paged:
            # the page pool is the largest serving buffer: donate it through
            # every admit/release op so XLA updates it in place instead of
            # copying the whole pool per call
            self._set_row = jax.jit(self._set_row_impl, donate_argnums=0)
            self._copy = jax.jit(self._copy_impl, donate_argnums=0)
            self._prefill_pages = jax.jit(self._prefill_pages_impl,
                                          static_argnames=("s_pad",),
                                          donate_argnums=1)
            # preemption: gather copies pages OUT (no donation — the pool
            # keeps serving), scatter writes them back in place
            self._gather_pages = jax.jit(self._gather_pages_impl)
            self._scatter_pages = jax.jit(self._scatter_pages_impl,
                                          donate_argnums=0)
        else:
            self._inject = jax.jit(functools.partial(
                inject_prefill, model, cache_len=cache_len))
            self._take_lane = jax.jit(self._take_lane_impl)
            self._write_lane = jax.jit(self._write_lane_impl,
                                       donate_argnums=0)
        self._reset = jax.jit(functools.partial(reset_probe_slot, pc),
                              static_argnames=("active",))
        self._write_probe = jax.jit(write_probe_slot)

    # ------------------------------------------------------------------
    # paged device ops (jitted in __init__)
    @staticmethod
    def _set_row_impl(state, slot, row):
        return dict(state, block_tables=state["block_tables"].at[slot].set(row))

    @staticmethod
    def _copy_impl(state, src, dst):
        pages = {k: v for k, v in state.items() if k != "block_tables"}
        return dict(A.copy_pages(pages, src, dst),
                    block_tables=state["block_tables"])

    def _prefill_pages_impl(self, params, state, batch_one, row, *,
                            s_pad: int):
        sub, _, _ = self.model.prefill(self.model.cfg, params, batch_one,
                                       s_pad)
        pages = {k: v for k, v in state.items() if k != "block_tables"}
        pages = A.prefill_to_pages(pages, sub, row,
                                   s_pad // self.block_size)
        return dict(pages, block_tables=state["block_tables"])

    @staticmethod
    def _gather_pages_impl(state, row):
        # page axis is 1 in every page leaf; NULL tail rows clamp to page 0
        # (their content is garbage but the scatter drops them — old and
        # new rows share the same n_blocks, hence the same NULL tail)
        src = jnp.where(row == NULL_BLOCK, 0, row)
        return {k: v[:, src] for k, v in state.items()
                if k != "block_tables"}

    @staticmethod
    def _scatter_pages_impl(state, pages, row):
        out = {"block_tables": state["block_tables"]}
        for k, v in state.items():
            if k == "block_tables":
                continue
            # NULL rows are redirected past the pool and dropped — the
            # copy-back can never touch the NULL page or a live page
            dst = jnp.where(row == NULL_BLOCK, v.shape[1], row)
            out[k] = v.at[:, dst].set(pages[k].astype(v.dtype), mode="drop")
        return out

    @staticmethod
    def _take_lane_impl(state, slot):
        return jax.tree.map(lambda x: x[:, slot], state)

    @staticmethod
    def _write_lane_impl(state, lane, slot):
        slot = jnp.asarray(slot, jnp.int32)
        return jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, jnp.expand_dims(one, 1).astype(full.dtype), slot,
                axis=1),
            state, lane)

    # ------------------------------------------------------------------
    def admit(self, slot: int, batch_one: Dict[str, jnp.ndarray],
              prompt_len: int, *, block_row=None, skip_prefill: bool = False,
              copy_tail=None) -> None:
        """Prefill + inject one request into ``slot`` and arm its probe.

        Paged mode additionally takes the request's reserved physical block
        ids (``block_row``); ``skip_prefill`` marks a prefix hit (the shared
        full pages already hold the prompt K/V) and ``copy_tail`` is the
        (src, dst) page pair for the donor's partial tail page, copied
        before this slot starts writing its own decode tokens into it."""
        if self.paged:
            assert block_row is not None, "paged admit needs a block row"
            row = jnp.asarray(pad_row(block_row, self.max_blocks))
            self.state = self._set_row(self.state,
                                       jnp.asarray(slot, jnp.int32), row)
            if copy_tail is not None:
                src, dst = copy_tail
                self.state = self._copy(self.state,
                                        jnp.asarray([src], jnp.int32),
                                        jnp.asarray([dst], jnp.int32))
            if not skip_prefill:
                pre = prefix_len(self.model.cfg, batch_one, prompt_len)
                s_pad = blocks_needed(pre, self.block_size) * self.block_size
                assert s_pad // self.block_size <= len(block_row), \
                    "block row shorter than the prefill prefix"
                self.state = self._prefill_pages(self.params, self.state,
                                                 batch_one, row, s_pad=s_pad)
        else:
            assert block_row is None and copy_tail is None and not skip_prefill
            self.state = self._inject(self.params, self.state, batch_one,
                                      jnp.asarray(slot, jnp.int32))
        self.st = self._reset(self.theta, self.st,
                              jnp.asarray(slot, jnp.int32), active=True)
        self.token = self.token.at[slot].set(0)
        # decode resumes AFTER the whole prefill prefix (vlm patches / meta
        # tokens included) — starting at prompt_len would clobber prefix
        # K/V and leave the prompt's own K/V forever behind the valid mask
        self.pos[slot] = 0 if self.model.cfg.arch_type == "audio" else \
            prefix_len(self.model.cfg, batch_one, prompt_len)

    def release(self, slot: int) -> None:
        """Evict the slot's request: park the probe row as no-op compute.
        Paged: the slot's table row is pointed at the NULL page so its
        parked write can't touch pages the pool hands to someone else."""
        self.st = self._reset(self.theta, self.st,
                              jnp.asarray(slot, jnp.int32), active=False)
        if self.paged:
            null_row = jnp.full((self.max_blocks,), NULL_BLOCK, jnp.int32)
            self.state = self._set_row(self.state,
                                       jnp.asarray(slot, jnp.int32), null_row)
        self.pos[slot] = 0

    def cancel(self, slot: int) -> None:
        """Voluntary mid-flight release (group-consensus sibling
        cancellation).  Distinct from an ORCA stop (no stop decision fired
        for this request) and from FINISHED (budget not exhausted), but the
        device-side mechanics are the release path: park the probe row,
        NULL the table row, zero the position.  Safe MID-PREFILL too: a
        resident PREFILL row already sits parked at the NULL page for the
        whole prefill (``begin_prefill``), so cancelling it simply never
        arms the row — the reserved pages are the scheduler/pool's to
        reclaim."""
        self.release(slot)

    def preempt(self, slot: int, *, block_row=None,
                armed: bool = True, prompt_len: int = 0) -> Spill:
        """INVOLUNTARY eviction: copy the slot's complete request identity
        to host RAM, then release the slot.  Unlike ``cancel`` the request
        is not dead — ``restore`` resumes it byte-identically later.

        Paged mode takes the victim's physical block ids (``block_row`` —
        the SCHEDULER's view, because a mid-prefill victim's device table
        row is still NULL while chunks write through explicit rows) and
        copies those pages out; dense mode copies the slot's whole
        decode-state lane.  ``armed=False`` marks a mid-prefill victim:
        its probe row is parked and its restore re-parks it (the table row
        stays NULL until ``finish_prefill`` arms it)."""
        probe = tuple(np.asarray(leaf[slot]) for leaf in self.st)
        token = int(np.asarray(self.token[slot]))
        pos = int(self.pos[slot])
        pages = lane = None
        n_blocks = 0
        if self.paged:
            assert block_row is not None, "paged preempt needs the block row"
            n_blocks = len(block_row)
            row = jnp.asarray(pad_row(block_row, self.max_blocks))
            pages = {k: np.asarray(v) for k, v in
                     self._gather_pages(self.state, row).items()}
        else:
            assert block_row is None
            lane = jax.tree.map(
                np.asarray,
                self._take_lane(self.state, jnp.asarray(slot, jnp.int32)))
        self.release(slot)
        return Spill(probe=probe, token=token, pos=pos, armed=bool(armed),
                     prompt_len=int(prompt_len), pages=pages,
                     n_blocks=n_blocks, lane=lane)

    def restore(self, slot: int, spill: Spill, *, block_row=None) -> None:
        """Resume a spilled request in ``slot``: page copy-back (or dense
        lane write), block-table rewrite, probe rows reloaded exactly,
        token and position restored.  The new ``block_row`` need not be the
        victim's original blocks — only the table indirection changes, the
        virtual sequence the model sees is identical."""
        if self.paged:
            assert block_row is not None, "paged restore needs a block row"
            assert len(block_row) == spill.n_blocks, \
                (len(block_row), spill.n_blocks)
            row = jnp.asarray(pad_row(block_row, self.max_blocks))
            pages = {k: jnp.asarray(v) for k, v in spill.pages.items()}
            self.state = self._scatter_pages(self.state, pages, row)
            # mid-prefill rows stay parked at NULL — remaining chunks write
            # through the explicit row and finish_prefill arms the table
            table = row if spill.armed else \
                jnp.full((self.max_blocks,), NULL_BLOCK, jnp.int32)
            self.state = self._set_row(self.state,
                                       jnp.asarray(slot, jnp.int32), table)
        else:
            assert block_row is None
            lane = jax.tree.map(jnp.asarray, spill.lane)
            self.state = self._write_lane(self.state, lane,
                                          jnp.asarray(slot, jnp.int32))
        rows = ProbeState(*[jnp.asarray(p) for p in spill.probe])
        self.st = self._write_probe(self.st, jnp.asarray(slot, jnp.int32),
                                    rows)
        self.token = self.token.at[slot].set(spill.token)
        self.pos[slot] = spill.pos

    # ------------------------------------------------------------------
    # chunked prefill: PREFILL is a resident phase, not an admission event
    def begin_prefill(self, slot: int) -> None:
        """Make ``slot`` a resident PREFILL row.  The probe is parked
        (``stopped=True``): the unified step treats the row as no-op decode
        — the probe kernel's boundary gate never touches its state and its
        dense K/V write is dropped by the write mask.  Paged: the slot's
        table row STAYS at NULL for the whole prefill (chunks write through
        their explicit block row), so the parked decode write can't corrupt
        the reserved pages."""
        assert self.chunk_tokens, "engine built without chunk_tokens"
        self.st = self._reset(self.theta, self.st,
                              jnp.asarray(slot, jnp.int32), active=False)
        if self.paged:
            null_row = jnp.full((self.max_blocks,), NULL_BLOCK, jnp.int32)
            self.state = self._set_row(self.state,
                                       jnp.asarray(slot, jnp.int32), null_row)
        self.token = self.token.at[slot].set(0)
        self.pos[slot] = 0

    def finish_prefill(self, slot: int, batch_one: Dict[str, jnp.ndarray],
                       prompt_len: int, *, block_row=None) -> None:
        """Arm ``slot`` after its last prefill chunk: point its table row at
        the now-filled pages (paged), reset the probe to (W0, b0) and resume
        decode at the prompt length — byte-identical slot state to a
        full-prefill ``admit``."""
        assert self.chunk_tokens, "engine built without chunk_tokens"
        if self.paged:
            assert block_row is not None, "paged finish_prefill needs a row"
            self.state = self._set_row(
                self.state, jnp.asarray(slot, jnp.int32),
                jnp.asarray(pad_row(block_row, self.max_blocks)))
        self.st = self._reset(self.theta, self.st,
                              jnp.asarray(slot, jnp.int32), active=True)
        self.token = self.token.at[slot].set(0)
        self.pos[slot] = prefix_len(self.model.cfg, batch_one, prompt_len)

    def _chunk_to_device(self, chunk: ChunkWork) -> Dict[str, jnp.ndarray]:
        """Lower a (possibly packed) ChunkWork to the fixed-shape device
        descriptor: segments laid out back to back in ``tokens``/``seg``,
        per-segment (slot, start, length, pages) arrays padded to
        ``max_pack`` rows with zero-length segments.  Trailing token
        padding keeps the LAST segment's id, which places it past that
        segment's length — invalid by construction, dropped at the
        write."""
        c, r = self.chunk_tokens, self.max_pack
        segs = chunk.segs
        assert 1 <= len(segs) <= r, (len(segs), r)
        toks = np.zeros((c,), np.int32)
        seg = np.full((c,), max(len(segs) - 1, 0), np.int32)
        slots = np.zeros((r,), np.int32)
        starts = np.zeros((r,), np.int32)
        lengths = np.zeros((r,), np.int32)
        rows = (np.full((r, self.max_blocks), NULL_BLOCK, np.int32)
                if self.paged else None)
        off = 0
        for si, s in enumerate(segs):
            assert off + s.length <= c, "packed segments exceed the chunk"
            toks[off:off + s.length] = np.asarray(
                s.tokens[s.start:s.start + s.length])
            seg[off:off + s.length] = si
            slots[si], starts[si], lengths[si] = s.slot, s.start, s.length
            if rows is not None and s.row is not None:
                rows[si, :len(s.row)] = np.asarray(s.row, np.int32)
            off += s.length
        out = {"tokens": jnp.asarray(toks), "seg": jnp.asarray(seg),
               "slots": jnp.asarray(slots), "starts": jnp.asarray(starts),
               "lengths": jnp.asarray(lengths), "active": jnp.asarray(True)}
        if rows is not None:
            out["rows"] = jnp.asarray(rows)
        return out

    def compile_counts(self) -> Dict[str, int]:
        """Executables behind each jitted engine entry point — the compile-
        cache regression surface.  The unified chunked step keeps ``step``
        at 1 however many distinct prompt lengths are admitted;
        ``admission_prefill`` counts the legacy per-length prefill
        executables (one per distinct prompt length / pad size)."""
        out = {"step": self._step_fn._cache_size()}
        if self.paged:
            out["admission_prefill"] = self._prefill_pages._cache_size()
        else:
            out["admission_prefill"] = self._inject._cache_size()
        return out

    def lowered_step(self):
        """The fused step lowered (not compiled) at this engine's current
        state and an idle chunk/spec descriptor — the program ``step``
        runs, for callers that check which kernels it holds."""
        args = [self.params, self.theta, self.token, self.state,
                jnp.asarray(self.pos, jnp.int32), self.st]
        if self.chunk_tokens:
            args.append(self._null_chunk)
        if self.spec_tokens:
            args.append(self._null_spec)
        return self._step_fn.lower(*args)

    def compiled_step_text(self) -> str:
        """The compiled fused step as HLO text: op names as a device trace
        shows them, each op's ``orca/...`` scope in its metadata.  Once the
        step has run, this finds the executable in JAX's caches."""
        return self.lowered_step().compile().as_text()

    # ------------------------------------------------------------------
    def step(self, chunk: Optional[ChunkWork] = None,
             spec_lens=None, spec_drafts=None,
             spec_have=None) -> SlotStepView:
        """One fused step for every slot (vector pos): decode + probe — and,
        in chunked mode, up to ``chunk_tokens`` prompt tokens of up to
        ``max_pack`` mid-prefill requests packed into ``chunk`` (None =
        decode-only, the same executable runs with an inactive chunk);
        several residents may finish their prefill in one step.

        A spec engine additionally takes ``spec_lens`` — per-slot verify
        lengths in [0, spec_tokens] (None = 0 everywhere) — and advances
        each slot's ``pos`` by its ACCEPTED length instead of 1; the view's
        spec fields carry the committed multi-token sequences.
        ``spec_drafts``/``spec_have`` inject host-side drafts (the shared
        draft cache): slots with ``have=False`` fall back to the model
        family's own drafter.  Tree engines (``spec_tree``) take drafts
        shaped (n_slots, W, D) and lens counts NODES in [0, 1 + W*D].

        Recorded as the spans ``orca.upload``, ``orca.dispatch``,
        ``orca.wait`` and ``orca.readback`` with the step's ``reads``
        count and, where the paged decode kernel runs, its
        ``attn_blocks_live`` and ``attn_blocks``."""
        with self.recorder.step():
            return self._step(chunk, spec_lens, spec_drafts, spec_have)

    def _step(self, chunk, spec_lens, spec_drafts, spec_have) -> SlotStepView:
        rec, read = self.recorder, self._read
        with rec.span("orca.upload"):
            args = [self.params, self.theta, self.token, self.state,
                    jnp.asarray(self.pos, jnp.int32), self.st]
            if self._block_counts is not None:
                live, launched = self._block_counts(self.pos)
                rec.count("attn_blocks_live", live)
                rec.count("attn_blocks", launched)
            if self.chunk_tokens:
                args.append(self._null_chunk if chunk is None
                            else self._chunk_to_device(chunk))
            else:
                assert chunk is None, "engine built without chunk_tokens"
            if self.spec_tokens:
                spec = dict(self._null_spec)
                if spec_lens is not None:
                    spec["lens"] = jnp.asarray(np.asarray(spec_lens,
                                                          np.int32))
                if spec_drafts is not None:
                    assert spec_have is not None, \
                        "spec_drafts needs its per-slot have mask"
                    spec["drafts"] = jnp.asarray(np.asarray(spec_drafts,
                                                            np.int32))
                    spec["have"] = jnp.asarray(np.asarray(spec_have, bool))
                args.append(spec)
            else:
                assert spec_lens is None and spec_drafts is None, \
                    "engine built without spec_tokens"
        with rec.span("orca.dispatch"):
            out = self._step_fn(*args)
        with rec.span("orca.wait"):
            jax.block_until_ready(out)
        with rec.span("orca.readback"):
            self.token, self.state, self.st = out[:3]
            view = SlotStepView(tokens=read(self.token),
                                stopped=read(self.st.stopped),
                                stop_step=read(self.st.stop_step),
                                n_scores=read(self.st.n_scores),
                                smoothed=read(self.st.smoothed))
            if not self.spec_tokens:
                self.pos = self.pos + 1
                return view
            extras = out[3]
            gen = read(extras["gen"])
            self.pos = self.pos + gen
            return view._replace(gen=gen, seq=read(extras["seq"]),
                                 seq_scores=read(extras["seq_scores"]),
                                 seq_n=read(extras["seq_n"]))

    def _read(self, x) -> np.ndarray:
        """One blocking device->host copy of a step output, counted as the
        step's ``reads``."""
        self.recorder.count("reads")
        return np.asarray(x)
