"""OrcaScheduler: continuous batching with ORCA-stop eviction.

The scheduler owns the request lifecycle (queues, admission, eviction,
metrics) and — in paged mode — the KV block pool; ``ContinuousServingEngine``
owns device state.  The loop follows the vLLM/sarathi shape — waiting
requests are admitted into fixed-shape batch slots; the moment the
calibrated ORCA threshold test stops a sequence, its slot is released and
refilled from the queue on the very next step — but the *capacity
mechanism* here is the paper's calibrated early stopping: every early stop
returns its remaining step budget to the fleet, so calibrated savings
become measurable throughput.

Paged admission (``paged=True``) replaces "find a free slot lane" with
"reserve blocks from the pool":

* a request needs ``ceil((prompt_len + max_new) / block_size)`` pages; if
  the pool can't cover the reservation the request stays WAITING — the
  scheduler backpressures instead of over-admitting (FIFO order is kept:
  head-of-line blocking, no starvation);
* a prompt that is already resident (self-consistency decoding: N samples
  of one prompt) is admitted as a block-table copy + refcount bump on the
  shared full prompt pages — prefill is skipped entirely; only the partial
  tail page (if any) is copied into a private page before this request
  writes its own decode tokens there;
* an ORCA stop releases the request's pages back to the pool immediately —
  the paper's early stop is literally a memory-reclaim event.

Chunked prefill (``chunk_tokens=N``) turns prefill itself into schedulable
work (the Sarathi shape): admission still reserves pages all-or-nothing,
but instead of a batch-1 full-prompt prefill stalling the whole fleet, the
request becomes a resident PREFILL row and the *batch composer* packs each
engine iteration up to ``token_budget`` tokens — every resident decode
token first, the remainder filled with a PACKED prefill chunk: up to
``chunk_tokens`` prompt tokens drawn from up to ``pack_max`` mid-prefill
residents (the tail of one prompt piggybacked with the head of the next,
block-diagonally isolated on device), so short prompt tails no longer
leave budget on the table.  ``pack_chunks=False`` restores the PR-4
one-request-per-chunk composer through the SAME step executable.  No
decode slot ever skips a step while prefill work is pending, TTFT and
per-step stall tails collapse (FleetMetrics p50/p99), and ONE compiled
step executable serves every prompt length and packing shape.

*Who* gets admitted and *how much* prefill rides each step is delegated to
a pluggable ``SchedulingPolicy`` (``repro.serving.policy``): FIFO (the
default, PR-4's composer), priority classes with anti-starvation aging
(``Request.priority``), and a TTFT-aware policy that widens the prefill
share when decode slots are idle — plus the probe-aware chunk-sizing knob
that shrinks prefill when residents approach a probe boundary.  Scheduling
changes *when* work happens, never *what* the probe sees: stop decisions
are identical to admission-time prefill across every policy and packing
mode (asserted in ``tests/test_chunked_prefill.py``,
``tests/test_packed_chunks.py`` and the throughput gate).

Preemption (``preemption=True``, the overload-safe default) makes the
scheduler reclaim residents, not just wait for them: when capacity (slots
or pages) fails for a unit that is strictly MORE urgent than some
resident, the policy's ``select_victim`` picks strictly-lower-priority
victims (newest first by default) and ``engine.preempt`` spills each one's
KV pages AND probe fast-weight state to host RAM (``engine.Spill``).
Spilled requests sit in a SWAPPED queue that re-admits BEFORE the waiting
queue (``engine.restore`` is a block-table rewrite + page copy-back with
the probe buffers reloaded bit-for-bit), and a swapped head that cannot
yet restore barriers its own class so it is never overtaken.  A
feasibility simulation runs before any spill (no victim is evicted unless
the unit will actually fit) and victims are only ever strictly lower
priority, so the preemption relation is a DAG — no livelock.  Because the
spill/restore round-trip is byte-exact, stop decisions are invariant
under ANY preemption schedule (asserted in ``tests/test_preemption.py``
and ``tests/test_validity_regression.py``).

Eviction is score-invariant by construction: each slot's probe fast
weights are reset to (W0, b0) at admission and the per-slot KV view (dense
lane or block table) only ever exposes the slot's own request, so a
request's score trajectory and stop step are identical to a fresh
single-request run (tested in ``tests/test_serving_scheduler.py`` and
``tests/test_paged_kv.py``; the throughput benchmark asserts it against
the static-batch baseline).
"""
from __future__ import annotations

import dataclasses
import time
import warnings
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.core.calibrator import GroupCalibrator
from repro.core.probe import ProbeConfig
from repro.models.registry import Model
from repro.serving.engine import (ChunkSeg, ChunkWork,
                                  ContinuousServingEngine, ServeConfig,
                                  Spill, chunk_supported, prefix_len)
from repro.serving.groups import RequestGroup, group_requests
from repro.serving.kv_pool import BlockPool, blocks_needed, prompt_key
from repro.serving.policy import (ComposeView, HostPressure,
                                  SchedulingPolicy, make_policy)
from repro.serving.draft_cache import DraftCache
from repro.serving.request import (FleetMetrics, Request, RequestState,
                                   latency_stats, spec_stats)
from repro.serving.tracing import StepRecorder


@dataclasses.dataclass(frozen=True)
class _AdmitPlan:
    """One request's reserved pages + how to fill them."""
    row: List[int]               # physical pages, virtual order
    n_shared: int                # leading pages refcount-shared with a donor
    skip_prefill: bool
    copy_tail: Optional[Tuple[int, int]]   # (donor tail page, private copy)
    register_key: Optional[str]  # register as prefix donor after admission


# constructor-keyword sentinel: distinguishes "not passed" (resolve from
# the unified ServeConfig) from an explicit None (which is meaningful for
# cache_len / num_blocks / chunk_tokens / token_budget / policy / ...)
_UNSET: object = object()


def _pick(explicit, cfg_value):
    """An explicitly passed constructor keyword wins; else the value comes
    from the unified ``ServeConfig`` (the api_redesign contract that lets
    one config object describe a whole scheduler — or N fleet hosts)."""
    return cfg_value if explicit is _UNSET else explicit


class OrcaScheduler:
    """Admit waiting requests into slots; evict on ORCA stop or budget.

    Driving protocol (shared with ``FleetRouter`` — ``serve_requests``
    duck-types over either):

    * ``submit(requests)`` — enqueue gang-admission units (opens a fresh
      serving session if none is active; callable repeatedly);
    * ``step()`` — ONE scheduler iteration (admission -> batch composition
      -> fused engine step -> collection/eviction -> consensus); returns
      False once the fleet is idle;
    * ``drain()`` — step until idle, close the session, return
      ``(requests, FleetMetrics)``;
    * ``run(requests)`` — the classic one-shot facade: submit + drain.

    ``prepare(requests)`` sizes the engine/pool for a population WITHOUT
    enqueueing it, and ``pressure()`` exports the ``HostPressure`` summary
    the fleet router's placement policy consumes.

    Every constructor keyword resolves against the unified ``ServeConfig``
    (explicit keyword wins), so ``OrcaScheduler(model, params, pc, theta,
    cfg)`` alone builds the fully-described scheduler.
    """

    def __init__(self, model: Model, params, pc: ProbeConfig, theta,
                 cfg: ServeConfig, *, n_slots: int = _UNSET,
                 cache_len: Optional[int] = _UNSET,
                 probe_impl: str = _UNSET,
                 interpret: Optional[bool] = _UNSET,
                 paged: bool = _UNSET, block_size: int = _UNSET,
                 num_blocks: Optional[int] = _UNSET,
                 prefix_sharing: bool = _UNSET,
                 chunk_tokens: Optional[int] = _UNSET,
                 token_budget: Optional[int] = _UNSET,
                 policy: Union[str, SchedulingPolicy, None] = _UNSET,
                 pack_chunks: bool = _UNSET,
                 pack_max: int = _UNSET,
                 consensus: Union[GroupCalibrator, float, None] = _UNSET,
                 preemption: bool = _UNSET,
                 spec_tokens: Optional[int] = _UNSET,
                 spec_tree: Optional[str] = _UNSET,
                 draft_cache: Optional[DraftCache] = None,
                 device: Optional[jax.Device] = None):
        # ``device``: the accelerator this scheduler's engine lives on —
        # params, KV cache, page pool and probe state (None: JAX's default
        # device).  The fleet router gives each host its own.
        self.device = device
        if device is not None:
            params, theta = jax.device_put((params, theta), device)
        self.model, self.params, self.pc, self.theta, self.cfg = \
            model, params, pc, theta, cfg
        n_slots = int(_pick(n_slots, cfg.n_slots))
        chunk_tokens = _pick(chunk_tokens, cfg.chunk_tokens)
        token_budget = _pick(token_budget, cfg.token_budget)
        spec_tokens = _pick(spec_tokens, cfg.spec_tokens)
        policy = _pick(policy, cfg.policy)
        consensus = _pick(consensus, cfg.consensus)
        pack_chunks = _pick(pack_chunks, cfg.pack_chunks)
        pack_max = _pick(pack_max, cfg.pack_max)
        preemption = _pick(preemption, cfg.preemption)
        self.n_slots = n_slots
        self.cache_len = _pick(cache_len, cfg.cache_len)
        # probe_impl/interpret route the fused step's probe math: "kernel"
        # (the Pallas serving_probe_step) or "ref" (jnp parity oracle)
        self.probe_impl = _pick(probe_impl, cfg.probe_impl)
        self.interpret = _pick(interpret, cfg.interpret)
        self.paged = bool(_pick(paged, cfg.paged))
        self.block_size = int(_pick(block_size, cfg.block_size))
        self.num_blocks = _pick(num_blocks, cfg.num_blocks)
        self.prefix_sharing = bool(_pick(prefix_sharing, cfg.prefix_sharing))
        # chunked prefill (Sarathi-style): prefill stops being an admission
        # event and becomes schedulable work — each engine iteration packs
        # every resident decode token plus up to ``chunk_tokens`` prompt
        # tokens of mid-prefill residents (PACKED across up to ``pack_max``
        # requests unless ``pack_chunks=False``), bounded by
        # ``token_budget`` tokens per step (default: n_slots decode tokens
        # + one full chunk)
        self.chunk_tokens = int(chunk_tokens) if chunk_tokens else None
        if self.chunk_tokens is not None and not model.supports_chunked:
            warnings.warn(
                f"chunk_tokens={self.chunk_tokens} ignored: model family "
                f"{model.cfg.name!r} has no chunked/packed prefill — "
                "serving falls back to admission-time (one-shot) prefill; "
                "drop chunk_tokens or use a family with "
                "supports_chunked=True to silence this",
                RuntimeWarning, stacklevel=2)
            self.chunk_tokens = None      # family without prefill_chunk
        # speculative draft-verify decode: each RUNNING slot may ride the
        # packed verify chunk with up to spec_tokens tokens per step, drawn
        # from the same token budget the prefill share composes against
        self.spec_tokens = int(spec_tokens) if spec_tokens else None
        if self.spec_tokens is not None and not model.supports_spec:
            warnings.warn(
                f"spec_tokens={self.spec_tokens} ignored: model family "
                f"{model.cfg.name!r} has no draft/verify speculative "
                "decode — serving falls back to one-token decode; drop "
                "spec_tokens or use a family with supports_spec=True to "
                "silence this",
                RuntimeWarning, stacklevel=2)
            self.spec_tokens = None       # family without verify_packed
        # tree speculative decode: "W.D" generalizes the verify block to
        # 1 + W*D candidate NODES per slot; self.spec_tokens becomes that
        # node count so every budget computation below stays unit-correct
        spec_tree = _pick(spec_tree, cfg.spec_tree)
        self.spec_tree: Optional[Tuple[int, int]] = None
        if spec_tree:
            if self.spec_tokens is not None:
                raise ValueError(
                    f"spec_tree={spec_tree!r} with spec_tokens="
                    f"{self.spec_tokens} is ambiguous — they are two "
                    "shapes of the same verify segment; fix by passing "
                    "ONE of them")
            if isinstance(spec_tree, (tuple, list)):
                shape = (int(spec_tree[0]), int(spec_tree[1]))
            else:
                shape = dataclasses.replace(
                    cfg, spec_tree=str(spec_tree),
                    spec_tokens=None).tree_shape()
            if not model.supports_tree:
                warnings.warn(
                    f"spec_tree={spec_tree!r} ignored: model family "
                    f"{model.cfg.name!r} has no tree speculative decode "
                    "— serving falls back to one-token decode; drop "
                    "spec_tree or use a family with supports_tree=True "
                    "to silence this",
                    RuntimeWarning, stacklevel=2)
            else:
                self.spec_tree = shape
                self.spec_tokens = 1 + shape[0] * shape[1]
        # shared n-gram draft cache: the serving layer's drafter for
        # families whose own draft is the degenerate repeat-last-token
        # self-draft.  The FleetRouter passes ONE instance to every host
        # (prefix-registry style); explicit injection also lets tests /
        # callers front ANY family with it
        if draft_cache is not None:
            self.draft_cache: Optional[DraftCache] = draft_cache
        elif (self.spec_tokens is not None and model.self_draft
                and cfg.draft_cache_size):
            self.draft_cache = DraftCache(capacity=cfg.draft_cache_size)
        else:
            self.draft_cache = None
        if self.spec_tokens is None:
            self.draft_cache = None       # nothing to draft for
        if token_budget is not None:
            token_budget = int(token_budget)
            floor = n_slots if (self.chunk_tokens is not None
                                or self.spec_tokens is not None) else 1
            if token_budget < floor:
                raise ValueError(
                    f"token_budget={token_budget} < n_slots={n_slots}: "
                    "every resident decode token rides each unified step, "
                    "so this budget can never be honored and would "
                    "silently starve prefill; fix by raising token_budget "
                    f"to >= n_slots (default n_slots + chunk_tokens = "
                    f"{n_slots + (self.chunk_tokens or 0)}) or lowering "
                    "n_slots")
        # default budget: one decode token per slot (spec_tokens of them
        # in draft-verify mode) plus the prefill chunk; an EXPLICIT
        # budget instead throttles spec extras before prefill share
        self.token_budget = (token_budget if token_budget
                             else n_slots * (self.spec_tokens or 1)
                             + (self.chunk_tokens or 0))
        # pluggable composer policy: admission order + per-step prefill
        # share (repro.serving.policy — "fifo", "priority", "ttft", or an
        # instance); packing is a composer property, not an executable one
        self.policy = make_policy(policy)
        self.pack_chunks = bool(pack_chunks)
        self.pack_max = int(pack_max)
        # group consensus stop (self-consistency tentpole): a calibrated
        # GroupCalibrator, a raw agreement threshold in (0, 1], or None
        # (groups still gang-schedule and share prompt pages, but every
        # sample runs to its own per-request stop)
        if isinstance(consensus, bool):
            raise ValueError(
                f"consensus={consensus!r} is not a threshold: pass a float "
                "agreement threshold in (0, 1], a calibrated "
                "GroupCalibrator, or None to disable the consensus stop")
        if isinstance(consensus, (int, float)):
            thr = float(consensus)
            if not 0.0 < thr <= 1.0:
                raise ValueError(
                    f"consensus={thr} is outside (0, 1]: the threshold is "
                    "the weight share the top answer must reach; fix by "
                    "passing a float in (0, 1] or a calibrated "
                    "GroupCalibrator")
            consensus = GroupCalibrator(lam=thr, burn_in=cfg.burn_in)
        elif consensus is not None:
            if not isinstance(consensus, GroupCalibrator):
                raise ValueError(
                    f"consensus must be a GroupCalibrator, a float in "
                    f"(0, 1] or None, got {type(consensus).__name__}")
            if consensus.lam is None:
                raise ValueError(
                    "consensus GroupCalibrator has no threshold — run "
                    "GroupCalibrator.calibrate(...) first or pass "
                    "consensus=<float threshold>")
        self.consensus = consensus
        # involuntary preemption: reservation failures for strictly-more-
        # urgent units spill lower-priority residents to host RAM instead
        # of waiting; False restores the wait-only (PR-6) admission
        self.preemption = bool(preemption)
        self._n_preempted = self._n_restored = self._n_spilled_blocks = 0
        self.pool: Optional[BlockPool] = None
        self._engine: Optional[ContinuousServingEngine] = None
        # every step's host spans and counters (repro.serving.tracing)
        self.recorder = StepRecorder()
        self._session_open = False
        self._reset_session()

    # ------------------------------------------------------------------
    # serving-session state: queues, residents and counters for ONE
    # submit..drain cycle.  Engine, pool and policy objects deliberately
    # survive across sessions (repeated runs must not recompile).
    def _reset_session(self) -> None:
        self._waiting: deque = deque()            # gang-admission units
        self._swapped: deque = deque()            # (request, Spill) pairs
        self._running: Dict[int, Request] = {}    # slot -> request
        self._prefilling: Dict[int, Request] = {}  # slot -> mid-prefill req
        self._plans: Dict[int, _AdmitPlan] = {}   # deferred donor registry
        self._free: List[int] = list(range(self.n_slots))
        self._requests: List[Request] = []        # submission order
        self.groups: List[RequestGroup] = []      # consensus outcomes
        self._open_groups: List[RequestGroup] = []
        self._steps = 0
        self._total_tokens = 0
        self._peak_blocks = self._prefill_skips = 0
        self._n_cancelled = self._cancel_freed = 0
        self._n_preempted = self._n_restored = self._n_spilled_blocks = 0
        self.recorder.reset()
        self._t0 = time.perf_counter()

    @property
    def has_work(self) -> bool:
        """True while any request is queued, swapped or resident."""
        return bool(self._waiting or self._swapped or self._running
                    or self._prefilling)

    # ------------------------------------------------------------------
    def _resident(self) -> bool:
        return bool(self._running or self._prefilling or self._swapped)

    def _refuse_rebuild(self, what: str, have, need) -> None:
        raise RuntimeError(
            f"submit() needs {what} of {need} but the live session has "
            f"{have} with requests resident — a rebuild would discard "
            "their KV/probe state; fix by sizing the fleet up front via "
            "prepare(<full request population>) (or an explicit "
            "cache_len/num_blocks) before serving starts")

    def _ensure_engine(self, requests: Sequence[Request]) -> ContinuousServingEngine:
        cache_len = self.cache_len
        if cache_len is None:
            mcfg = self.model.cfg
            max_prompt = max((prefix_len(mcfg, r.inputs, r.prompt_len)
                              for r in requests), default=0)
            if mcfg.arch_type == "audio":
                max_prompt = 0  # decoder cache holds generated tokens only
            max_new = max([r.max_new_tokens or self.cfg.max_new_tokens
                           for r in requests] + [self.cfg.max_new_tokens])
            cache_len = max_prompt + max_new
        if self.paged:
            # device-paged only for families with a page layout; every
            # family still gets pool-based admission control (backpressure)
            device_paged = self.model.supports_paged
            # the virtual capacity must also cover the largest prefill
            # prefix (vlm patches / meta tokens can exceed prompt+max_new);
            # _request_blocks reserves pages for it, so the engine's block
            # tables and the default pool have to be sized for it too
            cache_len = max([cache_len]
                            + [self._request_tokens(r) for r in requests])
            max_blocks = blocks_needed(cache_len, self.block_size)
            if self.num_blocks:
                num_blocks = int(self.num_blocks)
            else:
                num_blocks = self.n_slots * max_blocks + 1
                if self.pool is not None:
                    # derived sizing never shrinks a live pool: a smaller
                    # incremental submit (the router's placement path) must
                    # not drop pages a bigger earlier population reserved
                    num_blocks = max(num_blocks, self.pool.num_blocks)
            if self.pool is not None and self.pool.num_blocks != num_blocks \
                    and (self.pool.blocks_in_use or self._resident()):
                if num_blocks > self.pool.num_blocks:
                    self._refuse_rebuild("a page pool",
                                         self.pool.num_blocks, num_blocks)
                num_blocks = self.pool.num_blocks   # big enough: keep it
            if self.pool is None or self.pool.num_blocks != num_blocks:
                self.pool = BlockPool(num_blocks, self.block_size)
            if self._engine is None or self._engine.cache_len < cache_len:
                if self._engine is not None and self._resident():
                    self._refuse_rebuild("an engine cache_len",
                                         self._engine.cache_len, cache_len)
                with jax.default_device(self.device):
                    self._engine = ContinuousServingEngine(
                        self.model, self.params, self.pc, self.theta,
                        self.cfg, self.n_slots, cache_len,
                        probe_impl=self.probe_impl,
                        interpret=self.interpret, paged=device_paged,
                        block_size=self.block_size, num_blocks=num_blocks,
                        chunk_tokens=self.chunk_tokens,
                        pack_max=self.pack_max,
                        spec_tokens=(None if self.spec_tree
                                     else self.spec_tokens),
                        spec_tree=self.spec_tree, recorder=self.recorder)
        elif self._engine is None or self._engine.cache_len < cache_len:
            if self._engine is not None and self._resident():
                self._refuse_rebuild("an engine cache_len",
                                     self._engine.cache_len, cache_len)
            with jax.default_device(self.device):
                self._engine = ContinuousServingEngine(
                    self.model, self.params, self.pc, self.theta, self.cfg,
                    self.n_slots, cache_len, probe_impl=self.probe_impl,
                    interpret=self.interpret,
                    chunk_tokens=self.chunk_tokens, pack_max=self.pack_max,
                    spec_tokens=(None if self.spec_tree
                                 else self.spec_tokens),
                    spec_tree=self.spec_tree, recorder=self.recorder)
        return self._engine

    # ------------------------------------------------------------------
    # paged admission: reserve pages (all-or-nothing) + prefix sharing
    def _request_tokens(self, req: Request) -> int:
        """Virtual positions this request needs: the full prefill prefix
        (vlm patches / meta tokens included — decode resumes after it)
        plus the decode budget."""
        mcfg = self.model.cfg
        max_new = req.max_new_tokens or self.cfg.max_new_tokens
        if mcfg.arch_type == "audio":
            return max_new
        return prefix_len(mcfg, req.inputs, req.prompt_len) + max_new

    def _request_blocks(self, req: Request) -> int:
        return blocks_needed(self._request_tokens(req), self.block_size)

    def _draft_context(self, req: Request, before: int = 0) -> List[int]:
        """The request's last draft-cache n-gram of committed tokens
        (prompt tail + decoded tokens), as plain ints.  ``before`` drops
        that many just-landed trailing tokens — the PRE-step context the
        promotion path keys on."""
        n = self.draft_cache.ngram
        toks = req.tokens[:len(req.tokens) - before] if before \
            else req.tokens
        if len(toks) >= n:
            return [int(t) for t in toks[-n:]]
        prompt = (np.asarray(req.inputs["tokens"][0]).tolist()
                  if "tokens" in req.inputs else [])
        need = n - len(toks)
        return ([int(t) for t in prompt[max(len(prompt) - need, 0):]]
                + [int(t) for t in toks])

    def _sharing_key(self, req: Request) -> Optional[str]:
        if not (self.prefix_sharing and self._engine is not None
                and self._engine.paged):
            return None
        if set(req.inputs) != {"tokens"}:      # multimodal prefixes differ
            return None
        # sharing assumes virtual positions [0, prompt_len) hold exactly
        # the prompt's K/V — a hidden prefix (meta tokens) breaks that
        if prefix_len(self.model.cfg, req.inputs, req.prompt_len) \
                != req.prompt_len:
            return None
        return prompt_key(np.asarray(req.inputs["tokens"]))

    def _reserve(self, req: Request) -> Optional[_AdmitPlan]:
        """Try to reserve this request's pages; None = pool exhausted (the
        request stays WAITING — backpressure, not over-admission)."""
        pool = self.pool
        n_total = self._request_blocks(req)
        key = self._sharing_key(req)
        entry = pool.lookup_prefix(key) if key else None
        if entry is not None and entry.prompt_len == req.prompt_len \
                and len(entry.full_blocks) <= n_total:
            private = pool.allocate(n_total - len(entry.full_blocks))
            if private is None:
                return None
            shared = pool.share(entry.full_blocks)
            copy_tail = None
            if entry.tail_block is not None and private:
                copy_tail = (entry.tail_block, private[0])
            return _AdmitPlan(row=shared + private, n_shared=len(shared),
                              skip_prefill=True, copy_tail=copy_tail,
                              register_key=None)
        row = pool.allocate(n_total)
        if row is None:
            return None
        return _AdmitPlan(row=row, n_shared=0, skip_prefill=False,
                          copy_tail=None, register_key=key)

    def _register_donor(self, req: Request, plan: _AdmitPlan) -> None:
        if plan.register_key is None:
            return
        bs = self.block_size
        n_full = req.prompt_len // bs
        tail = plan.row[n_full] if (req.prompt_len % bs
                                    and n_full < len(plan.row)) else None
        self.pool.register_prefix(plan.register_key, plan.row[:n_full],
                                  tail, req.prompt_len)

    def _chunks_prefill(self, req: Request) -> bool:
        """Will this request's prompt prefill in scheduled chunks (its
        pages only hold the prompt K/V once the LAST chunk lands)?"""
        return bool(self._engine is not None and self._engine.chunk_tokens
                    and chunk_supported(self.model, req.inputs))

    def _share_from_donor(self, donor, req: Request) -> Optional[_AdmitPlan]:
        """Intra-gang prefix sharing: build a sibling's plan off the unit
        leader's freshly-reserved prompt pages (refcount bump on the full
        pages + private pages for the tail/decode), without waiting for
        the leader to populate the prefix registry.  Same page shape as a
        registry hit."""
        key, row, d_prompt = donor
        n_total = self._request_blocks(req)
        n_full = req.prompt_len // self.block_size
        if d_prompt != req.prompt_len or n_full > len(row) \
                or n_total < n_full:
            return None
        private = self.pool.allocate(n_total - n_full)
        if private is None:
            return None
        shared = self.pool.share(row[:n_full])
        copy_tail = None
        if req.prompt_len % self.block_size and n_full < len(row) \
                and private:
            copy_tail = (row[n_full], private[0])
        return _AdmitPlan(row=shared + private, n_shared=n_full,
                          skip_prefill=True, copy_tail=copy_tail,
                          register_key=None)

    def _reserve_unit(self, members: Sequence[Request]
                      ) -> Optional[List[Optional[_AdmitPlan]]]:
        """ALL-OR-NOTHING page reservation for a gang-admission unit.

        The first sample reserves (or prefix-hits) the prompt pages; the
        siblings share its full prompt pages by refcount — the group is
        its own prefix donor, so N samples of one prompt store the prompt
        K/V once even on a cold registry.  Intra-gang sharing only
        engages when the leader's prompt lands in one admission shot
        (chunked prefill defers the donor until the last chunk, so
        siblings then take full private reservations).  Any member
        failing rolls the whole unit back: a group is never
        half-reserved."""
        plans: List[_AdmitPlan] = []
        donor = None
        for req in members:
            plan = None
            key = self._sharing_key(req)
            if donor is not None and key is not None and key == donor[0]:
                plan = self._share_from_donor(donor, req)
            if plan is None:
                plan = self._reserve(req)
                if plan is not None and plan.register_key is not None \
                        and donor is None and not self._chunks_prefill(req):
                    plan_key: str = plan.register_key
                    donor = (plan_key, plan.row, req.prompt_len)
            if plan is None:
                for p in plans:
                    self.pool.free(p.row)
                return None
            plans.append(plan)
        return plans

    # ------------------------------------------------------------------
    # involuntary preemption: spill residents to host RAM, restore later
    def _spill(self, req: Request, running: Dict[int, Request],
               prefilling: Dict[int, Request], plans: Dict[int, "_AdmitPlan"],
               free: List[int], swapped) -> None:
        """Preempt one resident: engine state to host RAM, pages back to
        the pool, slot back to the fleet, request onto the SWAPPED queue."""
        eng = self._engine
        slot = req.slot
        armed = req.state is RequestState.RUNNING
        spill = eng.preempt(
            slot,
            block_row=(req.block_ids if eng.paged and req.block_ids
                       else None),
            armed=armed, prompt_len=req.prefill_progress)
        if self.paged and req.block_ids:
            self._n_spilled_blocks += len(req.block_ids)
            self.pool.free(req.block_ids)
        req.block_ids = []
        req.n_shared_blocks = 0
        running.pop(slot, None)
        prefilling.pop(slot, None)
        # a mid-prefill victim's deferred donor plan names the pages just
        # freed — stale the moment the spill lands, so it is dropped (the
        # restored request re-registers nothing; only an optimization lost)
        plans.pop(slot, None)
        free.append(slot)
        req.slot = -1
        req.state = RequestState.SWAPPED
        req.n_preempted += 1
        self._n_preempted += 1
        swapped.append((req, spill))

    def _restore(self, req: Request, spill: Spill,
                 row: Optional[List[int]], free: List[int],
                 running: Dict[int, Request],
                 prefilling: Dict[int, Request], steps: int) -> None:
        """Resume a spilled request in a free slot: page copy-back (the
        new pages need not be the originals — only the block-table
        indirection changes), probe buffers reloaded exactly, and the
        request re-enters RUNNING (armed) or PREFILL (mid-prompt, its
        remaining chunks ride the unified step as before)."""
        eng = self._engine
        slot = free.pop()
        eng.restore(slot, spill,
                    block_row=(row if eng.paged else None))
        if row is not None:
            req.block_ids = list(row)
            req.n_shared_blocks = 0
        req.slot = slot
        req.restored_step = steps
        self._n_restored += 1
        if spill.armed:
            req.state = RequestState.RUNNING
            running[slot] = req
        else:
            req.state = RequestState.PREFILL
            prefilling[slot] = req

    def _preempt_for(self, members: Sequence[Request], prio: int,
                     running: Dict[int, Request],
                     prefilling: Dict[int, Request], free: List[int],
                     swapped, plans: Dict[int, "_AdmitPlan"]) -> bool:
        """Make room (slots and, in paged mode, pages) for ``members`` by
        spilling strictly-lower-priority residents.

        Runs a FEASIBILITY SIMULATION first — victims are chosen by the
        policy over a shrinking candidate list while simulated refcount
        decrements track which shared pages would actually die — and
        executes NO spill unless the unit will fit afterwards, so a spill
        can never be wasted on a unit that still doesn't fit (and a
        restored victim, being strictly lower priority, can never preempt
        its preemptor back: the relation is a DAG, no livelock)."""
        if not self.preemption:
            return False
        cand = list(running.values()) + list(prefilling.values())
        victims: List[Request] = []
        sim_slots = len(free)
        need_slots = len(members)
        sim_pages = self.pool.num_free if self.paged else 0
        need_pages = (sum(self._request_blocks(r) for r in members)
                      if self.paged else 0)
        sim_dec: Dict[int, int] = {}

        def fits() -> bool:
            return sim_slots >= need_slots and sim_pages >= need_pages

        while not fits():
            vi = self.policy.select_victim(cand, prio)
            if vi is None:
                return False
            victim = cand.pop(vi)
            victims.append(victim)
            sim_slots += 1
            for b in victim.block_ids:
                d = sim_dec.get(b, 0) + 1
                sim_dec[b] = d
                # a shared page only returns with its LAST owner
                if self.pool.refcount(b) - d == 0:
                    sim_pages += 1
        for victim in victims:
            self._spill(victim, running, prefilling, plans, free, swapped)
        return True

    # ------------------------------------------------------------------
    # the submit/step/drain protocol (shared with FleetRouter)
    def prepare(self, requests: Sequence[Request]) -> None:
        """Size the engine and (in paged mode) the page pool for a request
        population WITHOUT enqueueing it.

        The fleet router calls this on every host with the FULL fleet
        population before placement, so no host ever needs a mid-flight
        engine rebuild (refused while requests are resident — a rebuild
        would discard their KV/probe state)."""
        fresh = not self._session_open
        if fresh:
            self._reset_session()
            self._session_open = True
        if requests:
            self._ensure_engine(requests)
        if fresh:
            # engine construction (jit) stays out of queue-wait time, same
            # as the fresh-submit path
            self._t0 = time.perf_counter()

    def submit(self, requests: Sequence[Request]) -> None:
        """Enqueue ``requests`` as gang-admission units, opening a fresh
        serving session if none is active.  Callable repeatedly — the
        engine/pool are sized for each submitted population, so size for
        the UNION up front via ``prepare`` when submitting incrementally."""
        requests = list(requests)
        fresh = not self._session_open
        if fresh:
            self._reset_session()
            self._session_open = True
        if not requests:
            return
        self._ensure_engine(requests)
        # gang-admission units: a whole self-consistency group (atomic:
        # all samples or none) or a singleton; with no grouped requests
        # this is exactly the classic per-request queue
        units, groups = group_requests(requests)
        for grp in groups:
            if grp.size > self.n_slots:
                raise ValueError(
                    f"group {grp.group_id} has {grp.size} samples but the "
                    f"fleet has {self.n_slots} slots: gang admission needs "
                    "every sample resident at once; fix by raising n_slots "
                    f"to >= {grp.size} or lowering the group size")
        if fresh:
            # the serving clock starts once the first batch is staged —
            # engine construction stays out of queue-wait time, matching
            # the pre-split run() semantics
            self._t0 = time.perf_counter()
        self._requests.extend(requests)
        self.groups.extend(groups)     # exposed: consensus outcomes
        if self.consensus:
            # groups whose consensus may still fire (checked every step a
            # member could have emitted a score; a lone sample never votes)
            self._open_groups.extend(g for g in groups if g.size >= 2)
        self._waiting.extend(units)

    def run(self, requests: Sequence[Request]
            ) -> Tuple[List[Request], FleetMetrics]:
        """Drive every request to STOPPED/FINISHED/CANCELLED; return them
        + metrics.  The classic one-shot facade over submit + drain."""
        if self._session_open and self.has_work:
            raise RuntimeError(
                "run() while a serving session is active would reset "
                "resident state; drive incremental traffic through "
                "submit()/step()/drain() instead")
        self._session_open = False     # fresh session even after a drain
        self.submit(requests)
        return self.drain()

    def drain(self) -> Tuple[List[Request], FleetMetrics]:
        """Step until the fleet is idle, close the session and return
        every submitted request plus the session's ``FleetMetrics``."""
        while self.step():
            pass
        wall = max(time.perf_counter() - self._t0, 1e-9)
        requests = list(self._requests)
        metrics = self._metrics(requests, wall)
        self._session_open = False
        return requests, metrics

    def step(self) -> bool:
        """ONE scheduler iteration: admission -> batch composition -> the
        fused engine step -> token collection / ORCA eviction -> prefill
        bookkeeping -> consensus.  Returns False when the fleet is idle
        (nothing queued, swapped or resident).

        Each iteration is one record of ``self.recorder``: the spans
        ``orca.step`` (the step index), ``orca.admit`` (the admitted
        request ids), ``orca.compose``, the engine's ``orca.upload`` /
        ``orca.dispatch`` / ``orca.wait`` / ``orca.readback``,
        ``orca.collect``, ``orca.prefill_done`` and ``orca.consensus``, and
        the step's counters (``repro.serving.tracing.COUNTERS``)."""
        if not self.has_work:
            return False
        rec = self.recorder
        with rec.step():
            with rec.span("orca.admit") as ann:
                admitted = self._admit()
                if admitted:
                    ann.set_metadata(req_ids=",".join(map(str, admitted)))
            with rec.span("orca.compose"):
                chunk, spec_kw, draft_ctx = self._compose()
            eng = self._engine
            if eng.chunk_tokens:
                view = eng.step(chunk, **spec_kw)
            else:
                view = eng.step(**spec_kw)
            self._steps += 1
            with rec.span("orca.collect"):
                self._collect(view, spec_kw.get("spec_lens"), draft_ctx)
            with rec.span("orca.prefill_done"):
                self._prefill_done(chunk)
            with rec.span("orca.consensus"):
                self._consensus()
        return True

    def _admit(self) -> List[int]:
        """Refill free slots before the next fused step; returns the ids of
        the requests admitted (restores are not admissions)."""
        eng = self._engine
        chunked = bool(eng.chunk_tokens)
        waiting, swapped = self._waiting, self._swapped
        running, prefilling = self._running, self._prefilling
        plans, free = self._plans, self._free
        steps = self._steps
        admitted: List[int] = []
        # SWAPPED requests (preemption victims) restore FIRST — ahead
        # of every WAITING unit — and a swapped head that cannot yet
        # restore BARRIERS its own class: only strictly-more-urgent
        # units admit past it, so a victim is never overtaken by its
        # own class.  Then the POLICY picks which WAITING UNIT (a
        # whole group, or a singleton for the classic request) — in
        # paged mode a unit that doesn't fit the pool holds its place
        # and WAITS for an eviction to return pages, and a group
        # additionally waits for enough free SLOTS: gang admission is
        # all-or-nothing on both resources, so a group is never
        # half-resident.  Pages are still reserved ALL-OR-NOTHING,
        # whether the prompt then prefills in one admission shot or in
        # scheduled chunks.  When capacity fails for a unit strictly
        # MORE urgent than some resident, ``_preempt_for`` spills
        # policy-chosen victims until the unit fits; and a gang
        # needing more slots than are free no longer stalls smaller
        # units behind it — the policy may SKIP it, bounded by the
        # ``max_head_skips`` aging guard (a pinned gang admits next).
        tried: set = set()        # id(unit) passed over this round
        barrier_prio: Optional[int] = None
        while swapped or waiting:
            if swapped and barrier_prio is None:
                req, spill = swapped[0]
                if req.done:      # cancelled while swapped
                    swapped.popleft()
                    continue
                if free:
                    row = None
                    if self.paged:
                        row = self.pool.allocate(
                            self._request_blocks(req))
                    if row is not None or not self.paged:
                        swapped.popleft()
                        self._restore(req, spill, row, free, running,
                                      prefilling, steps)
                        if self.paged:
                            self._peak_blocks = max(
                                self._peak_blocks,
                                self.pool.blocks_in_use)
                        continue
                if self._preempt_for([req], req.priority, running,
                                     prefilling, free, swapped, plans):
                    continue      # room made: retry the restore
                if not (running or prefilling):
                    raise RuntimeError(
                        f"swapped request {req.req_id} cannot restore "
                        "with the fleet empty — slot/page accounting "
                        "is corrupt")
                barrier_prio = req.priority
            if not waiting:
                break
            cand_idx = [i for i, u in enumerate(waiting)
                        if id(u) not in tried]
            if not cand_idx:
                break
            cand = [waiting[i] for i in cand_idx]
            sel = self.policy.select_admit_unit(cand, steps)
            idx = cand_idx[sel]
            unit = waiting[idx]
            members = [r for r in unit
                       if r.state is RequestState.WAITING]
            if not members:          # fully cancelled before admission
                del waiting[idx]
                continue
            prio = min(r.priority for r in members)
            if barrier_prio is not None and prio >= barrier_prio:
                break     # nothing more urgent than the blocked head
            if len(members) > len(free):
                # slot shortage: preempt strictly-less-urgent
                # residents; else let the policy skip the oversized
                # unit so smaller units behind it still admit
                if not self._preempt_for(members, prio, running,
                                         prefilling, free, swapped,
                                         plans):
                    if free and len(cand) > 1 \
                            and self.policy.on_skipped_unit(cand, sel):
                        tried.add(id(unit))
                        continue
                    break
            if self.paged:
                mplans = self._reserve_unit(members)
                if mplans is None and self._preempt_for(
                        members, prio, running, prefilling, free,
                        swapped, plans):
                    mplans = self._reserve_unit(members)
                if mplans is None:
                    if not (running or prefilling or swapped):
                        need = sum(self._request_blocks(r)
                                   for r in members)
                        what = (f"group {members[0].group_id}"
                                if members[0].group_id is not None
                                else f"request {members[0].req_id}")
                        raise RuntimeError(
                            f"{what} needs {need} pages but the "
                            f"pool holds {self.pool.num_usable}; "
                            "nothing left to evict")
                    break
            else:
                mplans = [None] * len(members)
            self.policy.on_admitted_unit(cand, sel)
            del waiting[idx]
            for req, plan in zip(members, mplans):
                slot = free.pop()
                admitted.append(req.req_id)
                req.slot, req.admitted_step = slot, steps
                req.queue_wait_s = time.perf_counter() - self._t0
                req.state = RequestState.PREFILL
                skip = plan.skip_prefill if plan is not None else False
                if plan is not None:
                    req.block_ids = list(plan.row)
                    req.n_shared_blocks = plan.n_shared
                    req.prefill_skipped = skip
                    self._prefill_skips += int(skip)
                    self._peak_blocks = max(self._peak_blocks,
                                            self.pool.blocks_in_use)
                if chunked and not skip \
                        and chunk_supported(self.model, req.inputs):
                    # prefill is schedulable work, not an admission
                    # event: the slot becomes a resident PREFILL row
                    # and the prompt rides the unified step in
                    # token-budget chunks
                    eng.begin_prefill(slot)
                    req.prefill_progress = 0
                    prefilling[slot] = req
                    if plan is not None:
                        # donor registration deferred: the pages only
                        # hold the prompt K/V once the last chunk lands
                        plans[slot] = plan
                else:
                    if plan is not None and eng.paged:
                        eng.admit(slot, req.inputs, req.prompt_len,
                                  block_row=plan.row,
                                  skip_prefill=skip,
                                  copy_tail=plan.copy_tail)
                    else:
                        # family without a page layout / non-text
                        # prompt: the pool still admission-controls,
                        # the device cache stays dense and prefill
                        # stays one shot
                        eng.admit(slot, req.inputs, req.prompt_len)
                    if plan is not None:
                        self._register_donor(req, plan)
                    req.state = RequestState.RUNNING
                    running[slot] = req
        return admitted

    def _compose(self):
        """Batch composition: every resident decode token rides this step;
        in spec mode each RUNNING slot additionally claims up to
        spec_tokens - 1 extra verify tokens (greedy in slot order, capped
        by its remaining decode budget) from the SAME token budget; the
        POLICY then sizes the prefill share of what's left, and the share
        is PACKED across mid-prefill residents in admission order — the
        tail of one prompt and the head of the next fuse into one
        block-diagonal chunk (pack_chunks=False: one request per chunk,
        PR-4's composer).  Returns (chunk, the engine's spec keywords,
        each drafting slot's draft-cache context)."""
        eng = self._engine
        running, prefilling = self._running, self._prefilling
        rec = self.recorder
        spec_kw: Dict[str, object] = {}
        draft_ctx: Dict[int, List[int]] = {}
        spec_total = len(running)
        if self.spec_tokens:
            spec_lens = np.zeros((self.n_slots,), np.int32)
            spec_drafts = spec_have = None
            # no token budget -> spec extras are bounded by block length
            # alone (n_slots * (spec_tokens - 1) can never exceed this cap)
            budget_left = (self.token_budget - len(running)
                           if self.token_budget is not None
                           else self.n_slots * self.spec_tokens)
            # tree mode: the accepted path is at most one node per DEPTH,
            # so extra nodes beyond width * (remaining - 1) can never
            # commit — the depth cap that keeps a near-budget slot from
            # claiming nodes it cannot use (width 1 == the linear cap)
            width = self.spec_tree[0] if self.spec_tree else 1
            for slot in sorted(running):
                req = running[slot]
                max_new = req.max_new_tokens or self.cfg.max_new_tokens
                remaining = max_new - len(req.tokens)
                extra = max(min(self.spec_tokens - 1,
                                width * (remaining - 1), budget_left), 0)
                spec_lens[slot] = 1 + extra
                budget_left -= extra
            spec_total = int(spec_lens.sum())
            if self.draft_cache is not None:
                # shared-cache drafts for every slot actually drafting
                # this step; misses keep have=False — the engine falls
                # back to the family drafter inside the same executable
                if self.spec_tree:
                    w_, d_ = self.spec_tree
                    spec_drafts = np.zeros((self.n_slots, w_, d_), np.int32)
                else:
                    w_, d_ = 1, self.spec_tokens - 1
                    spec_drafts = np.zeros((self.n_slots, d_), np.int32)
                spec_have = np.zeros((self.n_slots,), bool)
                for slot in sorted(running):
                    if spec_lens[slot] < 2:
                        continue
                    req = running[slot]
                    ctx = self._draft_context(req)
                    draft_ctx[slot] = ctx
                    tree, hit = self.draft_cache.lookup(ctx, w_, d_)
                    spec_drafts[slot] = tree if self.spec_tree else tree[0]
                    spec_have[slot] = hit
                    if hit:
                        req.draft_hits += 1
                    else:
                        req.draft_misses += 1
            spec_kw = dict(spec_lens=spec_lens, spec_drafts=spec_drafts,
                           spec_have=spec_have)
        chunk = None
        if prefilling:
            share = self.policy.prefill_share(self._compose_view(
                running, prefilling, self._waiting, eng))
            share = min(share, eng.chunk_tokens,
                        self.token_budget - spec_total)
            segs: List[ChunkSeg] = []
            residents = list(prefilling.items())
            if any(r.group_id is not None
                   for r in prefilling.values()):
                # sample spreading: order mid-prefill residents by
                # sample_idx first, so one packed chunk carries sample
                # k of SEVERAL groups rather than all samples of one —
                # siblings finish prefill on different steps and their
                # probe boundaries (hence votes) de-phase.  Ungrouped
                # fleets keep admission order byte-for-byte.
                residents.sort(key=lambda kv: (kv[1].sample_idx,
                                               kv[1].admitted_step,
                                               kv[1].req_id))
            for slot, req in residents:
                if share <= 0 or len(segs) >= eng.max_pack:
                    break
                n = min(share, req.prompt_len - req.prefill_progress)
                if n <= 0:
                    continue
                segs.append(ChunkSeg(
                    slot=slot,
                    tokens=np.asarray(req.inputs["tokens"][0]),
                    start=req.prefill_progress, length=int(n),
                    row=(np.asarray(req.block_ids, np.int32)
                         if eng.paged and req.block_ids else None)))
                share -= n
                if not self.pack_chunks:
                    break
            if segs:
                chunk = ChunkWork(segs=tuple(segs))
                rec.count("prefill_segments", len(segs))
        rec.count("decode_rows", len(running))
        rec.count("step_tokens",
                  spec_total + (chunk.total_tokens if chunk else 0))
        return chunk, spec_kw, draft_ctx

    def _collect(self, view, spec_lens, draft_ctx: Dict[int, List[int]]
                 ) -> None:
        """Token collection and ORCA eviction after the fused step."""
        eng = self._engine
        running, free = self._running, self._free
        steps = self._steps
        now = time.perf_counter()
        for slot, req in list(running.items()):
            if req.first_token_step < 0:
                req.first_token_step = steps
                req.ttft_s = now - self._t0
            if self.spec_tokens:
                # speculative block: the slot proposed spec_lens[slot]
                # tokens and the verifier accepted a prefix of
                # view.gen[slot]; append accepted tokens in order,
                # collecting each probe boundary's (score, answer)
                # vote as it lands, and TRUNCATE at the stop boundary
                # — tokens past the stop were never "emitted" (the
                # one-token engine would have evicted the slot there)
                lp = int(spec_lens[slot])
                g = int(view.gen[slot])
                req.spec_proposed += max(lp - 1, 0)
                req.spec_accepted += max(g - 1, 0)
                if lp > 0:
                    req.accepted_lens.append(g)
                    if self.spec_tree:
                        req.tree_nodes += max(lp - 1, 0)
                        req.tree_path_lens.append(g)
                stopped_now = bool(view.stopped[slot])
                stop_at = int(view.stop_step[slot]) if stopped_now else -1
                landed: List[int] = []
                for j in range(g):
                    tok = int(view.seq[slot, j])
                    req.tokens.append(tok)
                    landed.append(tok)
                    self._total_tokens += 1
                    nsj = int(view.seq_n[slot, j])
                    if nsj > len(req.scores):
                        req.scores.append(float(view.seq_scores[slot, j]))
                        req.answers.append(tok)
                    if stopped_now and nsj == stop_at:
                        break
                if self.draft_cache is not None and landed:
                    # promote what the VERIFIER accepted: the cache
                    # learns exactly the continuations this traffic
                    # commits, shared fleet-wide
                    ctx = draft_ctx.get(slot)
                    if ctx is None:
                        ctx = self._draft_context(req, before=len(landed))
                    self.draft_cache.observe(ctx, landed)
                n_scores = int(view.n_scores[slot])
            else:
                req.tokens.append(int(view.tokens[slot]))
                self._total_tokens += 1
                n_scores = int(view.n_scores[slot])
                if n_scores > len(req.scores):
                    req.scores.append(float(view.smoothed[slot]))
                    # the vote at this probe boundary: the answer hash
                    # is the token just decoded (the step's answer
                    # proxy, same convention as launch.serve's
                    # trajectory extraction) — recorded alongside the
                    # score so a group's consensus sees matched
                    # (confidence, answer) pairs
                    req.answers.append(int(view.tokens[slot]))
            max_new = req.max_new_tokens or self.cfg.max_new_tokens
            if bool(view.stopped[slot]):
                # ORCA stop: evict NOW — the slot is free next step
                req.stop_step = int(view.stop_step[slot])
                req.steps_run = req.stop_step
                self._complete(req, RequestState.STOPPED, steps)
            elif len(req.tokens) >= max_new:
                req.stop_step = -1
                req.steps_run = n_scores
                self._complete(req, RequestState.FINISHED, steps)
            else:
                continue
            eng.release(slot)
            if self.paged and req.block_ids:
                # the stop IS the reclaim: pages return to the pool now
                self.pool.free(req.block_ids)
            free.append(slot)
            del running[slot]

    def _prefill_done(self, chunk: Optional[ChunkWork]) -> None:
        """Prefill bookkeeping AFTER token collection: every segment of
        the packed chunk advances; a request whose last chunk just landed
        decodes its first token NEXT step."""
        if chunk is None:
            return
        eng, prefilling = self._engine, self._prefilling
        for seg in chunk.segs:
            req = prefilling[seg.slot]
            req.prefill_progress += seg.length
            if req.prefill_progress >= req.prompt_len:
                eng.finish_prefill(
                    seg.slot, req.inputs, req.prompt_len,
                    block_row=(req.block_ids
                               if eng.paged and req.block_ids
                               else None))
                del prefilling[seg.slot]
                plan = self._plans.pop(seg.slot, None)
                if plan is not None:
                    self._register_donor(req, plan)
                req.state = RequestState.RUNNING
                self._running[seg.slot] = req

    def _consensus(self) -> None:
        """Consensus stop: after this step's scores landed (and ORCA
        evictions ran — a sample stopping at this very boundary still
        votes its final frozen score), each open group's calibrated vote
        is re-checked; the first crossing CANCELS every still-running
        sibling mid-flight — slot, pages and probe state return to the
        fleet, the unspent budget becomes group savings."""
        if not self._open_groups:
            return
        eng, swapped, free = self._engine, self._swapped, self._free
        running, prefilling = self._running, self._prefilling
        steps = self._steps
        still_open: List[RequestGroup] = []
        for grp in self._open_groups:
            fire, ans, agr = self.consensus.decide(
                [r.scores for r in grp.requests],
                [r.answers for r in grp.requests])
            if fire:
                grp.consensus_step = steps
                grp.consensus_index = max(
                    len(r.scores) for r in grp.requests) - 1
                grp.consensus_answer = int(ans)
                grp.consensus_agreement = float(agr)
                for sib in grp.requests:
                    if sib.done:
                        continue
                    if sib.state is RequestState.SWAPPED:
                        # a spilled sibling holds no slot and no
                        # pages (both returned at spill) — drop
                        # its queued restore and mark it cancelled
                        for qi, (q, _) in enumerate(swapped):
                            if q is sib:
                                del swapped[qi]
                                break
                        sib.steps_run = len(sib.scores)
                        sib.stop_step = -1
                        self._complete(sib, RequestState.CANCELLED,
                                       steps)
                        self._n_cancelled += 1
                        continue
                    slot = sib.slot
                    eng.cancel(slot)
                    if self.paged and sib.block_ids:
                        self._cancel_freed += \
                            self.pool.free(sib.block_ids)
                    free.append(slot)
                    running.pop(slot, None)
                    if slot in prefilling:
                        # cancel-mid-prefill: the row sat parked
                        # at NULL the whole prefill, so it was
                        # never armed; drop the deferred donor
                        # plan with it
                        del prefilling[slot]
                        self._plans.pop(slot, None)
                    sib.steps_run = len(sib.scores)
                    sib.stop_step = -1
                    self._complete(sib, RequestState.CANCELLED,
                                   steps)
                    self._n_cancelled += 1
            elif not grp.done:
                still_open.append(grp)
        self._open_groups = still_open

    # ------------------------------------------------------------------
    def pressure(self, host: int = 0) -> HostPressure:
        """Export this scheduler's ``ComposeView``-style pressure summary
        — the per-host snapshot the ``FleetRouter``'s placement policy
        consumes each step (the gossip of the simulated fleet).  Valid at
        any point in a session, including before the first submit."""
        residents = list(self._running.values()) \
            + list(self._prefilling.values())
        queued = sum(sum(1 for r in u if not r.done)
                     for u in self._waiting)
        swapped_live = sum(1 for r, _ in self._swapped if not r.done)
        return HostPressure(
            host=int(host), n_slots=self.n_slots,
            n_running=len(self._running),
            n_prefilling=len(self._prefilling),
            n_swapped=swapped_live,
            n_waiting=len(self._waiting),
            queued_samples=queued,
            free_slots=len(self._free),
            pool_blocks=self.pool.num_usable if self.pool else 0,
            free_blocks=self.pool.num_free if self.pool else 0,
            blocks_in_use=self.pool.blocks_in_use if self.pool else 0,
            max_resident_priority=(max(r.priority for r in residents)
                                   if residents else None))

    # ------------------------------------------------------------------
    def _compose_view(self, running: Dict[int, Request],
                      prefilling: Dict[int, Request], waiting,
                      eng: ContinuousServingEngine) -> ComposeView:
        near = 0
        margin = self.policy.probe_margin
        if margin is not None and running:
            tps = self.cfg.tokens_per_step
            # tokens still owed before each resident's next probe boundary
            # (the step a stop decision can fire): len(tokens) counts
            # decoded tokens, the boundary closes every tokens_per_step
            near = sum(1 for r in running.values()
                       if tps - (len(r.tokens) % tps) <= margin)
        return ComposeView(n_running=len(running), n_slots=self.n_slots,
                           n_prefilling=len(prefilling),
                           n_waiting=len(waiting),
                           token_budget=self.token_budget,
                           chunk_tokens=eng.chunk_tokens,
                           near_boundary=near)

    # ------------------------------------------------------------------
    @staticmethod
    def _complete(req: Request, state: RequestState, step: int) -> None:
        req.state = state
        req.completed_step = step

    def _metrics(self, requests: Sequence[Request],
                 wall: float) -> FleetMetrics:
        """The session's FleetMetrics; step tallies and stall tails come
        from ``self.recorder`` (stalls: the wall time of each step)."""
        rec = self.recorder
        steps = self._steps
        active_slot_steps = rec.total("decode_rows")
        n = len(requests)
        sav = [r.savings(self.cfg.tokens_per_step, self.cfg.max_new_tokens)
               for r in requests]
        queue = [r.queue_steps for r in requests]
        # latency tails via the shared helper (CANCELLED excluded there;
        # the FleetRouter recomputes the same stats over the fleet union)
        ttft_p50, ttft_p99, per_class = latency_stats(list(requests))
        stalls = rec.step_ms()
        st = np.asarray(stalls if stalls else [0.0])
        # group-level accounting: savings COUNT a cancelled sample's
        # unspent budget (the whole point of consensus cancellation)
        tps, dmn = self.cfg.tokens_per_step, self.cfg.max_new_tokens
        real_groups = [g for g in self.groups if g.size >= 2]
        g_sav = [g.savings(tps, dmn) for g in real_groups]
        fired = [g for g in real_groups if g.decided]
        # total unspent reasoning steps across groups — what the fleet
        # actually got back (the documented group_savings semantics; the
        # old per-group mean fraction survives as group_savings_mean)
        g_unspent = [max(g.budget_steps(tps, dmn) - g.steps_spent(), 0)
                     for g in real_groups]
        # speculative-decode acceptance via the ONE shared helper
        # (CANCELLED siblings excluded there; the FleetRouter calls the
        # same function over the fleet union, so the two can never drift)
        return FleetMetrics(
            **spec_stats(list(requests)),
            samples_cancelled=self._n_cancelled,
            consensus_groups=len(fired),
            consensus_steps=(float(np.mean([g.consensus_index
                                            for g in fired]))
                             if fired else 0.0),
            group_savings=float(sum(g_unspent)),
            group_savings_mean=float(np.mean(g_sav)) if g_sav else 0.0,
            cancel_freed_blocks=self._cancel_freed,
            preemptions=self._n_preempted,
            restores=self._n_restored,
            spilled_blocks=self._n_spilled_blocks,
            n_requests=n, n_slots=self.n_slots, engine_steps=steps,
            active_slot_steps=active_slot_steps, wall_time_s=wall,
            requests_per_s=n / wall, tokens_per_s=self._total_tokens / wall,
            slot_utilization=(active_slot_steps
                              / max(steps * self.n_slots, 1)),
            mean_step_savings=float(np.mean(sav)) if sav else 0.0,
            mean_queue_steps=float(np.mean(queue)) if queue else 0.0,
            pool_blocks=self.pool.num_usable if self.pool else 0,
            peak_blocks_in_use=self._peak_blocks,
            prefill_skips=self._prefill_skips,
            ttft_ms_p50=ttft_p50, ttft_ms_p99=ttft_p99,
            stall_ms_p50=float(np.percentile(st, 50)),
            stall_ms_p99=float(np.percentile(st, 99)),
            prefill_chunks=rec.steps_with("prefill_segments", 1),
            packed_chunks=rec.steps_with("prefill_segments", 2),
            peak_step_tokens=rec.peak("step_tokens"), per_class=per_class)
