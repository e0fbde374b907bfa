"""FleetRouter: multi-host fleet serving over simulated hosts.

The scheduler stack is host-local by construction — the paged ``BlockPool``,
the unified token-budget step and the ``SchedulingPolicy`` instance all
live inside one ``OrcaScheduler``.  This module shards that scheduler
across N *simulated* hosts: each host owns its own engine, pool and a
host-local policy instance, and the router owns only PLACEMENT — which
host a gang-admission unit lands on.

The design is the ``SchedulingPolicy`` split moved up one level:

* the router's own policy instance orders the cross-host queue with the
  SAME ``select_admit_unit`` semantics (priority, anti-starvation aging,
  gangs as atomic units) the host admission loop uses;
* a ``PlacementPolicy`` then picks the host, fed by per-host
  ``HostPressure`` summaries gossiped each step (n_running, n_prefilling,
  n_swapped, free pages — the ``ComposeView``-style snapshot
  ``OrcaScheduler.pressure()`` exports from scheduler + kv_pool);
* prefix-registry-aware placement routes same-prompt-hash traffic
  (including whole self-consistency gangs) to the host already holding
  the donor pages, so prefix sharing becomes a fleet-level win — the
  follower's prefill collapses to a page-table copy on the donor host
  (``prefill_skipped``) instead of a cold prefill elsewhere.

Because each host runs the UNCHANGED single-host scheduler, a request's
stop decision depends only on its own trajectory — per-request stops stay
byte-identical to single-host serving under every placement (the standing
invariant).  A gang is never split across hosts: the whole group places
as one unit, preserving gang-admission atomicity and intra-gang page
sharing.

Host i is placed on local device i (round robin when there are fewer
devices than hosts): its params, KV pool and probe state live there, so on
a four-chip host four hosts are four one-chip replicas.  Hosts step
concurrently through a thread pool (the jitted fused step releases the
GIL, so hosts genuinely overlap); pass ``parallel_hosts=False`` for
strictly serial stepping.

The router speaks the same ``submit()`` / ``step()`` / ``drain()`` /
``run()`` protocol as ``OrcaScheduler``, so ``repro.api.serve_requests``
and the benchmark drive either interchangeably.
"""
from __future__ import annotations

import copy
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.serving.config import ServeConfig
from repro.serving.engine import prefix_len
from repro.serving.groups import RequestGroup, group_requests
from repro.serving.kv_pool import prompt_key
from repro.serving.policy import (HostPressure, PlacementPolicy,
                                  SchedulingPolicy, make_placement,
                                  make_policy)
from repro.serving.draft_cache import DraftCache
from repro.serving.request import (FleetMetrics, Request, latency_stats,
                                   spec_stats)
from repro.serving.scheduler import OrcaScheduler, _pick, _UNSET


def _clone_policy(spec: Any) -> SchedulingPolicy:
    """A fresh policy instance per host (and one for the router): aging
    and placement state must be host-local, never shared."""
    if spec is None or isinstance(spec, str):
        return make_policy(spec)
    return copy.deepcopy(spec)


class FleetRouter:
    """Shards ``OrcaScheduler`` across ``n_hosts`` simulated hosts.

    Speaks the scheduler's ``submit``/``step``/``drain``/``run`` protocol;
    construct via ``repro.api.fleet`` in application code.
    """

    def __init__(self, model, params, probe_config, theta,
                 cfg: Optional[ServeConfig] = None, *,
                 n_hosts: Any = _UNSET, placement: Any = _UNSET,
                 parallel_hosts: bool = True) -> None:
        cfg = cfg if cfg is not None else ServeConfig()
        self.n_hosts = int(_pick(n_hosts, cfg.n_hosts))
        if self.n_hosts < 1:
            raise ValueError(
                f"n_hosts={self.n_hosts} must be >= 1; fix by passing a "
                "positive host count (1 behaves like a single scheduler)")
        self.cfg = dataclasses.replace(cfg, n_hosts=self.n_hosts)
        self.model = model
        self.placement: PlacementPolicy = make_placement(
            _pick(placement, cfg.placement))
        # the router's own ordering policy: same select_admit_unit
        # semantics as host admission, applied to the cross-host queue
        self.policy = _clone_policy(cfg.policy)
        self.parallel_hosts = bool(parallel_hosts) and self.n_hosts > 1

        # per-host page budget: cfg.num_blocks is the TOTAL fleet budget,
        # split as evenly as pages allow (first hosts take the remainder)
        shares: List[Optional[int]] = [None] * self.n_hosts
        if cfg.num_blocks:
            per, rem = divmod(int(cfg.num_blocks), self.n_hosts)
            if per < 1:
                raise ValueError(
                    f"num_blocks={cfg.num_blocks} split across "
                    f"{self.n_hosts} hosts leaves a host with an empty "
                    "pool; fix by raising num_blocks to >= "
                    f"{self.n_hosts} or lowering n_hosts")
            shares = [per + (1 if i < rem else 0)
                      for i in range(self.n_hosts)]
        # ONE shared draft cache for the whole fleet (prefix-registry
        # style): a continuation accepted on any host drafts for every
        # other host's traffic.  Host-locally safe — hosts step in
        # threads but lookups/promotions happen in the scheduler's
        # host-side composer/collection, and the cache is pure Python
        spec_on = bool(cfg.spec_tokens or cfg.spec_tree)
        self.draft_cache: Optional[DraftCache] = (
            DraftCache(capacity=cfg.draft_cache_size)
            if spec_on and cfg.draft_cache_size
            and getattr(model, "self_draft", False) else None)
        # host i lives on local device i (round robin when hosts outnumber
        # devices): its own params, KV pool and probe state, so each host's
        # jitted step runs on its own chip
        devices = jax.local_devices()
        self.hosts: List[OrcaScheduler] = []
        for i, share in enumerate(shares):
            host_cfg = dataclasses.replace(
                cfg, n_hosts=1, num_blocks=share,
                policy=_clone_policy(cfg.policy))
            self.hosts.append(OrcaScheduler(
                model, params, probe_config, theta, host_cfg,
                draft_cache=self.draft_cache,
                device=devices[i % len(devices)]))
        # mirror the resolved single-host attributes callers introspect
        h0 = self.hosts[0]
        self.n_slots = h0.n_slots            # PER HOST
        self.paged = h0.paged
        self.block_size = h0.block_size
        self.prefix_sharing = h0.prefix_sharing
        self.consensus = h0.consensus
        self.group_size = cfg.group_size
        self._pool = (ThreadPoolExecutor(
            max_workers=self.n_hosts,
            thread_name_prefix="fleet-host")
            if self.parallel_hosts else None)
        self._session_open = False
        self._reset_session()

    # ------------------------------------------------------------------
    def _reset_session(self) -> None:
        self._queue: List[List[Request]] = []    # unplaced admission units
        self._population: List[Request] = []     # every submitted request
        self._prefix_home: Dict[str, int] = {}   # prompt hash -> host
        self._steps = 0
        self._routed_affine = 0
        self._t0 = time.perf_counter()

    @property
    def has_work(self) -> bool:
        """True while any request is unplaced, queued, swapped or
        resident on any host."""
        return bool(self._queue) or any(h.has_work for h in self.hosts)

    @property
    def groups(self) -> List[RequestGroup]:
        """Consensus outcomes across the fleet (host-owned groups)."""
        out: List[RequestGroup] = []
        for h in self.hosts:
            out.extend(h.groups)
        return out

    def pressures(self) -> List[HostPressure]:
        """The per-host gossip the placement policy consumes."""
        return [h.pressure(i) for i, h in enumerate(self.hosts)]

    # ------------------------------------------------------------------
    def prepare(self, requests: Sequence[Request]) -> None:
        """Size every host's engine/pool for ``requests`` (cumulative with
        earlier submissions) without enqueueing them."""
        if not self._session_open:
            self._reset_session()
            self._session_open = True
        self._population.extend(requests)
        for h in self.hosts:
            h.prepare(self._population)

    def submit(self, requests: Sequence[Request]) -> None:
        """Enqueue ``requests`` and place them onto hosts (eagerly: the
        placement queue fully drains, by total-capacity feasibility, so a
        unit no host can EVER fit raises instead of waiting forever)."""
        requests = list(requests)
        fresh = not self._session_open
        if fresh:
            self._reset_session()
            self._session_open = True
        if not requests:
            return
        self._population.extend(requests)
        # every host sizes for the full population up front: placement
        # must never trigger a mid-flight engine rebuild on a busy host
        for h in self.hosts:
            h.prepare(self._population)
        units, groups = group_requests(requests)
        for grp in groups:
            if grp.size > self.n_slots:
                raise ValueError(
                    f"group {grp.group_id} has {grp.size} samples but "
                    f"each host has {self.n_slots} slots: a gang is "
                    "never split across hosts, so the whole group must "
                    "fit one host; fix by raising n_slots to >= "
                    f"{grp.size} or lowering the group size")
        if fresh:
            self._t0 = time.perf_counter()
        self._queue.extend(units)
        self._place()

    def run(self, requests: Sequence[Request]
            ) -> Tuple[List[Request], FleetMetrics]:
        """One-shot facade: submit + drain (same contract as the
        scheduler's ``run``)."""
        if self._session_open and self.has_work:
            raise RuntimeError(
                "run() while a fleet session is active would reset "
                "resident state; drive incremental traffic through "
                "submit()/step()/drain() instead")
        self._session_open = False
        self.submit(requests)
        return self.drain()

    # ------------------------------------------------------------------
    def _affinity_key(self, req: Request) -> Optional[str]:
        """The prompt hash the prefix registry would file this request
        under — computed router-side (same conditions as the scheduler's
        ``_sharing_key``, without needing a live engine)."""
        if not (self.paged and self.prefix_sharing
                and self.model.supports_paged):
            return None
        if set(req.inputs) != {"tokens"}:
            return None
        if prefix_len(self.model.cfg, req.inputs, req.prompt_len) \
                != req.prompt_len:
            return None
        return prompt_key(np.asarray(req.inputs["tokens"]))

    def _place(self) -> None:
        """Drain the placement queue: the router policy picks the next
        unit (same priority/aging/gang semantics as host admission), the
        placement policy picks its host from the gossiped pressures."""
        while self._queue:
            pressures = self.pressures()
            cand = self._queue
            sel = self.policy.select_admit_unit(cand, self._steps)
            unit = cand[sel]
            members = [r for r in unit if not r.done]
            if not members:          # fully cancelled before placement
                del self._queue[sel]
                continue
            need_pages = 0
            if self.paged:
                need_pages = sum(self.hosts[0]._request_blocks(r)
                                 for r in members)
            key = self._affinity_key(members[0])
            affine = self._prefix_home.get(key) if key else None
            host_idx = self.placement.select_host(
                members, pressures, need_slots=len(members),
                need_pages=need_pages, affine_host=affine)
            if host_idx is None:
                what = (f"group {members[0].group_id}"
                        if members[0].group_id is not None
                        else f"request {members[0].req_id}")
                raise RuntimeError(
                    f"{what} needs {len(members)} slots and "
                    f"{need_pages} pages but no host can ever fit it "
                    f"(per-host: {self.n_slots} slots, "
                    f"{pressures[0].pool_blocks} pages); fix by raising "
                    "n_slots/num_blocks or lowering the group size")
            self.policy.on_admitted_unit(cand, sel)
            del self._queue[sel]
            if affine is not None and host_idx == affine:
                self._routed_affine += 1
            if key is not None and key not in self._prefix_home:
                self._prefix_home[key] = host_idx
            for r in members:
                r.host = host_idx
            self.hosts[host_idx].submit(members)

    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One fleet iteration: place unrouted units, then step every
        host with work — concurrently when ``parallel_hosts`` (the jitted
        fused step releases the GIL).  Returns False when the fleet is
        idle."""
        if not self.has_work:
            return False
        self._place()
        active = [h for h in self.hosts if h.has_work]
        if self._pool is not None and len(active) > 1:
            list(self._pool.map(lambda h: h.step(), active))
        else:
            for h in active:
                h.step()
        self._steps += 1
        return True

    def drain(self) -> Tuple[List[Request], FleetMetrics]:
        """Step until every host is idle; return all requests (submission
        order) + fleet-aggregated metrics."""
        while self.step():
            pass
        wall = max(time.perf_counter() - self._t0, 1e-9)
        per_host = [h.drain() for h in self.hosts]  # hosts idle: metrics only
        metrics = self._aggregate([m for _, m in per_host], wall)
        requests = list(self._population)
        self._session_open = False
        return requests, metrics

    # ------------------------------------------------------------------
    def _aggregate(self, host_metrics: List[FleetMetrics],
                   wall: float) -> FleetMetrics:
        """Fleet-level FleetMetrics: counters sum, rates recompute over
        the union at the FLEET wall clock, percentiles recompute over the
        request union (never averaged across hosts — wrong for tails)."""
        requests = self._population
        n = len(requests)
        total_tokens = sum(len(r.tokens) for r in requests)
        sav = [r.savings(self.cfg.tokens_per_step, self.cfg.max_new_tokens)
               for r in requests]
        queue = [r.queue_steps for r in requests]
        ttft_p50, ttft_p99, per_class = latency_stats(list(requests))
        steps = self._steps
        active = sum(m.active_slot_steps for m in host_metrics)
        fired_steps = [(m.consensus_steps, m.consensus_groups)
                       for m in host_metrics if m.consensus_groups]
        n_fired = sum(k for _, k in fired_steps)
        groups = [g for g in self.groups if g.size >= 2]
        tps, dmn = self.cfg.tokens_per_step, self.cfg.max_new_tokens
        g_sav = [g.savings(tps, dmn) for g in groups]
        # speculative acceptance over the request UNION via the ONE
        # shared helper the scheduler's _metrics also calls (counters sum
        # via the requests themselves; percentiles recompute over the
        # union, never averaged across hosts)
        return FleetMetrics(
            **spec_stats(list(requests)),
            n_requests=n, n_slots=self.n_slots, engine_steps=steps,
            active_slot_steps=active, wall_time_s=wall,
            requests_per_s=n / wall, tokens_per_s=total_tokens / wall,
            slot_utilization=(active / max(steps * self.n_slots
                                           * self.n_hosts, 1)),
            mean_step_savings=float(np.mean(sav)) if sav else 0.0,
            mean_queue_steps=float(np.mean(queue)) if queue else 0.0,
            pool_blocks=sum(m.pool_blocks for m in host_metrics),
            peak_blocks_in_use=sum(m.peak_blocks_in_use
                                   for m in host_metrics),
            prefill_skips=sum(m.prefill_skips for m in host_metrics),
            ttft_ms_p50=ttft_p50, ttft_ms_p99=ttft_p99,
            # stalls are per-host step latencies; the fleet tail is the
            # worst host (hosts step concurrently)
            stall_ms_p50=max(m.stall_ms_p50 for m in host_metrics),
            stall_ms_p99=max(m.stall_ms_p99 for m in host_metrics),
            prefill_chunks=sum(m.prefill_chunks for m in host_metrics),
            packed_chunks=sum(m.packed_chunks for m in host_metrics),
            peak_step_tokens=max(m.peak_step_tokens
                                 for m in host_metrics),
            per_class=per_class,
            samples_cancelled=sum(m.samples_cancelled
                                  for m in host_metrics),
            consensus_groups=n_fired,
            consensus_steps=(sum(s * k for s, k in fired_steps)
                             / n_fired if n_fired else 0.0),
            group_savings=sum(m.group_savings for m in host_metrics),
            group_savings_mean=float(np.mean(g_sav)) if g_sav else 0.0,
            cancel_freed_blocks=sum(m.cancel_freed_blocks
                                    for m in host_metrics),
            preemptions=sum(m.preemptions for m in host_metrics),
            restores=sum(m.restores for m in host_metrics),
            spilled_blocks=sum(m.spilled_blocks for m in host_metrics),
            n_hosts=self.n_hosts, routed_affine=self._routed_affine)
