"""Host spans and per-step counters of the serving loop.

Every ``OrcaScheduler.step`` is one ``StepRecord`` (a bare
``ContinuousServingEngine.step`` opens its own): the ``orca.*`` host spans
timed inside it and the step's counters.  Each span stamps
``time.perf_counter()`` into the record and enters a
``jax.profiler.TraceAnnotation``, which costs next to nothing with the
profiler off and, with it on, puts the span in the trace on the device
ops' clock.  The device half of the step runs under
``jax.named_scope("orca/step")`` and names its serving phases
``orca/...``; the model's phases below it carry plain names (``layers``,
``decode_attention``, ``kv_write``, ``mlp``, ``lm_head``).

Spans, outermost first: ``orca.step`` (the step index), ``orca.admit``
(the admitted request ids), ``orca.compose``, ``orca.upload``,
``orca.dispatch``, ``orca.wait``, ``orca.readback``, ``orca.collect``,
``orca.prefill_done`` and ``orca.consensus``.  Counters (``COUNTERS``) are
whole numbers per step.

The last ``CAPACITY`` records are kept.  For the whole session every
counter's per-step distribution and every step's wall time are kept too,
so the scheduler's ``FleetMetrics`` tallies (slot-steps, chunk launches,
peak step tokens) and its stall tails come from here.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from array import array
from collections import Counter, deque
from typing import Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

CAPACITY = 4096      # step records kept: a 30 s window of steps over 7.3 ms

COUNTERS = (          # and what reads each
    "decode_rows",       # resident requests the step decoded:
    #                      FleetMetrics.active_slot_steps, slot_utilization
    "step_tokens",       # tokens the step carries, decode, drafts and
    #                      prompt: FleetMetrics.peak_step_tokens
    "prefill_segments",  # requests the packed chunk carries:
    #                      FleetMetrics.prefill_chunks, packed_chunks
    "reads",             # blocking device->host reads of the step's
    #                      outputs: the benchmark's syncs.decode
    "attn_blocks_live",  # paged decode kernel: compute blocks per layer
    "attn_blocks",       # that ran / that a walk of every block-table
    #                      entry would launch, over the rows with
    #                      context: the benchmark's attn_live_share.decode
)


@dataclasses.dataclass
class StepRecord:
    """One serving step: host spans (name, t0, t1) on the
    ``time.perf_counter`` clock, and counters."""
    index: int
    t0: float
    t1: float = 0.0
    spans: List[Tuple[str, float, float]] = dataclasses.field(
        default_factory=list)
    counts: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def span_seconds(self, name: str) -> float:
        return sum(e - s for n, s, e in self.spans if n == name)


class StepRecorder:
    """The step records of one serving loop (one per scheduler)."""

    def __init__(self):
        self.records: deque = deque(maxlen=CAPACITY)
        self.current: Optional[StepRecord] = None
        self._steps = 0
        self._dist: Dict[str, Counter] = {}
        self._step_ms = array("d")

    def reset(self) -> None:
        """Start a new session: no records, no counts."""
        self.records.clear()
        self._steps = 0
        self._dist = {}
        self._step_ms = array("d")

    @contextlib.contextmanager
    def step(self) -> Iterator[StepRecord]:
        """Record one step; inside an open step, that step."""
        if self.current is not None:
            yield self.current
            return
        rec = StepRecord(self._steps, time.perf_counter())
        self.current = rec
        try:
            with TraceAnnotation("orca.step", step=rec.index):
                yield rec
        finally:
            rec.t1 = time.perf_counter()
            self.current = None
            self._steps += 1
            self.records.append(rec)
            self._step_ms.append(rec.seconds * 1e3)
            for name, n in rec.counts.items():
                self._dist.setdefault(name, Counter())[n] += 1

    @contextlib.contextmanager
    def span(self, name: str) -> Iterator[TraceAnnotation]:
        """Time ``name`` inside the open step; yields the annotation, whose
        ``set_metadata`` labels it in the trace."""
        t0 = time.perf_counter()
        with TraceAnnotation(name) as ann:
            yield ann
        self.current.spans.append((name, t0, time.perf_counter()))

    def count(self, name: str, n: int = 1) -> None:
        counts = self.current.counts
        counts[name] = counts.get(name, 0) + int(n)

    # session tallies -------------------------------------------------
    def total(self, name: str) -> int:
        return sum(v * k for v, k in self._dist.get(name, {}).items())

    def peak(self, name: str) -> int:
        return max(self._dist.get(name, {0: 0}), default=0)

    def steps_with(self, name: str, at_least: int = 1) -> int:
        """Steps of the session whose ``name`` counter reached
        ``at_least``."""
        return sum(k for v, k in self._dist.get(name, {}).items()
                   if v >= at_least)

    def step_ms(self) -> array:
        """Wall time of each step of the session, in ms."""
        return self._step_ms
