"""Set-up, warm-up and the measured window of one cell.

The window drives the program's normal serving path:
``repro.api.engine(...)`` builds an ``OrcaScheduler`` from a ``ServeConfig``
with paged KV and chunked prefill, and every iteration is one
``OrcaScheduler.step()``.  The benchmark is a closed loop of clients around
it: when a client's request completes, its next one is submitted before
the next step.  Every timestamp is the host clock.  A traced run traces the
window itself, so its per-layer metrics describe the same steps as the
window's rate.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from bench import traffic as T

# the program's events for a new executable: a backend compile, or one read
# back from the persistent cache; either inside the window is a fault
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


class CompileCounter:
    """Counts the process's compile events from the moment it is made."""

    def __init__(self):
        import jax
        self.n = 0

        def on_event(name, *_a, **_k):
            if name in COMPILE_EVENTS:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on_event)


@dataclasses.dataclass
class StepRecord:
    t0: float
    t1: float
    decode_cached: List[int]            # cached positions of each token row
    chunk_segs: List[Tuple[int, int]]   # (start, length) of prefill segments


@dataclasses.dataclass
class Served:
    """What one request was given: enough for the reference to redo it."""
    context: np.ndarray        # tokens prefilled for it
    tokens: List[int]          # tokens served
    scores: List[float]        # smoothed probe scores at each boundary


@dataclasses.dataclass
class Window:
    t_start: float
    t_end: float
    steps: List[StepRecord]
    n_slots: int
    tokens_out: int
    compiles: int
    attempted: int
    completed: int
    first_completion: int      # window step at which a request first ends

    @property
    def seconds(self) -> float:
        return self.t_end - self.t_start

    def step_ms(self) -> float:
        return 1e3 * self.seconds / max(len(self.steps), 1)


class Loop:
    """The closed loop of one cell around one scheduler."""

    def __init__(self, sched, traffic: T.Traffic, make_request: Callable):
        self.sched, self.traffic = sched, traffic
        self.make_request = make_request
        self.clients: Dict[int, object] = {}
        self.index: Dict[int, int] = {}
        self.items: Dict[int, T.Item] = {}          # req_id -> item
        self.seen_tok: Dict[int, int] = {}
        self.seen_pre: Dict[int, int] = {}
        self.requests: List[object] = []
        self.completions = 0

    def _submit(self, item: T.Item) -> None:
        req = self.make_request(item)
        self.items[req.req_id] = item
        self.clients[item.client] = req
        self.index[item.client] = item.index
        self.seen_tok[req.req_id] = 0
        self.seen_pre[req.req_id] = 0
        self.requests.append(req)
        self.sched.submit([req])

    def start(self) -> None:
        for c in range(self.traffic.clients):
            self._submit(self.traffic.first(c))

    def step(self) -> StepRecord:
        """One scheduler step, then the closed loop's resubmissions."""
        live = list(self.clients.values())
        t0 = time.perf_counter()
        self.sched.step()
        t1 = time.perf_counter()
        cached, segs = [], []
        for r in live:
            rid = r.req_id
            pre = r.prefill_progress
            if pre > self.seen_pre[rid]:
                segs.append((self.seen_pre[rid], pre - self.seen_pre[rid]))
                self.seen_pre[rid] = pre
            # the row fed token j-1 (token 0 for j = 0) at position
            # context + j, reading the context + j cached before it
            n = len(r.tokens)
            cached.extend(r.prompt_len + j
                          for j in range(self.seen_tok[rid], n))
            self.seen_tok[rid] = n
        for c, r in list(self.clients.items()):
            if r.done:
                self.completions += 1
                self._submit(self.traffic.next(c, self.index[c] + 1))
        return StepRecord(t0, t1, cached, segs)

    def served(self) -> List[Served]:
        return [Served(np.asarray(self.items[r.req_id].tokens),
                       list(r.tokens), list(r.scores))
                for r in self.requests]


def warm_up(loop: Loop) -> int:
    """Step until every client's first request, a continuation, has its
    context in the cache; returns the steps it took."""
    from repro.serving.request import RequestState
    steps = 0
    while not all(r.state is RequestState.RUNNING
                  for r in loop.clients.values()):
        loop.step()
        steps += 1
        if steps > 100_000:
            raise RuntimeError("warm-up did not reach its steady state")
    return steps


def run_window(loop: Loop, seconds: float, counter: CompileCounter,
               trace_dir: Optional[str] = None):
    """Steps for ``seconds`` of host time.  The window closes at the end of
    the last step that began before the deadline; every token that landed
    inside it counts.  With ``trace_dir`` the window runs under the
    profiler, each step inside a ``bench.step`` span, and the trace's
    reduction over exactly those steps comes back beside the window."""
    import jax
    from bench import trace as TR
    steps: List[StepRecord] = []
    spans = host_spans(loop.sched) if trace_dir else contextlib.nullcontext()
    step_span = (jax.profiler.TraceAnnotation if trace_dir
                 else contextlib.nullcontext)
    with spans:
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        try:
            if trace_dir:
                # the first step under the profiler stalls the host for
                # about a second while the tracer starts: it runs before
                # the window, outside its spans
                loop.step()
            c0, done0 = counter.n, loop.completions
            first = min(r.max_new_tokens - len(r.tokens)
                        for r in loop.clients.values())
            t_start = time.perf_counter()
            while time.perf_counter() - t_start < seconds:
                with step_span("bench.step"):
                    steps.append(loop.step())
        finally:
            if trace_dir:
                jax.profiler.stop_trace()
    t_end = steps[-1].t1 if steps else time.perf_counter()
    attempted = sum(1 for r in loop.requests
                    if loop.seen_tok[r.req_id] or loop.seen_pre[r.req_id])
    window = Window(t_start=t_start, t_end=t_end, steps=steps,
                    n_slots=loop.sched.n_slots,
                    tokens_out=sum(len(s.decode_cached) for s in steps),
                    compiles=counter.n - c0, attempted=attempted,
                    completed=loop.completions - done0,
                    first_completion=first)
    return window, (TR.reduce(TR.load(trace_dir)) if trace_dir else None)


@contextlib.contextmanager
def host_spans(sched):
    """Benchmark spans around the program's calls, for naming idle gaps in
    the traced window: the scheduler step, and inside it the engine step,
    the lowering of the prefill chunk and the dispatch of the fused step
    (the rest of the engine step is the read-back to the host)."""
    import jax
    eng = sched._engine
    saved = {n: getattr(eng, n) for n in ("step", "_chunk_to_device",
                                          "_step_fn")}
    sched_step = sched.step

    def wrap(fn, name):
        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return inner

    sched.step = wrap(sched_step, "bench.scheduler_step")
    eng.step = wrap(saved["step"], "bench.engine_step")
    eng._chunk_to_device = wrap(saved["_chunk_to_device"],
                                "bench.chunk_to_device")
    eng._step_fn = wrap(saved["_step_fn"], "bench.dispatch")
    try:
        yield
    finally:
        for n, fn in saved.items():
            setattr(eng, n, fn)
        del sched.step
