"""From a profiler trace to busy time, idle gaps and kernel time.

``load`` turns the ``.xplane.pb`` the JAX profiler writes into plain event
tuples; ``reduce`` works on those tuples alone, so the tests check it on a
recorded trace without a chip.  Device events are the ``XLA Ops`` lines of
the device planes; host events are the benchmark's own ``bench.*`` spans.
Busy time is the union of the device events of each chip, averaged over
the chips; an idle gap is a hole in that union, named by the innermost
benchmark span that covers its middle.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple

DEVICE_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


class Event(NamedTuple):
    kind: str            # "device" or "host"
    where: str           # plane name (device) or thread line (host)
    name: str
    start_ns: float
    end_ns: float


def op_name(text: str) -> str:
    """The HLO op's name from the trace's event text:
    ``"%paged_flash_decode.6 = (f32[...]) custom-call(...)"`` ->
    ``"paged_flash_decode.6"``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def load(trace_dir: str) -> List[Event]:
    """Every device op and every benchmark span of the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != DEVICE_LINE:
                continue
            for ev in line.events:
                if device:
                    out.append(Event("device", plane.name, op_name(ev.name),
                                     ev.start_ns, ev.end_ns))
                elif ev.name.startswith(SPAN_PREFIX):
                    out.append(Event("host", line.name, ev.name,
                                     ev.start_ns, ev.end_ns))
    return out


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _clip(iv, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in iv if e > t0 and s < t1]


def _self_times(evs: List[Tuple[float, float, str]]
                ) -> List[Tuple[str, float]]:
    """Each op's duration less that of the ops nested in it (a ``while``
    of the layer loop holds the kernels it runs), so that summed self
    times count every device nanosecond once."""
    out: List[Tuple[str, float]] = []
    stack: List[list] = []              # [end, name, child_ns, start]
    for s, e, name in sorted(evs, key=lambda x: (x[0], -x[1])):
        # an op that ends past the open one is its sibling, not its child
        while stack and (stack[-1][0] <= s or e > stack[-1][0]):
            end, n, child, st = stack.pop()
            out.append((n, end - st - child))
        if stack:
            stack[-1][2] += e - s
        stack.append([e, name, 0.0, s])
    while stack:
        end, n, child, st = stack.pop()
        out.append((n, end - st - child))
    return out


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                       # averaged over the chips
    device_ops: List[Tuple[str, float]]  # top ops by summed self seconds
    idle_gaps: List[Tuple[str, float]]   # longest holes, named by span
    op_seconds: Dict[str, float]         # op name -> seconds (with nested)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_seconds(self, kernel: str) -> float:
        """Device seconds of every op named ``kernel`` or ``kernel.<n>``."""
        return sum(s for name, s in self.op_seconds.items()
                   if name == kernel or name.startswith(kernel + "."))


def reduce(events: List[Event], window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> Reduction:
    """Reduce the events inside ``window`` (ns; default: from the first to
    the last ``bench.step`` span)."""
    host = [e for e in events if e.kind == "host"]
    dev = [e for e in events if e.kind == "device"]
    if window is None:
        steps = [e for e in host if e.name == "bench.step"]
        if not steps:
            raise ValueError("no bench.step span in the trace")
        window = (min(e.start_ns for e in steps), max(e.end_ns for e in steps))
    t0, t1 = window
    per_dev: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    named: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    op_ns: Dict[str, float] = defaultdict(float)
    for e in dev:
        iv = _clip([(e.start_ns, e.end_ns)], t0, t1)
        if not iv:
            continue
        per_dev[e.where].append(iv[0])
        named[e.where].append((iv[0][0], iv[0][1], e.name))
        op_ns[e.name] += iv[0][1] - iv[0][0]
    if not per_dev:
        raise ValueError("no device op inside the traced window")
    busy = [_union(iv) for iv in per_dev.values()]
    busy_ns = sum(sum(e - s for s, e in u) for u in busy) / len(busy)
    gaps: List[Tuple[str, float]] = []
    for u in busy:
        edges = [(t0, t0)] + u + [(t1, t1)]
        for (_, a), (b, _) in zip(edges[:-1], edges[1:]):
            if b > a:
                gaps.append((_span_at(host, (a + b) / 2), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    self_ns: Dict[str, float] = defaultdict(float)
    for evs in named.values():
        for n, ns in _self_times(evs):
            self_ns[n] += ns
    ops = sorted(((n, s * 1e-9 / len(busy)) for n, s in self_ns.items()),
                 key=lambda o: -o[1])
    return Reduction(window_s=(t1 - t0) * 1e-9, busy_s=busy_ns * 1e-9,
                     device_ops=ops[:top], idle_gaps=gaps[:top],
                     op_seconds={n: s * 1e-9 / len(busy)
                                 for n, s in op_ns.items()})


def _span_at(host: List[Event], t: float) -> str:
    """The innermost (shortest) benchmark span covering time ``t``."""
    cover = [e for e in host if e.start_ns <= t <= e.end_ns]
    if not cover:
        return "outside bench spans"
    return min(cover, key=lambda e: e.end_ns - e.start_ns).name
