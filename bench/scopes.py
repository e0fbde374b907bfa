"""Device time by the program's phase scopes, host time by its ``orca.*``
spans, in the traced window of one run.

The program names each phase of its fused serving step with
``jax.named_scope``: ``orca/step`` around the whole step and, below it, the
serving phases ``orca/chunk_prefill``, ``orca/probe``, ``orca/verify`` and
``orca/lm_head`` and the model's phases ``layers`` (the layer loop: its
per-layer slices of the stacked weights and page pools, norms,
projections), ``decode_attention``, ``kv_write``, ``mlp`` and ``lm_head``.
A scope path lists the phases of an op below ``orca/step``, outermost
first, with or without their ``orca/`` prefix: ``("step", "layers",
"decode_attention")``.  The ``XLA Ops`` events of a TPU trace carry an
op's HLO name but not its metadata, so each op's scope comes from the
compiled step's HLO text, which the engine gives
(``ContinuousServingEngine.compiled_step_text``): the op's own
``op_name``, else that of its first operand that has one, else that of the
op that calls its computation (the copies XLA adds carry no metadata).
Ops that ran inside another program's execution (the ``XLA Modules``
line) are kept apart.  The window is ``bench.trace``'s, from the first to
the last ``bench.step``; self times come from ``bench.trace._self_times``,
so no device nanosecond counts twice.  Idle gaps are named by the
innermost ``orca.*`` span covering them (``bench.step`` where none does).

The program's own step records (``time.perf_counter``, the harness's
clock) give the host-side metrics: ``window_records`` keeps the records of
the window's steps.

    python3 -m bench.scopes .bench_trace/<workload>

prints the breakdown of the newest traced run of that cell.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import trace as TR

STEP_HLO = "step_hlo.txt"            # the compiled step, beside the trace
MODULES_LINE = "XLA Modules"

_HEADER = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+) ")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|true_computation|"
                     r"false_computation)=%([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
# the phases below orca/step, as the program names them
PHASES = ("chunk_prefill", "layers", "decode_attention", "kv_write", "mlp",
          "lm_head", "probe", "verify")

Path = Tuple[str, ...]


def phase_path(op_name: str) -> Path:
    """The scope path of one ``op_name`` (its first name only): ``step``
    and the phases below ``orca/step``; ``()`` outside the step."""
    parts = op_name.split(";")[0].split("/")
    for i in range(len(parts) - 1):
        if parts[i] == "orca" and parts[i + 1] == "step":
            return ("step",) + tuple(p for p in parts[i + 2:]
                                     if p in PHASES)
    return ()


def scope_map(hlo_text: str) -> Tuple[str, Dict[str, Path]]:
    """(module name, {op name: scope path}) of a compiled HLO text; a
    path lists the phases outermost first, e.g. ``("step", "layers",
    "decode_attention")``; ``()`` for an op with none."""
    module = ""
    comp = None
    ops: Dict[str, Tuple[str, Path, List[str]]] = {}  # name -> comp, own, refs
    caller: Dict[str, str] = {}                       # computation -> op
    for line in hlo_text.splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            continue
        m = _INSTR.match(line)
        if m is None:
            h = _HEADER.match(line)
            if h is not None and line.rstrip().endswith("{"):
                comp = h.group(1)
            continue
        name, rest = m.groups()
        own = _OP_NAME.search(rest)
        path = phase_path(own.group(1)) if own else ()
        ops[name] = (comp, path, _REF.findall(rest))
        for c in _CALLED.finditer(rest):
            for callee in ([c.group(1)] if c.group(1)
                           else _REF.findall(c.group(2))):
                caller[callee] = name
    # an op with no scope of its own takes its first scoped operand's,
    # else its caller's; repeated until nothing changes (a caller's scope
    # may itself come from its own caller)
    path = {name: own for name, (_, own, _) in ops.items()}
    changed = True
    while changed:
        changed = False
        for name, (comp_, _, refs) in ops.items():
            if path[name]:
                continue
            got = next((path[r] for r in refs if r in ops
                        and ops[r][0] == comp_ and path[r]), ())
            if not got and comp_ in caller:
                got = path[caller[comp_]]
            if got:
                path[name] = got
                changed = True
    return module, path


def load(trace_dir: str) -> List[TR.Event]:
    """Device ops, program executions (kind ``"module"``) and the
    ``orca.*`` and ``bench.step`` host spans of the newest trace."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out: List[TR.Event] = []
    for plane in ProfileData.from_file(files[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name in (TR.DEVICE_LINE, MODULES_LINE):
                kind = "device" if line.name == TR.DEVICE_LINE else "module"
                for ev in line.events:
                    name = TR.op_name(ev.name) if kind == "device" \
                        else ev.name.split("(")[0]
                    out.append(TR.Event(kind, plane.name, name,
                                        ev.start_ns, ev.end_ns))
            elif not device:
                out.extend(TR.Event("host", line.name, ev.name,
                                    ev.start_ns, ev.end_ns)
                           for ev in line.events
                           if ev.name.startswith("orca.")
                           or ev.name == "bench.step")
    return out


@dataclasses.dataclass
class Scoped:
    window_s: float
    steps: int                        # bench.step spans in the window
    busy_s: float                     # summed self time, every program
    by_path: Dict[Path, float]        # the step's self seconds by scope path
    other_s: float                    # self seconds of other programs
    outside: List[Tuple[str, float]]  # step ops under no scope below step
    idle_gaps: List[Tuple[str, float]]
    host_s: Dict[str, float]          # orca.* span seconds in the window

    def under(self, scope: str) -> float:
        """Device self seconds of the step's ops inside phase ``scope``."""
        return sum(s for p, s in self.by_path.items() if scope in p)

    def innermost(self) -> Dict[str, float]:
        out: Dict[str, float] = defaultdict(float)
        for p, s in self.by_path.items():
            out["/".join(p[-1:]) or "(no scope)"] += s
        return dict(out)

    @property
    def covered(self) -> float:
        """Share of busy time under a phase below ``orca/step``."""
        inner = sum(s for p, s in self.by_path.items()
                    if "step" in p and len(p) >= 2)
        return inner / self.busy_s if self.busy_s else 0.0


def reduce(events: List[TR.Event], module: str, scopes: Dict[str, Path],
           top: int = 10) -> Scoped:
    """Attribute the window's device self time to the step's scopes."""
    steps = [e for e in events if e.kind == "host" and e.name == "bench.step"]
    if not steps:
        raise ValueError("no bench.step span in the trace")
    t0, t1 = min(e.start_ns for e in steps), max(e.end_ns for e in steps)
    runs: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    for e in events:
        if e.kind == "module":
            runs[e.where].append((e.start_ns, e.end_ns, e.name))
    for r in runs.values():
        r.sort()
    mine: Dict[str, list] = defaultdict(list)
    others: Dict[str, list] = defaultdict(list)
    for e in events:
        if e.kind != "device" or e.end_ns <= t0 or e.start_ns >= t1:
            continue
        iv = (max(e.start_ns, t0), min(e.end_ns, t1), e.name)
        r = runs.get(e.where, [])
        i = bisect.bisect_right(r, (e.start_ns, float("inf"), "")) - 1
        in_step = i >= 0 and r[i][1] > e.start_ns and r[i][2] == module
        (mine if in_step else others)[e.where].append(iv)
    n_dev = max(len(set(mine) | set(others)), 1)
    by_path: Dict[Path, float] = defaultdict(float)
    outside: Dict[str, float] = defaultdict(float)
    for evs in mine.values():
        for name, ns in TR._self_times(evs):
            p = scopes.get(name, ())
            by_path[p] += ns * 1e-9 / n_dev
            if not ("step" in p and len(p) >= 2):
                outside[name] += ns * 1e-9 / n_dev
    other_s = sum(ns for evs in others.values()
                  for _, ns in TR._self_times(evs)) * 1e-9 / n_dev
    host = [e for e in events if e.kind == "host"]
    host_s: Dict[str, float] = defaultdict(float)
    for e in host:
        if e.name.startswith("orca.") and e.start_ns >= t0 \
                and e.end_ns <= t1:
            host_s[e.name] += (e.end_ns - e.start_ns) * 1e-9
    gaps = TR.reduce([e for e in events if e.kind == "device"] + host,
                     top=top).idle_gaps
    return Scoped(window_s=(t1 - t0) * 1e-9, steps=len(steps),
                  busy_s=sum(by_path.values()) + other_s,
                  by_path=dict(by_path), other_s=other_s,
                  outside=sorted(outside.items(), key=lambda o: -o[1])[:top],
                  idle_gaps=gaps, host_s=dict(host_s))


def run_state(ctx) -> Tuple[object, Optional[str]]:
    """(the scheduler, the trace directory) of the run whose readers get
    ``ctx``: its fields ``program`` and ``trace_dir``, None where absent."""
    tdir = getattr(ctx, "trace_dir", None)
    return getattr(ctx, "program", None), None if tdir is None else str(tdir)


def from_ctx(ctx) -> Optional[Scoped]:
    """The scoped reduction of a traced run's window, or None when the run
    lacks the trace, the program's compiled step text (a program without
    it) or device ops (a run on the CPU).  Computed once per run and kept
    on ``ctx``; writes the step's HLO text beside the trace for
    ``python3 -m bench.scopes``."""
    if not hasattr(ctx, "scoped"):
        ctx.scoped = _reduce_run(*run_state(ctx))
    return ctx.scoped


def _reduce_run(program, tdir: Optional[str]) -> Optional[Scoped]:
    engine = getattr(program, "_engine", None)
    if tdir is None or not hasattr(engine, "compiled_step_text"):
        return None
    events = load(tdir)
    if not any(e.kind == "device" for e in events):
        return None
    text = engine.compiled_step_text()
    with open(os.path.join(tdir, STEP_HLO), "w") as f:
        f.write(text)
    module, scopes = scope_map(text)
    return reduce(events, module, scopes)


def window_records(ctx) -> list:
    """The program's step records of the window's steps (none for a
    program without a step recorder)."""
    recorder = getattr(run_state(ctx)[0], "recorder", None)
    if recorder is None or not recorder.records:
        return []
    w = ctx.window
    return [r for r in recorder.records
            if r.t0 >= w.t_start and r.t1 <= w.t_end]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: python3 -m bench.scopes .bench_trace/<workload>",
              file=sys.stderr)
        return 2
    tdir = argv[0]
    with open(os.path.join(tdir, STEP_HLO)) as f:
        module, scopes = scope_map(f.read())
    red = reduce(load(tdir), module, scopes, top=15)
    n = max(red.steps, 1)
    print(f"window {red.window_s:.4f} s, {red.steps} steps, busy "
          f"{red.busy_s:.4f} s ({red.other_s:.4f} s in other programs); "
          f"{100 * red.covered:.2f}% of busy under a phase below "
          "orca/step")
    print("device ms per step by innermost scope:")
    for name, s in sorted(red.innermost().items(), key=lambda x: -x[1]):
        print(f"  {name:24s} {1e3 * s / n:10.3f}  "
              f"{100 * s / red.busy_s:6.2f}%")
    print("device ms per step under each scope:")
    names = sorted({x for p in red.by_path for x in p})
    for name in names:
        print(f"  {name:24s} {1e3 * red.under(name) / n:10.3f}")
    print("step ops under no scope below orca/step (ms per step):")
    for name, s in red.outside:
        print(f"  {name:40s} {1e3 * s / n:10.4f}")
    print("host ms per step by span:")
    for name, s in sorted(red.host_s.items(), key=lambda x: -x[1]):
        print(f"  {name:20s} {1e3 * s / n:10.4f}")
    print("longest idle gaps (ms), by innermost span:")
    for name, s in red.idle_gaps:
        print(f"  {name:20s} {1e3 * s:10.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
