"""Run one cell of the chip benchmark once.

    python3 -m bench.run --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

The cell is found by name in ``BENCHMARK.json``: its configuration file
under ``bench/configs``, which names its architecture's module under
``family`` (``bench.families``), its traffic mix under ``bench/traffic``,
its limits under ``bench/limits/<workload>.json`` and a reader under
``bench/metrics/<metric>.py`` for each of its per-layer metrics.

A run refuses (exit 1, no result) unless JAX's devices are TPUs, as many as
the cell asks for.  It makes the weights on the chip from the seed,
calibrates the probe on a seeded synthetic bank, warms the loop up, then
measures for ``--seconds``: the end-to-end metrics with ``--trace 0``; with
``--trace 1`` the per-layer metrics, from the same window run under the
profiler.  Then it frees the program's state and holds a sample of what
was served to the family's float32 reference.  The last lines on standard
error are the numbers compared, each beside its limit; the last line on
standard output is the result as one JSON object.
"""
from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
import typing  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, Optional  # noqa: E402

import numpy as np  # noqa: E402

CHECKOUT = Path(__file__).resolve().parents[1]
BENCH = CHECKOUT / "bench"


class Refused(Exception):
    """The run cannot measure what the cell asks for."""


def process_age() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# the cell, by name

def load_cell(workload: str) -> dict:
    spec = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json "
                      f"(known: {sorted(cells)})")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    e2e = [m for m in spec["end_to_end"]
           if workload in m.get("workloads", [workload])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if workload in m.get("workloads", [workload])
                 and m["moves"] in moved]
    return {
        "workload": w,
        "config": json.loads((CHECKOUT / conf["file"]).read_text()),
        "mix": json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                          .read_text()),
        "limits": json.loads((BENCH / "limits" / f"{workload}.json")
                             .read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def reader(metric: str) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# the end-to-end metrics the benchmark takes itself, from the window
END_TO_END = {
    "output_tok_s": lambda w: w.tokens_out / w.seconds,
}


# ---------------------------------------------------------------------------
# the program under test

def use_compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``,
    a fixed path; every program is cached, however short its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def model_config(conf: dict):
    """The program's ``ModelConfig`` from the file's ``program`` group,
    handed over whole; hashable, as the program needs it."""
    from repro.configs.base import ModelConfig
    return _build(ModelConfig, conf["program"])


def _build(cls, fields: dict):
    """``cls(**fields)`` with every JSON list as a tuple and every
    dataclass-typed field (``moe``, ``ssm``, ``frontend``) built from its
    dict."""
    hints = typing.get_type_hints(cls)
    out = {}
    for k, v in fields.items():
        hint = hints.get(k)
        sub = next((t for t in (hint,) + typing.get_args(hint)
                    if dataclasses.is_dataclass(t)), None)
        out[k] = _build(sub, v) if sub and isinstance(v, dict) \
            else _tuples(v)
    return cls(**out)


def _tuples(v):
    return tuple(_tuples(x) for x in v) if isinstance(v, list) else v


def calibrate(conf: dict, mix: dict, seed: int):
    """Meta-train the TTT probe and LTT-calibrate it on a seeded synthetic
    bank of trajectories at the model's width (set-up, not traffic)."""
    from repro import api as orca
    from repro.core.probe import ProbeConfig
    from repro.trajectories.synthetic import TrajectoryDistribution, generate
    p = mix["probe"]
    d = conf["program"]["d_model"]
    bank_seed = seed % 4_000_000          # generate() seeds a RandomState
    bank = generate(TrajectoryDistribution("bench", d_phi=d), p["bank"],
                    seed=bank_seed)
    half = p["bank"] // 2
    train = bank.subset(np.arange(half))
    cal = bank.subset(np.arange(half, p["bank"]))
    pc = ProbeConfig(d_phi=d, smooth_window=p["smooth_window"], eta=p["eta"])
    calib = orca.fit(train, mode="consistent", method="ttt", pc=pc,
                     epochs=p["epochs"], epoch_select=False, seed=bank_seed)
    lam = orca.calibrated_lambda(calib, cal, p["delta"])
    return calib, float(lam)


def build(cell: dict, seed: int, family, fault: Optional[Callable] = None):
    """Weights (made by ``family``), probe and scheduler; returns (sched,
    traffic, probe, make_request, phases)."""
    import jax
    from repro import api as orca
    from repro.models import build as build_model
    from repro.serving import ServeConfig
    from repro.serving.request import Request
    from bench import check as C
    from bench import traffic as T
    from bench import weights as W
    conf, mix = cell["config"], cell["mix"]
    cfg = conf["program"]
    phases = {}
    t = time.perf_counter()
    model = build_model(model_config(conf))
    params = W.make_program_params(family, cfg, seed,
                                   model.abstract_params())
    jax.block_until_ready(params)
    phases["weights"] = time.perf_counter() - t
    t = time.perf_counter()
    calib, lam = calibrate(conf, mix, seed)
    phases["calibrate"] = time.perf_counter() - t
    serve = dict(mix["serve"])
    traffic = T.Traffic(mix, cfg["vocab_size"], seed)
    defaults = ServeConfig()
    burn_in = T.burn_in_past(mix, serve["tokens_per_step"], defaults.burn_in)
    if burn_in is not None:
        serve["burn_in"] = burn_in
    serve["cache_len"] = traffic.max_context() + 1
    sched = orca.engine(model, params, calib,
                        config=ServeConfig(lam=lam, **serve))
    if fault is not None:
        fault(sched)
    pc, theta = calib.serving_params()
    probe = C.Probe(w0=np.asarray(theta["W0"], np.float64),
                    b0=float(np.asarray(theta["b0"])), eta=pc.eta,
                    window=pc.smooth_window,
                    tokens_per_step=serve["tokens_per_step"], lam=lam,
                    burn_in=defaults.burn_in)

    def make_request(item):
        return Request(inputs={"tokens": item.tokens[None]},
                       prompt_len=int(item.tokens.shape[0]),
                       max_new_tokens=int(item.served))
    return sched, traffic, probe, make_request, phases


def would_be_savings(served, probe) -> float:
    """Mean share of each request's served tokens after the step at which
    the calibrated test would have stopped it (stops are held off)."""
    from bench import probe_ref as PR
    out = []
    for s in served:
        if not s.scores:
            continue
        m = PR.stop_index(np.asarray(s.scores), probe.lam, probe.burn_in)
        cut = len(s.tokens) if m is None else m * probe.tokens_per_step
        out.append(1.0 - min(cut, len(s.tokens)) / len(s.tokens))
    return float(np.mean(out)) if out else 0.0


# ---------------------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_chip: bool = True, cell: Optional[dict] = None,
             fault: Optional[Callable] = None,
             control: bool = False, compile_cache: bool = True) -> dict:
    """One run of one cell; returns the result object.  ``cell`` and
    ``fault`` are for the tests: a small cell given directly, and a hook
    that breaks the scheduler underneath before anything is served.
    ``control`` also reads the control and the probe fault on the same
    sample (for ``bench.control``; the benchmark's own runs never do);
    ``compile_cache=False`` leaves JAX's persistent cache as it is."""
    import jax
    cell = cell or load_cell(workload)
    chips = int(cell["workload"].get("chips", 1))
    devices = jax.devices()
    dev = devices[0]
    if require_chip:
        if dev.platform != "tpu":
            raise Refused(f"JAX's devices are {dev.platform!r}, not TPUs")
        if len(devices) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX sees "
                          f"{len(devices)}")
    cache_dir = use_compile_cache() if compile_cache else "off"
    sys.path.insert(0, str(CHECKOUT / "src"))
    from bench import check as C
    from bench import families as F
    from bench import harness as H
    from bench import roofline as RF
    family = F.load(cell["config"])
    counter = H.CompileCounter()
    log(f"device: {len(devices)} x {dev.device_kind} ({dev.platform}); "
        f"compile cache {cache_dir}")
    t_import = process_age()
    sched, traffic, probe, make_request, phases = build(cell, seed, family,
                                                        fault)
    t = time.perf_counter()
    loop = H.Loop(sched, traffic, make_request)
    loop.start()
    warm_steps = H.warm_up(loop)
    phases["warm-up"] = time.perf_counter() - t
    setup_s = process_age()
    log("setup: " + ", ".join(
        [f"process start to set-up {t_import:.3f}s"]
        + [f"{k} {v:.3f}s" for k, v in phases.items()])
        + f"; {warm_steps} warm-up steps; {counter.n} compiles")
    tdir = None
    if trace:
        tdir = CHECKOUT / ".bench_trace" / workload
        shutil.rmtree(tdir, ignore_errors=True)
        tdir.mkdir(parents=True)
    window, red = H.run_window(loop, seconds, counter,
                               str(tdir) if trace else None)
    peak = None
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    if stats:
        peak = int(stats.get("peak_bytes_in_use", 0))
    compile_counts = sched._engine.compile_counts()
    served = loop.served()
    stops = sum(r.stop_step >= 0 for r in loop.requests)
    log(f"window: {window.seconds:.3f}s, {len(window.steps)} steps, "
        f"{window.tokens_out} tokens out, {window.completed} requests "
        f"completed (the first due at window step "
        f"{window.first_completion}), "
        f"{sum(bool(s.chunk_segs) for s in window.steps)} steps with "
        f"prefill, {window.compiles} compiles inside it; step executables "
        f"{compile_counts}")
    log(f"probe: lambda* {probe.lam!r}; probe stops {stops}; would-be "
        f"savings at lambda* {would_be_savings(served, probe):.4f}, served "
        "risk not measured (no labels) — not meaningful on random weights")
    if stops:
        raise RuntimeError(f"{stops} probe stops: the traffic holds stops "
                           "off, so served lengths no longer follow it")
    if window.compiles or compile_counts.get("step", 1) != 1:
        raise RuntimeError("the program compiled inside the window")

    metrics: Dict[str, dict] = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        chip = RF.peaks(dev.device_kind) if require_chip else \
            RF.ChipPeaks(1e12, 1e11, "test")
        ctx = types.SimpleNamespace(cfg=cell["config"]["program"],
                                    family=family, mix=cell["mix"],
                                    window=window, trace=red, chip=chip,
                                    program=sched, trace_dir=str(tdir))
        for m in cell["per_layer"]:
            v = reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        ctx.program = None
        device.update(busy_s=red.busy_s, window_s=red.window_s)
        breakdown = {"device_ops": [list(o) for o in red.device_ops],
                     "idle_gaps": [list(g) for g in red.idle_gaps]}
        log(f"trace: {len(window.steps)} steps, window {red.window_s:.4f}s, "
            f"busy {red.busy_s:.4f}s")
    else:
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
                continue
            metrics[m["name"]] = {"value": END_TO_END[m["name"]](window),
                                  "unit": m["unit"]}

    # the program's state is freed before the reference runs, so the
    # reference neither sets the memory peak nor runs short of memory
    mix, conf = cell["mix"], cell["config"]
    del sched, loop
    gc.collect()
    from bench import reference as R
    from bench import weights as W
    picked = C.sample(served, mix["check"]["requests"], seed,
                      mix["serve"]["tokens_per_step"])
    t_pad = C.padded(traffic.max_context() + 1, R.Q_BLOCK)
    canon = W.make_canonical(family, conf["program"], seed)
    limits = cell["limits"]
    margin = limits.get("score_gap", limits.get("score_mean", 0.0))
    readings = C.compare(family, conf["program"], canon, picked, probe,
                         t_pad, score_limit=margin)
    log(f"reference: {int(readings['requests'])} requests, "
        f"{int(readings['tokens'])} served tokens, "
        f"{int(readings['scores'])} probe scores, "
        f"{readings['reference_s']:.3f}s; readings not held to a limit: "
        + ", ".join(f"{k} {readings[k]!r}" for k in C.READINGS
                    if k not in limits))
    correct = C.verdict(readings, limits)
    result = {"correct": bool(correct), "attempted": window.attempted,
              "failed": 0, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": readings[k], "limit": v}
                        for k, v in limits.items()}
    if control:
        for variant in C.VARIANTS[1:]:
            result[variant] = C.compare(family, conf["program"], canon,
                                        picked, probe, t_pad,
                                        variant=variant, score_limit=margin)
        result["readings"] = readings
    return result


def emit(result: dict) -> None:
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except Refused as e:
        print(f"[bench] refusing: {e}", file=sys.stderr)
        return 1
    emit(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
