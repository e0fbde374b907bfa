"""A cell small enough for the CPU: the harness end to end, with Pallas in
interpret mode and the float32 program, in a few seconds."""
import json
from pathlib import Path

import jax
import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
SEED = 2 ** 40 + 3
LLAMA = "bench.families.llama"

PROGRAM = {"name": "tiny", "arch_type": "dense", "source": "test",
           "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
           "d_head": 16, "d_ff": 128, "vocab_size": 256, "norm": "rmsnorm",
           "mlp": "swiglu", "tie_embeddings": True, "rope_theta": 10000.0,
           "dtype": "float32", "kv_cache_dtype": "float32"}
MIX = {"clients": 4, "quantiles": 4,
       "prompt_tokens": {"dist": "loguniform", "lo": 16, "hi": 64},
       "served_tokens": {"dist": "loguniform", "lo": 24, "hi": 64},
       "serve": {"n_slots": 4, "tokens_per_step": 8, "paged": True,
                 "chunk_tokens": 32},
       "probe": {"bank": 16, "epochs": 2, "delta": 0.2, "eta": 0.01,
                 "smooth_window": 4},
       "check": {"requests": 2}}
CELL = "smollm-360m.reasoning"
# The numbers the committed cell holds, each with a limit set at this size
# as the cell's is at its own, the same share of the way below its upper
# reading (the cell: mean_gap 2.1x below the control, score_gap 6.6x below
# the probe fault).  Here on the CPU the float32 program reads 0 to 1e-7;
# the dense control reads mean_gap 1.1e-4, the probe fault score_gap 0.044.
TINY_LIMITS = {"mean_gap": 4e-5, "score_gap": 6e-3}
LIMITS = {k: TINY_LIMITS[k] for k in json.loads(
    (ROOT / "bench" / "limits" / f"{CELL}.json").read_text())}


def cell(moe: bool = False, family: str = LLAMA, tied: bool = True) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    program = dict(PROGRAM, tie_embeddings=tied)
    if moe:
        program.update(arch_type="moe", moe={"n_experts": 4, "top_k": 2})
    return {"workload": {"name": "tiny", "chips": 1},
            "config": {"name": "tiny", "family": family, "program": program},
            "mix": MIX, "limits": LIMITS,
            "end_to_end": [m for m in spec["end_to_end"]
                           if CELL in m.get("workloads", [CELL])],
            "per_layer": [m for m in spec["per_layer"]
                          if CELL in m.get("workloads", [CELL])]}


def run(fault=None, control=False, moe=False, seed=SEED, trace=False,
        **config):
    """One run of the tiny cell; ``config`` goes to ``cell``."""
    from bench import run as RUN
    return RUN.run_cell("tiny", seed, 1.0, trace, require_chip=False,
                        cell=cell(moe, **config), fault=fault,
                        control=control, compile_cache=False)


def on_engine(patch):
    """A fault hook: ``patch(engine)`` once the scheduler builds its
    engine (it does so lazily, at the first submit)."""
    def fault(sched):
        make = sched._ensure_engine

        def ensure(requests):
            fresh = sched._engine is None
            eng = make(requests)
            if fresh:
                patch(eng)
            return eng
        sched._ensure_engine = ensure
    return fault


def altered_token(eng):
    """Every 7th step, the tokens the step produces are changed before the
    scheduler serves them."""
    step = eng.step
    count = [0]

    def wrapped(*a, **k):
        view = step(*a, **k)
        count[0] += 1
        if count[0] % 7:
            return view
        return view._replace(tokens=(view.tokens + 1) % PROGRAM["vocab_size"])
    eng.step = wrapped


def state_unchanged(eng):
    """The fused step hands back the KV state it was given."""
    fn = eng._step_fn

    def wrapped(*args):
        before = jax.tree.map(jnp.copy, args[3])
        out = fn(*args)
        return (out[0], before) + tuple(out[2:])
    wrapped._cache_size = fn._cache_size
    eng._step_fn = wrapped


def probe_frozen(monkeypatch):
    """The probe's test-time update is skipped: the fused probe step hands
    back the fast weights it was given (patched where the engine calls it,
    before any engine traces its step)."""
    from repro.serving import engine as E
    step = E.serving_probe_step

    def wrapped(zq, zk, boundary, W, b, *a, **k):
        return step(zq, zk, boundary, W, b, *a, **k)._replace(W=W, b=b)
    monkeypatch.setattr(E, "serving_probe_step", wrapped)


def half_batch(eng):
    """Half of the rows are left out of the step: they repeat their input
    token instead of decoding."""
    fn = eng._step_fn

    def wrapped(*args):
        out = fn(*args)
        keep = jnp.arange(out[0].shape[0]) % 2 == 0
        return (jnp.where(keep, out[0], args[2]),) + tuple(out[1:])
    wrapped._cache_size = fn._cache_size
    eng._step_fn = wrapped
