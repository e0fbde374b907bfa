#!/usr/bin/env python3
"""Smoke run of the ORCA serving path on a TPU at smollm-360m's published
widths (32 layers, d_model 960, 15/5 heads, d_head 64, d_ff 2560, vocab
49152), weights random from ``--seed``.

    python3 chip_smoke.py [--seed 0]       # one chip: phases (b) to (d)
    python3 chip_smoke.py --four-chips     # four chips: the fleet phase only

Phases, in one process (the process that touches JAX holds the chip):

(a) refuse to run unless JAX's first device is a TPU, and refuse the
    switches that would put the off-chip path on it
    (``REPRO_PALLAS_INTERPRET``, ``REPRO_PAGED_ATTN=jnp``);
(b) logits: prefill a prompt and greedily decode through the KV cache on
    the dense path and on the paged Pallas path, and hold every step's
    logits to the model's own full forward pass over the same tokens;
(c) serving: calibrate the TTT probe as ``repro.launch.serve`` does, then
    serve 8 requests on 4 slots through ``launch.serve``'s own code in
    three modes — dense; ``--paged --chunk-tokens 64``; ``--paged
    --spec-tree 2.2``;
(d) the paged decode kernel alone at the reasoning benchmark cell's
    shapes (16 rows, 253 table entries of 16 positions, caches to 4041):
    its compiled (o, l, m) partials against a float32 reference, for d 64
    bf16 and int8 pages, d 32 in 16- and 32-token pages, and d 80 (padded
    to 128 lanes);
--four-chips: serve one queue through a 4-host ``FleetRouter`` (host i on
    chip i), then through one host on one chip, and require identical
    per-request stops.

Every phase prints the implementation it ran.  A failed check exits
non-zero; the last line of a passing run is one JSON object naming the
device.  The persistent compilation cache is ``JAX_COMPILATION_CACHE_DIR``
when set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

PROMPT, CHUNK, DECODE, PAGE = 48, 32, 16, 16   # logits phase geometry
# (b) tolerance, as max|logit - reference| over max|reference|.  The phase
# runs the model in float32 with matmul precision "highest" (XLA's and the
# Mosaic kernels' dots), so the cache paths and the reference differ only by
# float32 reduction order: ~3e-6 (dense) and ~9e-6 (paged) at the reduced
# 2-layer config.  K/V held in bfloat16 instead (8 significand bits) moves
# the logits by 3e-2 (paged) to 7e-2 (dense) of their scale at that config,
# 30x this bound.
F32_LOGIT_TOL = 1e-3
# --delta 0.3, looser than the serve driver's default 0.2, lets LTT select
# a threshold on random weights, so early stops evict and refill slots
SERVE_BASE = ["--arch", "smollm-360m", "--requests", "8", "--slots", "4",
              "--max-new-tokens", "96", "--delta", "0.3"]
SERVE_MODES = (
    # name, extra flags, Mosaic kernels the fused step must hold
    ("dense", [], 1),                                    # probe
    ("paged+chunked", ["--paged", "--chunk-tokens", "64"], 3),
    ("paged+tree", ["--paged", "--spec-tree", "2.2"], 2),
)
# (d) geometry and tolerance.  Each bound is on max|got - ref| over
# max|ref| of rows with context: outputs o / l, running max m, and l.
# Rounding reads ~1e-3 to 1e-2 on a v5e (PERF.md); a wrong lane merge
# after the kernel read 1.29 there.
KERNEL_ROWS, KERNEL_ENTRIES, KERNEL_TOL = 16, 253, 5e-2
KERNEL_CASES = (
    # name, kv heads, query heads, head dim, page dtype, page size; d 32
    # packs four positions a lane row only where a page fills whole 8-row
    # tiles (32-token pages), and is padded to 128 lanes at 16
    ("d64 bf16", 5, 15, 64, "bfloat16", PAGE),
    ("d64 int8", 5, 15, 64, "int8", PAGE),
    ("d32 bf16", 5, 15, 32, "bfloat16", PAGE),
    ("d32 bf16 x32", 5, 15, 32, "bfloat16", 2 * PAGE),
    ("d80 bf16", 5, 15, 80, "bfloat16", PAGE),
)
FLEET_HOSTS = 4
FLEET_FLAGS = ["--requests", "16", "--paged", "--chunk-tokens", "64"]


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[smoke]   ok: {what}")


def mosaic_kernels(lowered) -> int:
    """Pallas kernels compiled by Mosaic inside a lowered program."""
    return lowered.as_text().count("tpu_custom_call")


# ---------------------------------------------------------------------------
# (b) logits through the cache against the full forward pass

def check_logits(cfg, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ops import on_tpu
    from repro.models import attention as A
    from repro.models import build
    from repro.models.transformer import logits_from_hidden

    cfg = dataclasses.replace(cfg, dtype="float32", kv_cache_dtype="float32")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    vocab = cfg.vocab_size
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1), (1, PROMPT),
                                0, vocab, jnp.int32)
    cache_len = PROMPT + DECODE
    n_pages = -(-cache_len // PAGE)
    print(f"[smoke] (b) logits: {cfg.name} float32, prompt {PROMPT}, "
          f"{DECODE} greedy decode steps; paged attention = "
          f"{A.resolve_paged_impl()} kernels")

    prefill = jax.jit(functools.partial(model.prefill, cfg),
                      static_argnums=2)
    decode = jax.jit(functools.partial(model.decode_step, cfg),
                     donate_argnums=2)
    head = jax.jit(functools.partial(logits_from_hidden, cfg))
    forward = jax.jit(functools.partial(model.forward, cfg))
    verify = jax.jit(functools.partial(model.verify_packed, cfg),
                     donate_argnums=2)

    def rel_err(rows, ref):
        got = np.stack([np.asarray(r, np.float32) for r in rows])
        return float(np.abs(got - ref).max() / np.abs(ref).max())

    with jax.default_matmul_precision("highest"):
        t0 = time.perf_counter()
        # dense cache: one-shot prefill, then greedy decode through it
        cache, h_last, _ = prefill(params, {"tokens": prompt}, cache_len)
        dense = [head(params, h_last)[0, :vocab]]
        toks = [int(jnp.argmax(dense[0]))]
        for i in range(DECODE):
            logits, _, cache = decode(params, jnp.asarray([toks[-1]]), cache,
                                      jnp.asarray(PROMPT + i, jnp.int32))
            dense.append(logits[0, :vocab])
            toks.append(int(jnp.argmax(dense[-1])))
        seq = jnp.concatenate([prompt[0], jnp.asarray(toks[:DECODE])])
        ref, _, _ = forward(params, {"tokens": seq[None]})
        ref = np.asarray(ref[0, :, :vocab], np.float32)   # (P + N, vocab)

        # paged cache: the prompt in CHUNK-token packed chunks (the packed
        # Pallas kernel reads the pages earlier chunks wrote), then the same
        # tokens one at a time through the paged decode kernel
        state = model.init_paged_state(1, n_pages + 1, PAGE, n_pages)
        table = jnp.arange(1, n_pages + 1, dtype=jnp.int32)[None]
        state["block_tables"] = table + 0      # its own (donated) buffer
        zero = jnp.zeros((1,), jnp.int32)
        paged, kernels = [], 0
        for start in range(0, PROMPT, CHUNK):
            n = min(CHUNK, PROMPT - start)
            buf = jnp.zeros((CHUNK,), jnp.int32).at[:n].set(
                prompt[0, start:start + n])
            args = (params, buf, state, jnp.zeros((CHUNK,), jnp.int32),
                    zero, jnp.asarray([start], jnp.int32),
                    jnp.asarray([n], jnp.int32), table)
            if start == 0 and on_tpu():
                kernels += mosaic_kernels(verify.lower(*args))
            logits, _, state = verify(*args)
            paged.extend(logits[j, :vocab] for j in range(n))
        for i in range(DECODE):
            args = (params, jnp.asarray([toks[i]]), state,
                    jnp.asarray(PROMPT + i, jnp.int32))
            if i == 0 and on_tpu():
                kernels += mosaic_kernels(decode.lower(*args))
            logits, _, state = decode(*args)
            paged.append(logits[0, :vocab])
        err_dense = rel_err(dense, ref[PROMPT - 1:])
        err_paged = rel_err(paged, ref)
        wall = time.perf_counter() - t0
    agree = float(np.mean(np.argmax(np.stack(
        [np.asarray(r) for r in paged]), -1)[PROMPT - 1:]
        == np.asarray(toks[:DECODE + 1])))
    print(f"[smoke]   dense cache: max|dlogit|/max|logit| = {err_dense:.3e} "
          f"over {len(dense)} positions")
    print(f"[smoke]   paged cache: max|dlogit|/max|logit| = {err_paged:.3e} "
          f"over {len(paged)} positions; greedy agreement {agree:.3f}; "
          f"{kernels} Mosaic kernels in the paged chunk + decode programs")
    print(f"[smoke]   wall {wall:.1f}s (compiles included)")
    check(err_dense <= F32_LOGIT_TOL,
          f"dense cache logits within {F32_LOGIT_TOL:g} of forward")
    check(err_paged <= F32_LOGIT_TOL,
          f"paged Pallas logits within {F32_LOGIT_TOL:g} of forward")
    if on_tpu():
        check(kernels >= 2, "paged chunk and decode run Mosaic kernels")


# ---------------------------------------------------------------------------
# (c) serving through launch.serve's code

def _stops(done):
    return [(r.stop_step, r.state.value, len(r.tokens))
            for r in sorted(done, key=lambda r: r.req_id)]


def _serve_checked(serve_mod, parser, flags, model, params, calib, lam):
    args = parser.parse_args(flags)
    t0 = time.perf_counter()
    sched, done, fleet = serve_mod.serve(args, model, params, calib, lam)
    wall = time.perf_counter() - t0
    check(len(done) == args.requests and all(r.done for r in done),
          "every request reached an end")
    check(all(len(r.tokens) <= args.max_new_tokens for r in done),
          f"no request decoded past {args.max_new_tokens} tokens")
    return sched, done, fleet, wall


def serve_modes(model, params, seed: int, modes=SERVE_MODES, *,
                base=SERVE_BASE) -> None:
    from repro.kernels import ops as K
    from repro.launch import serve as serve_mod
    from repro.models import attention as A

    parser = serve_mod.build_parser()
    args = parser.parse_args(base + ["--seed", str(seed)])
    t0 = time.perf_counter()
    calib, lam, _, calibrated = serve_mod.calibrate(args, model, params)
    how = "calibrated by LTT" if calibrated else "fell back to the demo value"
    print(f"[smoke] (c) lambda* {how} = {lam:.3f} "
          f"({time.perf_counter() - t0:.1f}s incl. trajectory harvest + "
          "probe meta-training)")
    for name, flags, want_kernels in modes:
        attn = (f"paged attention = {A.resolve_paged_impl()} kernels"
                if "--paged" in flags else "dense jnp decode attention")
        probe = "interpreted" if K.resolve_interpret() else "Mosaic-compiled"
        print(f"[smoke] (c) serve {name}: {attn}, {probe} probe kernel")
        sched, done, fleet, wall = _serve_checked(
            serve_mod, parser, base + ["--seed", str(seed)] + flags, model,
            params, calib, lam)
        eng = sched._engine
        counts = eng.compile_counts()
        kernels = mosaic_kernels(eng.lowered_step()) if K.on_tpu() else 0
        print(f"[smoke]   stops {[s for s, _, _ in _stops(done)]}; "
              f"compile_counts {counts}; {kernels} Mosaic kernels in the "
              f"step; wall {wall:.1f}s (serve {fleet.wall_time_s:.2f}s, "
              f"{fleet.engine_steps} steps)")
        check(counts["step"] == 1, "one step executable for the whole run")
        if K.on_tpu():
            check(kernels >= want_kernels,
                  f"the fused step holds >= {want_kernels} Mosaic kernels")


# ---------------------------------------------------------------------------
# (d) the paged decode kernel's partials against a float32 reference

def _decode_kernel_case(kv, h, d, dtype, bs, seed):
    """(q, k pages, v pages, tables, valid, scales, float32 k and v pages)
    of one case: rows at lengths 0 and 1 to 7 short of the table's end
    (geometric), one with a hole; entries past a row's length point at the NULL page or at the
    next row's pages."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.attention import quantize_kv
    b, nb = KERNEL_ROWS, KERNEL_ENTRIES
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    p = b * nb + 1
    q = jax.random.normal(keys[0], (b, h, d), jnp.float32)
    kf, vf = (jax.random.normal(k, (p, kv, bs, d), jnp.float32)
              for k in keys[1:])
    if dtype == "int8":
        (kp, ks), (vp, vs) = quantize_kv(kf), quantize_kv(vf)
        scales = (ks, vs)
        kf, vf = kp.astype(jnp.float32) * ks, vp.astype(jnp.float32) * vs
    else:
        kp, vp = kf.astype(dtype), vf.astype(dtype)
        kf, vf, scales = kp.astype(jnp.float32), vp.astype(jnp.float32), ()
    rng = np.random.RandomState(seed)
    tables = rng.permutation(np.arange(1, p)).reshape(b, nb)
    lens = np.concatenate(
        [[0], np.round(np.geomspace(1, nb * bs - 7, b - 1))]).astype(int)
    valid = np.arange(nb * bs)[None] < lens[:, None]
    valid[b - 3, 5:400] = False
    for i in range(b):
        dead = np.arange(-(-lens[i] // bs), nb)
        tables[i, dead[::2]] = 0
        tables[i, dead[1::2]] = tables[(i + 1) % b, dead[1::2]]
    return (q, kp, vp, jnp.asarray(tables, jnp.int32), jnp.asarray(valid),
            scales, kf, vf)


def check_decode_kernel(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref as R
    from repro.kernels.decode_attention import paged_flash_decode

    print(f"[smoke] (d) paged decode kernel: {KERNEL_ROWS} rows, "
          f"{KERNEL_ENTRIES} table entries, compiled partials against a "
          f"float32 reference (bound {KERNEL_TOL:g})")
    ref_fn = jax.jit(R.paged_prefill_chunk_ref)
    for name, kv, h, d, dtype, bs in KERNEL_CASES:
        q, kp, vp, tables, valid, scales, kf, vf = _decode_kernel_case(
            kv, h, d, dtype, bs, seed)
        got = paged_flash_decode(q, kp, vp, tables, valid, *scales,
                                 interpret=False, return_partials=True)
        with jax.default_matmul_precision("highest"):
            ref = ref_fn(q[:, None], kf, vf, tables, valid)
        o, l, m = (np.asarray(x, np.float64) for x in got)
        ro, rl, rm = (np.asarray(x, np.float64)[:, :, :, 0] for x in ref)
        live = np.asarray(valid).any(axis=1)

        def rel(a, b):
            return float(np.abs(a[live] - b[live]).max()
                         / np.abs(b[live]).max())
        out = o / np.maximum(l, 1e-30)[..., None]
        rout = ro / np.maximum(rl, 1e-30)[..., None]
        errs = {"out": rel(out, rout), "m": rel(m, rm), "l": rel(l, rl)}
        print(f"[smoke]   {name}: " + ", ".join(
            f"{k} {v:.3e}" for k, v in errs.items()))
        check(max(errs.values()) <= KERNEL_TOL,
              f"{name} partials within {KERNEL_TOL:g} of the reference")
        check(bool((m[~live] <= -1e29).all() and not l[~live].any()
                   and not o[~live].any()),
              f"{name}: a row with no context keeps m = NEG_INF, l = o = 0")


# ---------------------------------------------------------------------------
# --four-chips: device-placed fleet hosts against one host on one chip

def four_chip_fleet(model, params, seed: int, *, base=SERVE_BASE) -> None:
    import jax
    from repro.kernels import ops as K
    from repro.launch import serve as serve_mod
    from repro.models import attention as A

    devices = jax.devices()
    check(len(devices) >= FLEET_HOSTS, f"{FLEET_HOSTS} devices visible")
    print(f"[smoke] fleet: {' '.join(FLEET_FLAGS)}; paged attention = "
          f"{A.resolve_paged_impl()} kernels, "
          f"{'interpreted' if K.resolve_interpret() else 'Mosaic-compiled'} "
          "probe kernel")
    parser = serve_mod.build_parser()
    flags = base + ["--seed", str(seed)] + FLEET_FLAGS
    args = parser.parse_args(flags)
    calib, lam, _, calibrated = serve_mod.calibrate(args, model, params)
    print(f"[smoke] fleet: lambda* "
          f"{'calibrated' if calibrated else 'fell back'} = {lam:.3f}")
    router, done_n, _, wall_n = _serve_checked(
        serve_mod, parser, flags + ["--hosts", str(FLEET_HOSTS)], model,
        params, calib, lam)
    ids = [h.device.id for h in router.hosts]
    for i, h in enumerate(router.hosts):
        held = {d.id for leaf in jax.tree.leaves(h._engine.state)
                for d in leaf.devices()}
        print(f"[smoke]   host {i}: device {h.device.id}, engine state on "
              f"{sorted(held)}")
        check(held == {h.device.id}, f"host {i} state lives on its device")
    check(len(set(ids)) == FLEET_HOSTS,
          f"{FLEET_HOSTS} distinct device ids {ids}")
    _, done_1, _, wall_1 = _serve_checked(
        serve_mod, parser, flags, model, params, calib, lam)
    stops_n, stops_1 = _stops(done_n), _stops(done_1)
    print(f"[smoke]   {FLEET_HOSTS} hosts: stops {[s for s, _, _ in stops_n]} "
          f"({wall_n:.1f}s); 1 host: stops {[s for s, _, _ in stops_1]} "
          f"({wall_1:.1f}s)")
    check(stops_n == stops_1, "per-request stops identical, "
          f"{FLEET_HOSTS} hosts vs 1 host")


# ---------------------------------------------------------------------------

def refuse_off_chip_switches() -> None:
    interp = os.environ.get("REPRO_PALLAS_INTERPRET", "")
    if interp not in ("", "0", "false", "False"):
        sys.exit(f"[smoke] refusing: REPRO_PALLAS_INTERPRET={interp} would "
                 "interpret the kernels instead of running them on the chip")
    paged = os.environ.get("REPRO_PAGED_ATTN", "")
    if paged not in ("", "pallas"):
        sys.exit(f"[smoke] refusing: REPRO_PAGED_ATTN={paged} would gather "
                 "pages in jnp instead of the paged Pallas kernels")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-host fleet vs 1-host comparison "
                         "(needs four chips)")
    args = ap.parse_args(argv)

    refuse_off_chip_switches()
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"[smoke] no repro package under {ROOT / 'src'}: run "
                 "chip_smoke.py from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"[smoke] refusing: JAX's first device is {dev.platform!r}, "
                 "not a TPU")

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build

    cache_dir = use_compile_cache(ROOT)
    cache_events = {"hits": 0, "writes": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["writes"] += 1

    jax.monitoring.register_event_listener(on_event)
    print(f"[smoke] devices: {len(jax.devices())} x {dev.device_kind} "
          f"({dev.platform}); compile cache {cache_dir}")

    t0 = time.perf_counter()
    cfg = get_config("smollm-360m")
    if args.four_chips:
        model = build(cfg)
        four_chip_fleet(model, model.init(jax.random.PRNGKey(args.seed)),
                        args.seed)
    else:
        check_logits(cfg, args.seed)
        model = build(cfg)
        serve_modes(model, model.init(jax.random.PRNGKey(args.seed)),
                    args.seed)
        check_decode_kernel(args.seed)
    print(f"[smoke] compile cache: {cache_events['hits']} hits, "
          f"{cache_events['writes']} entries written; total wall "
          f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        raise SystemExit(1)
