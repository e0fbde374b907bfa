import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first initialization).

# Multi-pod dry-run: lower + compile every (architecture x input shape) on
# the production meshes, with NO device allocation (ShapeDtypeStruct inputs).
#
#     PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b \
#         --shape train_4k [--multi-pod] [--json out.json]
#
# Success criteria (deliverable e): ``.lower().compile()`` succeeds on the
# 16x16 single-pod mesh and the 2x16x16 multi-pod mesh for every pair;
# ``compiled.memory_analysis()`` proves the per-device footprint and
# ``cost_analysis()`` + the optimized HLO feed the roofline report
# (EXPERIMENTS.md §Dry-run / §Roofline).

import argparse
import functools
import json
import sys
import time
from typing import Any, Dict, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALIASES, INPUT_SHAPES, InputShape, get_config
from repro.core.probe import ProbeConfig, init_outer
from repro.launch import shardings as SH
from repro.launch.mesh import PRODUCTION_DEVICE_KIND, make_production_mesh
from repro.models import build
from repro.optim import Adam
from repro.parallel import use_parallel
from repro.roofline import build_report
from repro.serving import init_probe_state, make_serve_step


def _abstract(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _cast(tree, dtype):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), tree)


def skip_reason(cfg, shape: InputShape) -> Optional[str]:
    if shape.name == "long_500k":
        if cfg.arch_type == "audio":
            return ("skipped: encoder-decoder audio head has an architecturally "
                    "bounded decoder context (DESIGN.md §Arch-applicability)")
        if not cfg.supports_long_context:
            return "skipped: full attention without a sub-quadratic variant"
    return None


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool,
               compile_: bool = True, microbatches: int = 1,
               donate_cache: bool = True, hlo_out: Optional[str] = None
               ) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    reason = skip_reason(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    if reason:
        return {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
                "status": "skip", "reason": reason}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(np.prod(list(mesh.shape.values())))
    model = build(cfg)
    ctx = SH.make_context(cfg, mesh, shape, multi_pod=multi_pod)
    rules = ctx.rules
    t0 = time.time()

    with use_parallel(ctx):
        params_a = jax.eval_shape(model.init, jax.random.PRNGKey(0))
        pspecs = model.specs(rules)
        batch_a = model.batch_specs(shape)
        bspecs = SH.batch_spec_tree(batch_a, rules)

        if shape.kind == "train":
            params_s = SH.with_shardings(params_a, pspecs, mesh)
            opt = Adam(lr=1e-4)
            opt_a = jax.eval_shape(opt.init, params_a)
            from repro.optim.adam import AdamState
            ospecs = AdamState(step=P(), mu=pspecs, nu=pspecs)
            opt_s = SH.with_shardings(opt_a, ospecs, mesh)
            batch_s = SH.with_shardings(batch_a, bspecs, mesh)

            bspec_shardings = jax.tree.map(
                lambda s: NamedSharding(mesh, s), bspecs)

            def grads_of(params, batch):
                if microbatches <= 1:
                    (loss, _), grads = jax.value_and_grad(
                        lambda p: model.loss(p, batch), has_aux=True)(params)
                    return loss, grads
                # gradient accumulation: scan over microbatches; activation
                # working set scales by 1/microbatches (§Perf iteration A)
                mb = jax.tree.map(
                    lambda x: x.reshape((microbatches,
                                         x.shape[0] // microbatches)
                                        + x.shape[1:]), batch)

                def acc(carry, mbatch):
                    loss_c, g_c = carry
                    mbatch = jax.tree.map(
                        lambda x, s: jax.lax.with_sharding_constraint(x, s),
                        mbatch, bspec_shardings)
                    (loss, _), g = jax.value_and_grad(
                        lambda p: model.loss(p, mbatch), has_aux=True)(params)
                    g = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     g_c, g)
                    return (loss_c + loss, g), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
                (loss, grads), _ = jax.lax.scan(acc, (jnp.float32(0), g0), mb)
                scale = 1.0 / microbatches
                return loss * scale, jax.tree.map(lambda g: g * scale, grads)

            def train_step(params, opt_state, batch):
                loss, grads = grads_of(params, batch)
                updates, opt_state = opt.update(grads, opt_state, params)
                params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                      params, updates)
                return params, opt_state, loss

            out_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs),
                      jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs),
                      NamedSharding(mesh, P()))
            lowered = jax.jit(train_step, out_shardings=out_sh,
                              donate_argnums=(0, 1)).lower(
                params_s, opt_s, batch_s)

        elif shape.kind == "prefill":
            params_s = SH.with_shardings(_cast(params_a, jnp.bfloat16),
                                         pspecs, mesh)
            batch_s = SH.with_shardings(batch_a, bspecs, mesh)
            cache_len = shape.seq_len

            def prefill_step(params, batch):
                state, last_h, _ = model.prefill(cfg, params, batch, cache_len)
                return state, last_h

            state_a = jax.eval_shape(
                lambda: model.init_decode_state(shape.global_batch, cache_len))
            sspecs = SH.decode_state_specs(cfg, state_a, rules,
                                           flash_decode=cfg.arch_type != "ssm")
            out_sh = (jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs),
                      NamedSharding(mesh, P(rules["batch"], None)))
            lowered = jax.jit(prefill_step, out_shardings=out_sh).lower(
                params_s, batch_s)

        else:  # decode
            cache_len, window = model.decode_geometry(shape)
            params_s = SH.with_shardings(_cast(params_a, jnp.bfloat16),
                                         pspecs, mesh)
            pc = ProbeConfig(d_phi=cfg.d_model)
            theta_a = jax.eval_shape(
                functools.partial(init_outer, pc), jax.random.PRNGKey(0))
            theta_s = SH.replicated(theta_a, mesh)
            from repro.serving import ServeConfig
            scfg = ServeConfig(tokens_per_step=16, lam=0.9)
            serve_step = make_serve_step(model, pc, scfg, window=window)
            state_a = jax.eval_shape(
                lambda: model.init_decode_state(shape.global_batch, cache_len))
            sspecs = SH.decode_state_specs(cfg, state_a, rules,
                                           flash_decode=ctx.flash_decode)
            state_s = SH.with_shardings(state_a, sspecs, mesh)
            probe_a = jax.eval_shape(
                lambda: init_probe_state(pc, jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), theta_a),
                    shape.global_batch, cfg.d_model))
            pspecs_probe = SH.probe_state_specs(probe_a, rules)
            probe_s = SH.with_shardings(probe_a, pspecs_probe, mesh)
            token_s = jax.ShapeDtypeStruct(
                (shape.global_batch,), jnp.int32,
                sharding=NamedSharding(mesh, P(rules["batch"])))
            pos_s = jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(mesh, P()))
            out_sh = (NamedSharding(mesh, P(rules["batch"])),
                      jax.tree.map(lambda s: NamedSharding(mesh, s), sspecs),
                      jax.tree.map(lambda s: NamedSharding(mesh, s),
                                   pspecs_probe))
            # donating the KV cache + probe state lets XLA update them in
            # place instead of double-buffering the whole cache (§Perf C)
            donate = (3, 5) if donate_cache else ()
            lowered = jax.jit(serve_step, out_shardings=out_sh,
                              donate_argnums=donate).lower(
                params_s, theta_s, token_s, state_s, pos_s, probe_s)

        t_lower = time.time() - t0
        result = {"arch": cfg.name, "shape": shape.name, "mesh": mesh_name,
                  "status": "lowered", "lower_s": round(t_lower, 1)}
        if not compile_:
            return result
        t0 = time.time()
        compiled = lowered.compile()
        result["compile_s"] = round(time.time() - t0, 1)
        result["status"] = "ok"
        # --- artifacts for the roofline
        mem = compiled.memory_analysis()
        memory_stats = None
        if mem is not None:
            memory_stats = {
                "bytes": getattr(mem, "temp_size_in_bytes", 0)
                + getattr(mem, "argument_size_in_bytes", 0)
                + getattr(mem, "output_size_in_bytes", 0)
                - getattr(mem, "alias_size_in_bytes", 0),
                "temp": getattr(mem, "temp_size_in_bytes", None),
                "args": getattr(mem, "argument_size_in_bytes", None),
                "output": getattr(mem, "output_size_in_bytes", None),
            }
            result["memory_analysis"] = memory_stats
        try:
            costs = compiled.cost_analysis()
            cost = costs if isinstance(costs, dict) else (
                costs[0] if costs else None)
        except Exception:
            cost = None
        hlo = compiled.as_text()
        if hlo_out:
            with open(hlo_out, "w") as f:
                f.write(hlo)
        report = build_report(cfg, shape, mesh_name, chips, hlo,
                              PRODUCTION_DEVICE_KIND, cost=cost,
                              memory_stats=memory_stats)
        result["roofline"] = json.loads(report.to_json())
        result["options"] = {"microbatches": microbatches,
                             "donate_cache": donate_cache}
        return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True,
                    help=f"one of {sorted(ALIASES)} or internal ids")
    ap.add_argument("--shape", required=True, choices=sorted(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-compile", action="store_true",
                    help="stop after .lower() (debugging)")
    ap.add_argument("--json", default=None, help="write result json here")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-donate", action="store_true")
    ap.add_argument("--hlo-out", default=None)
    args = ap.parse_args(argv)
    res = lower_pair(args.arch, args.shape, multi_pod=args.multi_pod,
                     compile_=not args.no_compile,
                     microbatches=args.microbatches,
                     donate_cache=not args.no_donate, hlo_out=args.hlo_out)
    print(json.dumps(res, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=1)
    return 0 if res["status"] in ("ok", "skip", "lowered") else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
