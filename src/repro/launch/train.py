"""Training driver.

CPU demo (reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --reduced \
        --steps 50 --batch 8 --seq 128

On a real cluster the same step function runs under the production mesh:
pass --mesh single|multi (requires 256/512 devices) and the full config.
"""
from __future__ import annotations

import argparse
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import latest_step, restore, save_pytree
from repro.configs import InputShape, get_config
from repro.data import TokenPipeline, TokenPipelineConfig
from repro.launch import shardings as SH
from repro.launch.mesh import make_production_mesh
from repro.models import build
from repro.optim import Adam, cosine_schedule
from repro.parallel import use_parallel


def make_train_step(model, opt):
    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: model.loss(p, batch), has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u.astype(p.dtype), params, updates)
        return params, opt_state, loss, metrics

    return jax.jit(train_step, donate_argnums=(0, 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--mesh", choices=["none", "single", "multi"], default="none")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    shape = InputShape("cli", args.seq, args.batch, "train")

    ctx = None
    if args.mesh != "none":
        mesh = make_production_mesh(multi_pod=args.mesh == "multi")
        ctx = SH.make_context(cfg, mesh, shape, multi_pod=args.mesh == "multi")

    opt = Adam(lr=cosine_schedule(args.lr, args.warmup, args.steps),
               clip_norm=1.0)
    pipe = TokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=model.text_len(shape),
        global_batch=args.batch, seed=args.seed))

    def run():
        params = model.init(jax.random.PRNGKey(args.seed))
        opt_state = opt.init(params)
        start = 0
        if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
            step = latest_step(args.ckpt_dir)
            params = restore(params, f"{args.ckpt_dir}/step_{step}")
            start = step
            print(f"[train] resumed from step {step}")
        step_fn = make_train_step(model, opt)
        n_params = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
        print(f"[train] {cfg.name}: {n_params/1e6:.1f}M params, "
              f"{args.steps} steps @ batch {args.batch} x seq {args.seq}")
        losses = []
        t0 = time.time()
        for i, batch in enumerate(pipe):
            step = start + i
            if step >= args.steps:
                break
            extra = {}
            if cfg.arch_type == "vlm":
                extra["patch_embeds"] = jnp.zeros(
                    (args.batch, cfg.frontend.n_tokens, cfg.frontend.embed_dim),
                    jnp.dtype(cfg.dtype))
            if cfg.arch_type == "audio":
                extra["frames"] = jnp.zeros(
                    (args.batch, cfg.frontend.n_tokens, cfg.d_model),
                    jnp.dtype(cfg.dtype))
            jb = {**{k: jnp.asarray(v) for k, v in batch.items()}, **extra}
            params, opt_state, loss, _ = step_fn(params, opt_state, jb)
            losses.append(float(loss))
            if (step + 1) % args.log_every == 0:
                dt = time.time() - t0
                print(f"[train] step {step+1:5d} loss {np.mean(losses[-args.log_every:]):.4f} "
                      f"({dt/ (i+1):.2f}s/step)")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                save_pytree(params, args.ckpt_dir, step=step + 1)
        if args.ckpt_dir:
            save_pytree(params, args.ckpt_dir, step=start + len(losses))
        print(f"[train] done: loss {losses[0]:.4f} -> {np.mean(losses[-5:]):.4f}")
        return losses

    if ctx is not None:
        with use_parallel(ctx):
            losses = run()
    else:
        losses = run()
    return 0 if losses[-1] < losses[0] else 1


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    raise SystemExit(main())
