"""Multi-device correctness tests: run in a subprocess with 8 emulated host
devices (XLA_FLAGS must be set before jax init, so these cannot run in the
main pytest process which already initialized 1 device).

Covers: expert-parallel MoE vs the dense oracle, sequence-sharded
flash-decode vs the single-device core, and FSDP/TP train-step lowering on a
small mesh.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_in_devices(code: str, n_devices: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


def test_moe_ep_matches_dense_oracle():
    run_in_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_test_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config
    from repro.models import moe as M
    from repro.models.common import init_params
    from repro.parallel import ParallelContext, use_parallel
    import dataclasses

    cfg = get_config("granite-moe-1b-a400m").reduced()   # 4 experts top-2
    mesh = make_test_mesh((2, 4))
    params = init_params(M.moe_decls(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                          jnp.float32)
    y_dense, aux_dense = M.moe_dense(params, x, cfg)
    # generous capacity so the EP path drops nothing -> must match exactly
    cfg_full = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    ctx = ParallelContext(mesh=mesh, rules={"batch": ("data",)},
                          data_axes=("data",), model_axis="model",
                          ep_moe=True)
    with use_parallel(ctx):
        y_ep, aux_ep = jax.jit(lambda p, x: M.moe_ep(p, x, cfg_full, ctx))(params, x)
    np.testing.assert_allclose(np.asarray(y_dense, np.float32),
                               np.asarray(y_ep, np.float32), rtol=2e-3, atol=2e-3)
    print("EP == dense oracle OK")
    # gradient flows through the EP path
    def loss(p):
        y, aux = M.moe_ep(p, x, cfg_full, ctx)
        return jnp.sum(jnp.square(y)) + aux
    with use_parallel(ctx):
        g = jax.jit(jax.grad(loss))(params)
    gn = sum(float(jnp.sum(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert np.isfinite(gn) and gn > 0
    print("EP grad OK", gn)
    """)


def test_flash_decode_sharded_matches_core():
    run_in_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_test_mesh
    from repro.models.attention import _decode_core, _flash_decode_sharded
    from repro.parallel import ParallelContext

    mesh = make_test_mesh((2, 4))
    ctx = ParallelContext(mesh=mesh, rules={"batch": "data"},
                          data_axes=("data",), model_axis="model",
                          flash_decode=True)
    b, kv, g, s, d = 4, 2, 3, 64, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    qg = jax.random.normal(ks[0], (b, kv, g, d))
    k = jax.random.normal(ks[1], (b, kv, s, d))
    v = jax.random.normal(ks[2], (b, kv, s, d))
    valid = jax.random.uniform(ks[3], (b, s)) > 0.3
    valid = valid.at[:, 0].set(True)
    ref = _decode_core(qg, k, v, valid)

    def sharded(*a):
        o, l, m = _flash_decode_sharded(ctx, *a)
        return o / jnp.maximum(l, 1e-30)[..., None]

    out = jax.jit(sharded)(qg, k, v, valid)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                               rtol=1e-5, atol=1e-5)
    print("flash-decode sharded OK")
    """)


def test_small_mesh_train_and_decode_lowering():
    """A miniature of the dry-run on a 2x4 mesh with REAL execution:
    one train step + one serve step of a reduced arch, sharded."""
    run_in_devices("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.launch.mesh import make_test_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import get_config, InputShape
    from repro.launch import shardings as SH
    from repro.models import build
    from repro.optim import Adam
    from repro.parallel import use_parallel
    import dataclasses

    cfg = get_config("granite-moe-1b-a400m").reduced()
    model = build(cfg)
    mesh = make_test_mesh((2, 4))
    shape = InputShape("t", 64, 4, "train")
    ctx = SH.make_context(cfg, mesh, shape, multi_pod=False)
    ctx = dataclasses.replace(ctx, attn_impl="einsum", remat=False)
    with use_parallel(ctx):
        params = model.init(jax.random.PRNGKey(0))
        opt = Adam(lr=1e-3)
        opt_state = opt.init(params)
        batch = model.make_batch(jax.random.PRNGKey(1), shape)

        def train_step(params, opt_state, batch):
            (loss, m), grads = jax.value_and_grad(
                lambda p: model.loss(p, batch), has_aux=True)(params)
            upd, opt_state = opt.update(grads, opt_state, params)
            params = jax.tree.map(lambda p, u: p + u.astype(p.dtype),
                                  params, upd)
            return params, opt_state, loss

        params2, _, loss = jax.jit(train_step)(params, opt_state, batch)
        assert np.isfinite(float(loss)), loss
        print("sharded train step OK, loss", float(loss))

    dshape = InputShape("d", 64, 4, "decode")
    dctx = SH.make_context(cfg, mesh, dshape, multi_pod=False)
    with use_parallel(dctx):
        from repro.core.probe import ProbeConfig, init_outer
        from repro.serving import ServeConfig, init_probe_state, make_serve_step
        pc = ProbeConfig(d_phi=cfg.d_model)
        theta = init_outer(pc, jax.random.PRNGKey(2))
        scfg = ServeConfig(tokens_per_step=4, lam=0.9)
        cache_len, window = model.decode_geometry(dshape)
        state = model.init_decode_state(4, cache_len)
        st = init_probe_state(pc, theta, 4, cfg.d_model)
        step = jax.jit(make_serve_step(model, pc, scfg, window=window))
        tok = jnp.zeros((4,), jnp.int32)
        tok, state, st = step(params2, theta, tok, state,
                              jnp.asarray(0, jnp.int32), st)
        assert np.isfinite(np.asarray(st.smoothed, np.float32)).all()
        print("sharded serve step OK")
    """)


def test_fleet_hosts_live_on_their_own_devices():
    """FleetRouter places host i on local device i — params, page pool and
    probe state — and serves the same stops as one host on one device."""
    run_in_devices("""
    import jax, numpy as np
    from repro.serving.replay import serve_replay

    rs = np.random.RandomState(0)
    bank = (rs.randn(8, 12, 16) * 0.3
            + np.linspace(0, 1.2, 12)[None, :, None]).astype(np.float32)
    theta = {"W0": np.full((16,), 0.25, np.float32), "b0": np.float32(-0.5)}
    kw = dict(lam=0.6, burn_in=1, n_slots=2, paged=True, block_size=4)
    one, _, _ = serve_replay(bank, theta, n_hosts=1, **kw)
    four, _, router = serve_replay(bank, theta, n_hosts=4, **kw)
    ids = [h.device.id for h in router.hosts]
    assert ids == [0, 1, 2, 3], ids
    for h in router.hosts:
        held = {d.id for leaf in jax.tree.leaves(h._engine.state)
                for d in leaf.devices()}
        assert held == {h.device.id}, (held, h.device.id)
    key = lambda rs_: [r.stop_step for r in sorted(rs_, key=lambda r: r.req_id)]
    assert key(one) == key(four), (key(one), key(four))
    assert any(s >= 0 for s in key(one))              # stops actually fired
    print("fleet device placement OK", ids, key(one))
    """, n_devices=4)
