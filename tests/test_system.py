"""End-to-end behaviour tests for the ORCA system (paper-level claims on a
small synthetic corpus) + driver smoke tests."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.pipeline import evaluate_probe, run_orca
from repro.core.probe import ProbeConfig
from repro.trajectories import corpus_splits, ood_benchmark

# the deprecated shims (ServingEngine.serve / run_orca) are exercised here
# ON PURPOSE as equality baselines — silence their DeprecationWarning
pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


@pytest.fixture(scope="module")
def orca_run():
    train, cal, test = corpus_splits(240, 90, 90, d_phi=96, seed=1)
    out = run_orca(train, cal, test, mode="supervised",
                   pc=ProbeConfig(d_phi=96), deltas=(0.1, 0.2), epochs=25,
                   seed=1)
    return train, cal, test, out


def test_risk_control_holds(orca_run):
    """LTT guarantee: test error <= delta (+ finite-sample slack) whenever a
    threshold was selected."""
    *_, out = orca_run
    for method in ("ttt", "static"):
        for r in out[method].results:
            if np.isfinite(r.lam):
                assert r.error <= r.delta + 0.08, (method, r.delta, r.error)


def test_ttt_beats_static_in_distribution(orca_run):
    *_, out = orca_run
    t = out["ttt"].at(0.1)
    s = out["static"].at(0.1)
    assert t.savings >= s.savings - 0.02, (t.savings, s.savings)


def test_ttt_ood_gap(orca_run):
    """Zero-shot OOD: TTT savings should exceed static by a clear margin
    (paper's Table 3 headline)."""
    train, cal, test, out = orca_run
    probe, static = out["_probe"], out["_static"]
    ood = ood_benchmark("math500", 90, d_phi=96)
    e_t = evaluate_probe(probe.scores(cal), cal, probe.scores(ood), ood,
                         "supervised", (0.1,)).results[0]
    e_s = evaluate_probe(static.scores(cal.phis, cal.mask), cal,
                         static.scores(ood.phis, ood.mask), ood,
                         "supervised", (0.1,)).results[0]
    assert e_t.savings > e_s.savings, (e_t.savings, e_s.savings)


def test_consistent_mode_is_label_free_and_works(orca_run):
    train, cal, test, _ = orca_run
    out = run_orca(train, cal, test, mode="consistent",
                   pc=ProbeConfig(d_phi=96), deltas=(0.1,), epochs=25,
                   include_static=False, seed=1)
    r = out["ttt"].results[0]
    assert r.error <= 0.1 + 0.08
    assert r.savings >= 0.0


def test_train_driver_cli(tmp_path):
    """The training driver runs end-to-end (reduced config, 25 steps) and
    reduces the loss (exit code 0 asserts this)."""
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "smollm-360m",
         "--reduced", "--steps", "25", "--batch", "4", "--seq", "64",
         "--lr", "1e-3", "--ckpt-dir", str(tmp_path / "ck"),
         "--log-every", "10"],
        capture_output=True, text=True, timeout=900, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert (tmp_path / "ck").exists()


def test_dryrun_cli_skip_path():
    """The dry-run CLI handles the documented skip without device setup."""
    env = dict(os.environ, PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "whisper-tiny",
         "--shape", "long_500k"],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr
    assert '"skip"' in proc.stdout


ROOT = os.path.dirname(SRC)


@pytest.mark.parametrize("env", [
    {"JAX_PLATFORMS": "cpu"},
    {"JAX_PLATFORMS": "cpu", "REPRO_PALLAS_INTERPRET": "1"},
    {"JAX_PLATFORMS": "cpu", "REPRO_PAGED_ATTN": "jnp"},
])
def test_chip_smoke_refuses_off_chip(env):
    """chip_smoke.py never runs (or reports success) off a TPU, nor with a
    switch that would put the off-chip kernel path on one."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, **env))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "refusing" in proc.stdout + proc.stderr


def test_compile_cache_env_dir_wins(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself and the
    launch code sets nothing."""
    import jax
    from repro.launch import compile_cache as CC
    monkeypatch.setenv(CC.ENV, str(tmp_path / "env_cache"))
    before = jax.config.jax_compilation_cache_dir
    assert CC.use_compile_cache(tmp_path) == str(tmp_path / "env_cache")
    assert jax.config.jax_compilation_cache_dir == before
    assert not (tmp_path / ".jax_cache").exists()


def test_compile_cache_default_is_fixed_checkout_path(monkeypatch, tmp_path):
    """Without the variable the cache is <checkout>/.jax_cache — the same
    path on every call, so the next process in the checkout hits it."""
    import jax
    from repro.launch import compile_cache as CC
    monkeypatch.delenv(CC.ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = CC.use_compile_cache(tmp_path)
        assert path == str(tmp_path.resolve() / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert CC.use_compile_cache(tmp_path) == path
        assert CC.CHECKOUT == type(CC.CHECKOUT)(ROOT).resolve()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_chip_path_never_imports_the_cpu_dry_run():
    """launch/dryrun.py forces 512 host devices at import; nothing that
    chip_smoke.py imports may pull it (or benchmarks/dryrun_matrix.py) in."""
    code = ("import sys; sys.path[:0] = [{root!r}, {src!r}]\n"
            "import chip_smoke, repro.launch.serve, repro.launch.compile_cache\n"
            "import repro.serving, repro.api, repro.models\n"
            "bad = [m for m in ('repro.launch.dryrun', 'benchmarks.dryrun_matrix')"
            " if m in sys.modules]\n"
            "assert not bad, bad\n"
            "import os; assert 'xla_force_host_platform_device_count' not in "
            "os.environ.get('XLA_FLAGS', '')\n").format(root=ROOT, src=SRC)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300,
                          env=dict(env, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr
