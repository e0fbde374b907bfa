"""The weights made from the seed, and the plain reference against the
program's own forward pass at a small size on the CPU; configurations as
the harness hands them to the reference's jit cache and to the program."""
import dataclasses
import json
from pathlib import Path
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import families as F
from bench import reference as R
from bench import weights as W
from bench.families import llama as LLAMA
from bench.tests import family_untied as UNTIED

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
TINY = {"name": "tiny", "arch_type": "dense", "source": "test",
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "d_head": 16, "d_ff": 96, "vocab_size": 300, "norm": "rmsnorm",
        "mlp": "swiglu", "tie_embeddings": True, "rope_theta": 10000.0,
        "dtype": "float32", "kv_cache_dtype": "float32"}
TINY_MOE = dict(TINY, name="tiny-moe", arch_type="moe",
                moe={"n_experts": 4, "top_k": 2})
TINY_UNTIED = dict(TINY, name="tiny-untied", tie_embeddings=False)


def _model(cfg):
    from bench.run import model_config
    from repro.models import build
    return build(model_config({"program": cfg}))


def test_seed_key_takes_large_seeds():
    a = W.seed_key(2 ** 40 + 5)
    b = W.seed_key(2 ** 40 + 6)
    assert not np.array_equal(jax.random.key_data(a), jax.random.key_data(b))
    W.seed_key(0)
    with pytest.raises(ValueError):
        W.seed_key(-1)


@pytest.mark.parametrize("name", sorted(p.stem for p in
                                        CONFIGS.glob("*.json")))
def test_layout_matches_the_program_at_published_widths(name):
    conf = json.loads((CONFIGS / f"{name}.json").read_text())
    fam, cfg = F.load(conf), conf["program"]
    model = _model(cfg)
    got = jax.eval_shape(lambda k: fam.program_tree(cfg,
                                                    fam.canonical(cfg, k)),
                         W.seed_key(1))
    want = model.abstract_params()
    shape = lambda t: jax.tree.map(lambda a: (a.shape, str(a.dtype)), t)  # noqa
    assert shape(got) == shape(want)


@pytest.mark.parametrize("fam, cfg", [(LLAMA, TINY), (LLAMA, TINY_MOE),
                                      (UNTIED, TINY_UNTIED)],
                         ids=["dense", "moe", "untied"])
def test_reference_matches_the_program_forward(fam, cfg):
    model = _model(cfg)
    params = W.make_program_params(fam, cfg, 7, model.abstract_params())
    canon = W.make_canonical(fam, cfg, 7)
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, R.Q_BLOCK), 0,
                              cfg["vocab_size"], jnp.int32)
    with jax.default_matmul_precision("highest"):
        want, _, _ = model.forward(model.cfg, params, {"tokens": toks})
    want = np.asarray(want[0, :, :cfg["vocab_size"]])
    _, got = R.run_rows(fam, cfg, canon, np.asarray(toks[0]), 0,
                        R.Q_BLOCK)
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


@pytest.mark.parametrize("cfg", [TINY, TINY_MOE], ids=["dense", "moe"])
def test_int8_control_departs_from_float32(cfg):
    canon = W.make_canonical(LLAMA, cfg, 9)
    toks = jax.random.randint(jax.random.PRNGKey(4), (R.Q_BLOCK,), 0,
                              cfg["vocab_size"], jnp.int32)
    _, ref = R.run_rows(LLAMA, cfg, canon, toks, 0, R.Q_BLOCK)
    _, alt = R.run_rows(LLAMA, cfg, canon, toks, 0, R.Q_BLOCK, low=True)
    rel = np.abs(alt - ref).max() / np.abs(ref).max()
    assert rel > 1e-3


def test_rows_are_the_rows_of_the_whole_pass():
    cfg = TINY
    canon = W.make_canonical(LLAMA, cfg, 5)
    toks = np.arange(R.Q_BLOCK, dtype=np.int32) % cfg["vocab_size"]
    h_all, lg_all = R.run_rows(LLAMA, cfg, canon, toks, 0, R.Q_BLOCK)
    h, lg = R.run_rows(LLAMA, cfg, canon, toks, 100, 37)
    assert h.shape == (37, cfg["d_model"]) and lg.shape == (37, 300)
    np.testing.assert_allclose(lg, lg_all[100:137], rtol=1e-5, atol=1e-5)


def test_probe_reference_stops():
    from bench import probe_ref as PR
    h = np.ones((64, 4))
    s = PR.probe_scores(h, np.zeros(4), 0.0, 0.01, 16, 4)
    assert s.shape == (4,) and s[0] == pytest.approx(0.5)
    assert np.all(np.diff(s) < 0)          # label-0 updates push scores down
    assert PR.stop_index(np.array([0.9, 0.9, 0.9]), 0.8, 1) == 2
    assert PR.stop_index(np.array([0.9, 0.9]), 0.95, 0) is None
    far = np.array([0.1, 0.9, 0.9])
    assert PR.stop_disagrees(np.array([0.1, 0.1, 0.1]), far, 0.8, 0, 0.01)
    assert not PR.stop_disagrees(np.array([0.1, 0.1, 0.1]), far, 0.8, 0, 0.2)


# a configuration with a list-valued key and a group nested two deep, as a
# family with per-layer attention would state them
LISTED = dict(TINY_MOE, layer_types=["sliding", "full"],
              rope={"full": {"type": "yarn", "factor": 16.0},
                    "sliding": {"theta": 10000.0}, "none": None})


def test_freeze_keeps_lists_and_nested_groups():
    key = R.freeze(LISTED)
    hash(key)
    assert R.thaw(key) == LISTED
    assert R.freeze(R.thaw(key)) == key
    # a list of pairs is not taken for a group, nor a tuple for a scalar
    assert R.thaw(R.freeze({"a": [["b", 1]]})) == {"a": [["b", 1]]}
    assert R.freeze({"a": [1, 2]}) != R.freeze({"a": {"1": 2}})


@pytest.mark.parametrize("value", [np.float32(1.0), {1, 2}, object()],
                         ids=["numpy", "set", "object"])
def test_freeze_raises_on_what_it_cannot_freeze(value):
    with pytest.raises(TypeError):
        R.freeze(dict(TINY, extra=[value]))


def test_model_config_takes_lists_and_groups_and_hashes(monkeypatch):
    from repro.configs import base
    from bench.run import model_config

    @dataclasses.dataclass(frozen=True)
    class Listed(base.ModelConfig):
        layer_types: Optional[Tuple[str, ...]] = None
        window_pattern: Tuple[Tuple[int, int], ...] = ()

    monkeypatch.setattr(base, "ModelConfig", Listed)
    cfg = dict(TINY_MOE, layer_types=["sliding", "full"],
               window_pattern=[[3, 1024], [1, 0]],
               ssm={"state_dim": 8, "head_dim": 32})
    mc = model_config({"program": cfg})
    assert isinstance(mc, Listed)
    assert mc.layer_types == ("sliding", "full")
    assert mc.window_pattern == ((3, 1024), (1, 0))
    assert mc.moe == base.MoEConfig(n_experts=4, top_k=2)
    assert mc.ssm == base.SSMConfig(state_dim=8, head_dim=32)
    assert hash(mc) == hash(model_config({"program": cfg}))
