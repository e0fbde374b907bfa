"""BENCHMARK.json keeps to the benchmark's contract, and every cell resolves
to its files by name."""
import importlib.util
import json
import re
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and not re.search(
        r"[\t\n\r]", s)


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["command"]) <= 32
    assert all(_line(w) for w in SPEC["command"])
    assert 1 <= SPEC["run_seconds"] <= 51
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()


def test_names_and_units_use_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in SPEC[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
            if "better" in e:
                assert e["better"] in ("lower", "higher")
    for w in SPEC["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in SPEC["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        group_names = [e["name"] for e in SPEC[group]]
        assert len(group_names) == len(set(group_names))


def test_entry_keys_and_lines():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and _line(w["why"])
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_setup_metric_and_time_budget():
    setup = [m for m in SPEC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]
    runs = 2 + 14 * 24
    assert runs * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def _reports(cell):
    e2e = {m["name"] for m in SPEC["end_to_end"]
           if cell in m.get("workloads", [cell])}
    pl = {m["name"] for m in SPEC["per_layer"]
          if cell in m.get("workloads", [cell])}
    return e2e, pl


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    w = {x["name"]: x for x in SPEC["workloads"]}[cell]
    conf = {c["name"]: c for c in SPEC["configs"]}[w["config"]]
    assert cell == f"{w['config']}.{w['traffic']}"
    assert conf["file"].startswith("bench/")
    body = json.loads((ROOT / conf["file"]).read_text())
    assert body["name"] == conf["name"] and "program" in body
    mix = json.loads((ROOT / "bench" / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    assert {"clients", "quantiles", "serve", "probe", "check"} <= set(mix)
    limits = json.loads((ROOT / "bench" / "limits" / f"{cell}.json")
                        .read_text())
    from bench.check import READINGS
    assert limits and set(limits) <= set(READINGS)
    e2e, pl = _reports(cell)
    assert "setup_s" in e2e and len(e2e) >= 2 and pl
    for name in pl:
        path = ROOT / "bench" / "metrics" / f"{name}.py"
        spec = importlib.util.spec_from_file_location("m", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(mod.read)


def test_per_layer_metrics_move_a_metric_their_cells_report():
    for m in SPEC["per_layer"]:
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert m["moves"] in _reports(cell)[0], (m["name"], cell)


def test_every_configuration_is_used():
    used = {w["config"] for w in SPEC["workloads"]}
    assert used == {c["name"] for c in SPEC["configs"]}
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("name", [c["name"] for c in SPEC["configs"]])
def test_every_configuration_names_a_family_with_the_whole_interface(name):
    import inspect
    from bench import families as F
    conf = {c["name"]: c for c in SPEC["configs"]}[name]
    body = json.loads((ROOT / conf["file"]).read_text())
    family = F.load(body)
    assert family.__name__ == body["family"]
    params = {n: list(inspect.signature(getattr(family, n)).parameters)
              for n in F.INTERFACE}
    assert params == {
        "canonical": ["cfg", "key"], "program_tree": ["cfg", "w"],
        "hidden_states": ["cfg", "w", "tokens", "low"],
        "logits": ["w", "h"],
        "token_flops": ["cfg", "attended", "head"],
        "chunk_flops": ["cfg", "start", "length"],
        "decode_attention_work": ["cfg", "cached"]}


def test_a_configuration_without_a_family_raises_naming_the_key():
    from bench import families as F
    with pytest.raises(ValueError, match="no 'family' key"):
        F.load({"name": "x", "program": {}})
    with pytest.raises(AttributeError, match="lacks"):
        F.load({"name": "x", "family": "bench.roofline"})
