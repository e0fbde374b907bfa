"""Device time by the program's phase scopes and host time by its
``orca.*`` spans (``bench.scopes``): the scope of each op from compiled HLO
text, self-time attribution, window clipping, idle gaps named by the
program's spans, and the readers of the five metrics built on them — on
hand-made input and on one step recorded on a TPU v5e."""
import json
import types
from pathlib import Path

import pytest

from bench import run as RUN
from bench import scopes as S
from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"
STEP = "jit(unified_step)/orca/step"

# the shape of the compiled step on a v5e: the chunk cond's inactive
# branch copies the page pools (copies XLA adds carry no metadata), the
# K/V scatter after the layer loop is copied back to the parameter layout
HLO = f"""HloModule jit_unified_step, is_scheduled=true

%region_0.1 (arg_tuple.1: (bf16[8], bf16[8])) -> (bf16[8]) {{
  %arg_tuple.1 = (bf16[8]{{0}}, bf16[8]{{0}}) parameter(0)
  %get-tuple-element.1 = bf16[8]{{0}} get-tuple-element(%arg_tuple.1), index=0
  %copy.179 = bf16[8]{{0}} copy(%get-tuple-element.1), backend_config={{"x":1}}
  ROOT %tuple.2 = (bf16[8]{{0}}) tuple(%copy.179)
}}

%fused_computation.3 (param_0: bf16[8]) -> bf16[8] {{
  %param_0 = bf16[8]{{0}} parameter(0)
  ROOT %mul.1 = bf16[8]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{STEP}/orca/chunk_prefill/cond/branch_1_fun/layers/while/body/mlp/mul"}}
}}

%region_1.2 (arg_tuple.2: (bf16[8], bf16[8])) -> (bf16[8]) {{
  %arg_tuple.2 = (bf16[8]{{0}}, bf16[8]{{0}}) parameter(0)
  %get-tuple-element.2 = bf16[8]{{0}} get-tuple-element(%arg_tuple.2), index=0
  %fusion.3 = bf16[8]{{0}} fusion(%get-tuple-element.2), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{STEP}/orca/chunk_prefill/cond/branch_1_fun/layers/while/body/mlp/mul"}}
  ROOT %tuple.3 = (bf16[8]{{0}}) tuple(%fusion.3)
}}

ENTRY %main.9 (cache.1: bf16[8], p.2: pred[]) -> bf16[8] {{
  %cache.1 = bf16[8]{{0}} parameter(0), metadata={{op_name="cache['k']"}}
  %p.2 = pred[] parameter(1)
  %copy-start = (bf16[8]{{0}}, bf16[8]{{0}}, u32[]) copy-start(%cache.1)
  %tuple.1 = (bf16[8]{{0}}, bf16[8]{{0}}) tuple(%cache.1, %cache.1), metadata={{op_name="{STEP}/orca/chunk_prefill/cond"}}
  %cond.17 = (bf16[8]{{0}}) conditional(%p.2, %tuple.1, %tuple.1), branch_computations={{%region_0.1, %region_1.2}}, metadata={{op_name="{STEP}/orca/chunk_prefill/cond"}}
  %get-tuple-element.3 = bf16[8]{{0}} get-tuple-element(%cond.17), index=0, metadata={{op_name="{STEP}/orca/chunk_prefill/cond"}}
  %fusion.10 = bf16[8]{{0}} fusion(%get-tuple-element.3), kind=kLoop, calls=%fused_computation.3, metadata={{op_name="{STEP}/kv_write/scatter;reshape"}}
  ROOT %copy.195 = bf16[8]{{0}} copy(%fusion.10), backend_config={{"x":2}}
}}
"""


def test_scope_of_each_op_from_its_own_operand_or_caller_metadata():
    module, paths = S.scope_map(HLO)
    assert module == "jit_unified_step"
    assert paths["cond.17"] == ("step", "chunk_prefill")
    # the inactive branch's copy: its caller's scope
    assert paths["copy.179"] == ("step", "chunk_prefill")
    assert paths["fusion.3"] == ("step", "chunk_prefill", "layers", "mlp")
    # the copy after the scatter: its operand's scope (first op name only)
    assert paths["copy.195"] == ("step", "kv_write")
    # a prefetch of a parameter has none
    assert paths["copy-start"] == ()
    assert paths["cache.1"] == ()


def test_phase_path_reads_phases_below_the_step_only():
    # the serving phases carry the orca/ prefix, the model's do not
    assert S.phase_path(f"{STEP}/orca/probe/cond/mul") == ("step", "probe")
    assert S.phase_path(f"{STEP}/layers/while/body/decode_attention/"
                        "pjit(paged_flash_decode)/custom_call") \
        == ("step", "layers", "decode_attention")
    # names that are no phase are left out; outside the step, no path
    assert S.phase_path(f"{STEP}/while/body/add") == ("step",)
    assert S.phase_path("jit(train_step)/mlp/dot_general") == ()
    assert S.phase_path(f"{STEP}/lm_head/argmax;{STEP}/probe/x") \
        == ("step", "lm_head")


def dev(name, s, e, where="/device:TPU:0"):
    return TR.Event("device", where, name, s, e)


def run(name, s, e, where="/device:TPU:0"):
    return TR.Event("module", where, name, s, e)


def host(name, s, e):
    return TR.Event("host", "python3", name, s, e)


SCOPES = {"cond.17": ("step", "chunk_prefill"),
          "copy.179": ("step", "chunk_prefill"),
          "paged_flash_decode.6": ("step", "layers", "decode_attention"),
          "while.14": ("step", "layers"),
          "copy.195": ("step", "kv_write"),
          "fusion.7": ("step",)}
EVENTS = [
    host("bench.step", 100, 300), host("orca.step", 104, 299),
    host("orca.dispatch", 104, 110), host("orca.wait", 110, 250),
    host("orca.readback", 250, 260), host("orca.collect", 260, 290),
    run("jit_unified_step", 105, 245),
    dev("cond.17", 105, 135), dev("copy.179", 106, 134),
    dev("while.14", 140, 220), dev("paged_flash_decode.6", 141, 219),
    dev("copy.195", 220, 240), dev("fusion.7", 240, 245),
    run("jit__reset_impl", 270, 280), dev("fusion.7", 271, 279),
    # before the window: clipped off
    run("jit_unified_step", 0, 95), dev("copy.179", 10, 90),
]


def test_self_time_by_scope_clipped_to_the_window():
    red = S.reduce(EVENTS, "jit_unified_step", SCOPES)
    assert red.steps == 1
    assert red.window_s == pytest.approx(200e-9)
    assert red.under("chunk_prefill") == pytest.approx(30e-9)
    assert red.under("layers") == pytest.approx(80e-9)
    assert red.under("decode_attention") == pytest.approx(78e-9)
    assert red.under("kv_write") == pytest.approx(20e-9)
    assert red.innermost()["layers"] == pytest.approx(2e-9)    # the while
    assert red.innermost()["chunk_prefill"] == pytest.approx(30e-9)
    # the other program's fusion.7 is not the step's fusion.7
    assert red.other_s == pytest.approx(8e-9)
    assert red.outside == [("fusion.7", pytest.approx(5e-9))]
    assert red.busy_s == pytest.approx(143e-9)
    assert red.covered == pytest.approx(130 / 143)
    assert red.host_s["orca.wait"] == pytest.approx(140e-9)


def test_idle_gaps_are_named_by_the_programs_spans():
    red = S.reduce(EVENTS, "jit_unified_step", SCOPES)
    assert red.idle_gaps == [("orca.readback", pytest.approx(26e-9)),
                             ("orca.collect", pytest.approx(21e-9)),
                             ("bench.step", pytest.approx(5e-9)),
                             ("orca.wait", pytest.approx(5e-9))]


# ---------------------------------------------------------------------------
# the readers

class Record(types.SimpleNamespace):
    @property
    def seconds(self):
        return self.t1 - self.t0

    def span_seconds(self, name):
        return sum(e - s for n, s, e in self.spans if n == name)


def ctx(**kw):
    window = types.SimpleNamespace(t_start=10.0, t_end=20.0,
                                   steps=[object()] * 2)
    return types.SimpleNamespace(window=window, **kw)


NEW = ("host_ms.decode", "syncs.decode", "chunk_path_ms.decode",
       "decode_attn_ms.decode", "probe_ms.decode")


@pytest.mark.parametrize("metric", NEW)
def test_readers_read_nothing_without_the_programs_records(metric):
    """A run of a program without spans, scopes or a trace."""
    assert RUN.reader(metric)(ctx()) is None
    assert RUN.reader(metric)(ctx(program=None, trace_dir=None)) is None
    # a scheduler without a step recorder or a compiled step's text
    bare = types.SimpleNamespace(_engine=types.SimpleNamespace())
    assert RUN.reader(metric)(ctx(program=bare, trace_dir="t")) is None


def test_host_readers_read_the_windows_steps_only():
    recs = [Record(t0=9.0, t1=10.5, spans=[("orca.wait", 9.1, 10.4)],
                   counts={"reads": 7}),            # began before the window
            Record(t0=11.0, t1=11.3, spans=[("orca.wait", 11.1, 11.25)],
                   counts={"reads": 5}),
            Record(t0=12.0, t1=12.3, spans=[("orca.wait", 12.0, 12.29)],
                   counts={"reads": 5})]
    program = types.SimpleNamespace(
        recorder=types.SimpleNamespace(records=recs))
    c = ctx(program=program, trace_dir=None)
    assert S.window_records(c) == recs[1:]
    host_ms = RUN.reader("host_ms.decode")(c)
    assert host_ms == pytest.approx(1e3 * (0.15 + 0.01) / 2)
    assert RUN.reader("syncs.decode")(c) == 5


def test_readers_read_the_program_and_trace_dir_from_ctx(tmp_path):
    recs = [Record(t0=11.0, t1=11.3, spans=[("orca.wait", 11.1, 11.25)],
                   counts={"reads": 5})]
    sched = types.SimpleNamespace(
        recorder=types.SimpleNamespace(records=recs),
        _engine=types.SimpleNamespace())
    c = ctx(program=sched, trace_dir=tmp_path)
    assert S.run_state(c) == (sched, str(tmp_path))
    got = [RUN.reader(m)(c) for m in NEW]
    assert got[:2] == [pytest.approx(150.0), 5]
    assert got[2:] == [None] * 3       # no compiled step text

    def run_cell(sched, tdir):
        """Locals named as ``bench.run.run_cell``'s: not read."""
        bare = ctx()
        return S.run_state(bare), [RUN.reader(m)(bare) for m in NEW]
    assert run_cell(sched, tmp_path) == ((None, None), [None] * len(NEW))


def test_device_readers_read_nothing_from_a_cpu_trace(tmp_path):
    """A trace with host spans and no device op, as the CPU records."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    assert not any(e.kind == "device" for e in S.load(str(tmp_path)))
    engine = types.SimpleNamespace(compiled_step_text=lambda: HLO)
    c = ctx(program=types.SimpleNamespace(_engine=engine),
            trace_dir=tmp_path)
    for metric in NEW[2:]:
        assert RUN.reader(metric)(c) is None


# ---------------------------------------------------------------------------
# one step recorded on a TPU v5e

def test_recorded_chip_step():
    rec = json.loads((DATA / "scopes_smollm-360m.reasoning.json")
                     .read_text())
    events = [TR.Event(*e) for e in rec["events"]]
    scopes = {n: tuple(p) for n, p in rec["scopes"].items()}
    red = S.reduce(events, rec["module"], scopes)
    want = rec["expect"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert red.covered == pytest.approx(want["covered"], rel=1e-9)
    for scope, seconds in want["under"].items():
        assert red.under(scope) == pytest.approx(seconds, rel=1e-9)
    assert [n for n, _ in red.idle_gaps] == [n for n, _ in
                                             want["idle_gaps"]]
    # what the step is made of: the decode kernel, the inactive chunk
    # branch's four page-pool copies, the copies after the K/V scatter
    assert red.under("decode_attention") / red.busy_s > 0.75
    assert [n for n, p in scopes.items() if p == ("step", "chunk_prefill")
            and n.startswith("copy.")] != []
    assert red.under("chunk_prefill") > 0.025
    assert red.under("kv_write") > 0.010
    assert red.covered > 0.95
    assert red.idle_gaps[0][0] == "orca.readback"
