"""The serving loop's own measurement: host spans and counters in the step
recorder (``repro.serving.tracing``), and the named scopes the
fused step carries into its compiled program."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.probe import ProbeConfig, init_outer
from repro.models import build
from repro.serving import (ChunkWork, ContinuousServingEngine, OrcaScheduler,
                           ServeConfig, make_request, replay_model,
                           replay_params)
from repro.serving import tracing
from repro.serving.tracing import CAPACITY, COUNTERS, StepRecorder

SPANS = ("orca.admit", "orca.compose", "orca.upload", "orca.dispatch",
         "orca.wait", "orca.readback", "orca.collect", "orca.prefill_done",
         "orca.consensus")
SCOPES = ("step", "chunk_prefill", "layers", "decode_attention", "kv_write",
          "mlp", "lm_head", "probe")


def _replay(seed=0, n=10, t=16, d=16):
    rs = np.random.RandomState(seed)
    bank = (rs.randn(n, t, d) * 0.6).astype(np.float32)
    model, params = replay_model(bank, prompt_len=4), replay_params(bank)
    pc = ProbeConfig(d_phi=d, smooth_window=2)
    theta = init_outer(pc, jax.random.PRNGKey(2))
    theta["b0"] = jnp.asarray(0.4)
    cfg = ServeConfig(tokens_per_step=1, max_new_tokens=t, lam=0.62,
                      burn_in=2)
    return model, params, pc, theta, cfg


def _requests():
    return [make_request(np.full((4,), i, np.int64), max_new_tokens=16,
                         priority=j % 2)
            for j, i in enumerate([9, 1, 5, 7, 3, 2, 8])]


def _serve(**kw):
    model, params, pc, theta, cfg = _replay()
    sched = OrcaScheduler(model, params, pc, theta, cfg, n_slots=3,
                          paged=True, block_size=4, **kw)
    done, fleet = sched.run(_requests())
    return sched, done, fleet


@pytest.fixture(scope="module")
def small_model():
    cfg = get_config("smollm_360m").reduced()
    model = build(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(small_model, **kw):
    model, params = small_model
    pc = ProbeConfig(d_phi=model.cfg.d_model, smooth_window=2)
    theta = init_outer(pc, jax.random.PRNGKey(1))
    cfg = ServeConfig(tokens_per_step=2, max_new_tokens=8, lam=0.6,
                      burn_in=1)
    return ContinuousServingEngine(model, params, pc, theta, cfg, 2, 32,
                                   paged=True, block_size=4, **kw)


# ---------------------------------------------------------------------------
# the recorder itself

def test_recorder_tallies_counts_over_the_session():
    rec = StepRecorder()
    segments = [2, 0, 5, 1] * (CAPACITY // 4) + [5, 3]
    for n in segments:
        with rec.step():
            rec.count("prefill_segments", n)
            rec.count("decode_rows", 3)
    assert len(rec.records) == CAPACITY             # the ring is bounded
    assert rec.records[-1].index == len(segments) - 1
    # the session's tallies are exact past the ring
    assert rec.total("prefill_segments") == sum(segments)
    assert rec.total("decode_rows") == 3 * len(segments)
    assert rec.peak("prefill_segments") == 5
    assert rec.steps_with("prefill_segments", 1) \
        == sum(n >= 1 for n in segments)
    assert rec.steps_with("prefill_segments", 2) \
        == sum(n >= 2 for n in segments)
    assert rec.total("absent") == rec.peak("absent") == 0
    # and so is every step's wall time
    assert len(rec.step_ms()) == len(segments)
    assert list(rec.step_ms())[-CAPACITY:] == [r.seconds * 1e3
                                               for r in rec.records]
    rec.reset()
    assert not rec.records and rec.total("decode_rows") == 0
    assert len(rec.step_ms()) == 0


def test_nested_step_is_the_open_step():
    rec = StepRecorder()
    with rec.step() as outer:
        with rec.step() as inner:
            with rec.span("orca.upload"):
                rec.count("reads", 2)
        assert inner is outer
    assert len(rec.records) == 1
    assert rec.records[0].counts == {"reads": 2}
    assert [n for n, _, _ in rec.records[0].spans] == ["orca.upload"]


# ---------------------------------------------------------------------------
# spans and counters of the serving loop

def test_every_span_nests_in_its_step():
    sched, done, fleet = _serve(chunk_tokens=3, token_budget=8)
    recs = list(sched.recorder.records)
    assert [r.index for r in recs] == list(range(fleet.engine_steps))
    for r in recs:
        names = [n for n, _, _ in r.spans]
        assert names == list(SPANS)              # each once, in step order
        assert all(r.t0 <= s <= e <= r.t1 for _, s, e in r.spans)
        starts = [s for _, s, _ in r.spans]
        assert starts == sorted(starts)
        assert set(r.counts) <= set(COUNTERS)
        # the spans account for the step but for a few lines between them
        assert sum(e - s for _, s, e in r.spans) <= r.seconds


def test_fleet_metrics_read_as_before():
    """The values the scheduler's own tallies gave on the same scenario
    (FIFO-free priority mix on the replay model), now derived from the
    recorder."""
    expect = {  # engine_steps, active_slot_steps, prefill_chunks,
        #         packed_chunks, peak_step_tokens, slot_utilization
        "packed": (16, 28, 10, 4, 5, 0.583333333333),
        "unpacked": (19, 28, 14, 0, 5, 0.491228070175),
        "spec": (8, 10, 7, 0, 10, 0.416666666667)}
    runs = {"packed": dict(chunk_tokens=3, token_budget=8, pack_chunks=True),
            "unpacked": dict(chunk_tokens=3, token_budget=8,
                             pack_chunks=False),
            "spec": dict(spec_tokens=3, chunk_tokens=4)}
    for name, kw in runs.items():
        sched, done, f = _serve(**kw)
        assert [r.stop_step for r in done] == [3, 3, 6, 6, 4, 3, 3]
        got = (f.engine_steps, f.active_slot_steps, f.prefill_chunks,
               f.packed_chunks, f.peak_step_tokens,
               round(f.slot_utilization, 12))
        assert got == expect[name], name
        stalls = [r.seconds * 1e3 for r in sched.recorder.records]
        assert len(stalls) == f.engine_steps
        assert f.stall_ms_p50 == pytest.approx(np.percentile(stalls, 50))
        assert f.stall_ms_p99 == pytest.approx(np.percentile(stalls, 99))


def test_stall_tails_cover_the_whole_session(monkeypatch):
    """A session longer than the ring of kept records: the stall
    percentiles still read every step's wall time."""
    monkeypatch.setattr(tracing, "CAPACITY", 4)
    sched, done, f = _serve(chunk_tokens=3, token_budget=8)
    rec = sched.recorder
    assert len(rec.records) == 4 < f.engine_steps
    stalls = list(rec.step_ms())
    assert len(stalls) == f.engine_steps == 16
    assert stalls[-4:] == [r.seconds * 1e3 for r in rec.records]
    assert f.stall_ms_p50 == pytest.approx(np.percentile(stalls, 50))
    assert f.stall_ms_p99 == pytest.approx(np.percentile(stalls, 99))


def test_counters_match_what_the_loop_does():
    sched, done, fleet = _serve(chunk_tokens=3, token_budget=8)
    recs = list(sched.recorder.records)
    # one-token decode: five outputs read back (tokens, stopped,
    # stop_step, n_scores, smoothed), chunk or none
    assert all(r.counts["reads"] == 5 for r in recs)
    assert any(r.counts.get("prefill_segments", 0) == 0 for r in recs)
    assert any(r.counts.get("prefill_segments", 0) >= 2 for r in recs)
    # every prompt token rides a step beside that step's decode rows
    assert sum(r.counts["step_tokens"] - r.counts["decode_rows"]
               for r in recs) == sum(r.prompt_len for r in done)
    assert sum(r.counts["decode_rows"] for r in recs) \
        == fleet.active_slot_steps


def test_reads_are_counted_where_they_happen(monkeypatch):
    """The count follows the engine's copies to the host: one fewer copy
    is one fewer read."""
    sched, done, fleet = _serve(chunk_tokens=3, token_budget=8)
    eng = sched._engine
    eng.recorder.reset()
    read = eng._read
    monkeypatch.setattr(eng, "_read",
                        lambda x: read(x) if x is not eng.st.smoothed
                        else np.asarray(x))
    eng.step(None)
    assert eng.recorder.records[-1].counts == {"reads": 4}


def test_spec_steps_read_more():
    sched, done, fleet = _serve(spec_tokens=3)
    for r in sched.recorder.records:
        assert r.counts["reads"] == 9             # + gen, seq, scores, n


def test_bare_engine_step_records_its_own_step(small_model):
    eng = _engine(small_model, chunk_tokens=4)
    eng.admit(0, {"tokens": jnp.arange(3)[None]}, 3, block_row=[1])
    eng.step()
    eng.step(ChunkWork.single(1, np.arange(6), 0, 4,
                              row=np.array([2, 3])))
    first, second = eng.recorder.records
    assert first.counts == second.counts == {"reads": 5}
    assert [n for n, _, _ in first.spans] == [
        "orca.upload", "orca.dispatch", "orca.wait", "orca.readback"]


def test_decode_blocks_are_counted_from_the_uploaded_positions(
        small_model, monkeypatch):
    """``attn_blocks_live`` / ``attn_blocks``: the paged decode kernel's
    compute blocks per layer over the rows with context, counted on the
    host from the positions the step uploads — no read more."""
    from repro.kernels.decode_attention import decode_pages_per_block
    monkeypatch.setenv("REPRO_PAGED_ATTN", "pallas")
    model, params = small_model
    pc = ProbeConfig(d_phi=model.cfg.d_model, smooth_window=2)
    theta = init_outer(pc, jax.random.PRNGKey(1))
    cfg = ServeConfig(tokens_per_step=2, max_new_tokens=8, lam=0.6,
                      burn_in=1)
    eng = ContinuousServingEngine(model, params, pc, theta, cfg, 4, 2048,
                                  paged=True, block_size=128)
    k = eng.state["k"]
    assert decode_pages_per_block(128, 16, k.shape[2], k.shape[-1],
                                  k.dtype.itemsize) == 8
    eng.pos = np.array([0, 1, 1024, 1025], np.int32)
    eng.step()
    eng.step()
    first, second = eng.recorder.records
    # 16 table entries of 128 positions: 2 blocks of 1024 per row.  Rows
    # at 1, 1024 and 1025 run 1, 1 and 2 of them; the row at 0 runs none.
    assert first.counts == {"reads": 5, "attn_blocks_live": 4,
                            "attn_blocks": 6}
    # every row moved one position on: 1, 2, 1025, 1026
    assert second.counts == {"reads": 5, "attn_blocks_live": 6,
                             "attn_blocks": 8}


# ---------------------------------------------------------------------------
# the device half: named scopes in the compiled step

def test_lowered_step_carries_every_scope(small_model):
    eng = _engine(small_model, chunk_tokens=4)
    text = eng.lowered_step().as_text(debug_info=True)
    for scope in ("step", "chunk_prefill", "probe", "lm_head"):
        assert f"orca/{scope}" in text, scope       # the serving phases
    # the model's phases, without the serving prefix (a scan body's
    # locations name the scopes relative to the body)
    for phase in ("layers", "decode_attention", "kv_write", "mlp",
                  "lm_head"):
        assert re.search(rf'"(?:[^"]*/)?{phase}/', text), phase
        assert f"orca/{phase}" not in text or phase == "lm_head"
    assert "orca/verify" not in text


def test_spec_step_carries_the_verify_scope(small_model):
    eng = _engine(small_model, chunk_tokens=4, spec_tokens=3)
    text = eng.lowered_step().as_text(debug_info=True)
    for scope in ("step", "chunk_prefill", "verify", "probe"):
        assert f"orca/{scope}" in text, scope


def test_compiled_text_names_the_scope_of_each_op(small_model):
    from bench import scopes as S
    eng = _engine(small_model, chunk_tokens=4)
    eng.step()
    module, paths = S.scope_map(eng.compiled_step_text())
    assert module == "jit_unified_step"
    assert eng.compile_counts()["step"] == 1
    inner = {p[-1] for p in paths.values() if p}
    assert set(SCOPES) - {"step"} <= inner
    assert ("step", "chunk_prefill") in set(paths.values())
    assert ("step", "layers", "decode_attention") in set(paths.values())
