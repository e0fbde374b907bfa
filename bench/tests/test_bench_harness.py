"""A whole run of a small cell on the CPU: set-up, warm-up, window and the
comparison with the reference.  A sound program reads correct; a token
altered where the step produces it reads not correct."""
import weakref

import pytest

from bench.tests import tiny


@pytest.mark.parametrize("moe", [False, True], ids=["dense", "moe"])
def test_sound_run_is_correct(moe):
    res = tiny.run(moe=moe)
    assert res["correct"] is True
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["attempted"] > 0 and res["failed"] == 0
    for v in res["checks"].values():
        assert v["value"] <= v["limit"]


def test_altered_token_is_not_correct():
    res = tiny.run(fault=tiny.on_engine(tiny.altered_token))
    assert res["correct"] is False
    assert res["checks"]["mean_gap"]["value"] > \
        res["checks"]["mean_gap"]["limit"]


def test_refuses_without_a_tpu(capsys):
    from bench import run as RUN
    rc = RUN.main(["--workload", "smollm-360m.reasoning", "--seed",
                   str(2 ** 40), "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "refusing" in out.err


@pytest.mark.parametrize("family, correct",
                         [("bench.tests.family_untied", True),
                          ("bench.tests.family_untied_embed_logits", False)],
                         ids=["sound", "logits_from_the_embedding"])
def test_family_from_outside_bench_families(family, correct):
    """An untied LM head, which the llama family cannot state, served and
    checked through a family module of the tests' own."""
    res = tiny.run(family=family, tied=False)
    assert res["correct"] is correct
    gap = res["checks"]["mean_gap"]
    assert (gap["value"] <= gap["limit"]) is correct


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
def test_scheduler_is_freed_before_the_reference_check(monkeypatch, trace):
    from bench import check as C
    sched, alive = [], []
    compare = C.compare

    def first_compare(*a, **k):
        if not alive:
            alive.append(sched[0]() is not None)
        return compare(*a, **k)
    monkeypatch.setattr(C, "compare", first_compare)
    if trace:
        # the CPU's trace has no device line: one op in each step's span
        from bench import trace as TR
        load = TR.load

        def loaded(trace_dir):
            events = load(trace_dir)
            return events + [TR.Event("device", "/device:TPU:0", "fusion.1",
                                      e.start_ns, e.end_ns)
                             for e in events if e.name == "bench.step"]
        monkeypatch.setattr(TR, "load", loaded)
    res = tiny.run(fault=lambda s: sched.append(weakref.ref(s)), trace=trace)
    assert res["correct"] is True and alive == [False]
    if trace:
        assert {"mfu.decode", "idle_share.decode", "host_ms.decode"} <= \
            set(res["metrics"])
