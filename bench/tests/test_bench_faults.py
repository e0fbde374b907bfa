"""Faults planted under the timed path, each of which the comparison has to
catch under the numbers the committed cell holds (``tiny.LIMITS``), and
the control: the reference one precision below, in the program's place."""
from bench import check as C
from bench.tests import tiny


def test_state_left_unchanged_is_not_correct():
    res = tiny.run(fault=tiny.on_engine(tiny.state_unchanged))
    assert res["correct"] is False


def test_half_the_batch_left_out_is_not_correct():
    res = tiny.run(fault=tiny.on_engine(tiny.half_batch))
    assert res["correct"] is False


def test_probe_fast_weights_left_unchanged_is_not_correct(monkeypatch):
    tiny.probe_frozen(monkeypatch)
    res = tiny.run()
    assert res["correct"] is False
    assert res["checks"]["score_gap"]["value"] > \
        res["checks"]["score_gap"]["limit"]


def test_control_reads_above_the_limits():
    res = tiny.run(control=True)
    assert res["correct"] is True
    ctrl, prog = res["control"], res["readings"]
    assert ctrl["tokens"] == prog["tokens"] > 0
    assert ctrl["mean_gap"] > 3 * max(prog["mean_gap"], 1e-7)
    assert C.verdict(ctrl, tiny.LIMITS) is False
    assert C.verdict(res["probe_frozen"], tiny.LIMITS) is False
