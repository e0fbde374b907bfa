"""A whole run of a small cell on the CPU: set-up, warm-up, window and the
comparison with the reference.  A sound program reads correct; a token
altered where the step produces it reads not correct."""
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
