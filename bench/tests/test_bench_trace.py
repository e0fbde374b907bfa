"""The trace reduction: busy union, idle share, idle gaps named by the
benchmark's spans, and kernel time by name — on hand-made events and on a
trace recorded on a TPU v5e."""
import json
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


def dev(name, s, e, where="/device:TPU:0"):
    return TR.Event("device", where, name, s, e)


def host(name, s, e):
    return TR.Event("host", "python3", name, s, e)


EVENTS = [
    host("bench.step", 0, 40), host("bench.dispatch", 14, 21),
    dev("while.1", 0, 12), dev("paged_flash_decode.6", 1, 11),
    dev("fusion.1", 12, 15),
    dev("paged_flash_decode", 20, 30), dev("paged_flash_decode_x", 28, 29),
]


def test_busy_is_the_union_of_device_ops():
    red = TR.reduce(EVENTS)
    assert red.window_s == pytest.approx(40e-9)
    assert red.busy_s == pytest.approx(25e-9)
    assert red.idle_share == pytest.approx(1 - 25 / 40)


def test_idle_gaps_are_named_by_the_innermost_span():
    red = TR.reduce(EVENTS)
    assert red.idle_gaps == [("bench.step", pytest.approx(10e-9)),
                             ("bench.dispatch", pytest.approx(5e-9))]


def test_kernel_time_by_name():
    red = TR.reduce(EVENTS)
    assert red.kernel_seconds("paged_flash_decode") == pytest.approx(20e-9)
    assert red.kernel_seconds("fusion") == pytest.approx(3e-9)
    assert red.kernel_seconds("absent") == 0.0


def test_device_ops_rank_self_time():
    ops = dict(TR.reduce(EVENTS).device_ops)
    assert TR.reduce(EVENTS).device_ops[0][0] == "paged_flash_decode.6"
    assert ops["while.1"] == pytest.approx(2e-9)        # 12 less its child
    assert ops["paged_flash_decode"] == pytest.approx(9e-9)
    assert sum(ops.values()) == pytest.approx(TR.reduce(EVENTS).busy_s)


def test_op_names_from_hlo_text():
    assert TR.op_name("%paged_flash_decode.6 = (f32[16,5,3,64]) "
                      "custom-call(s32[16,253] %x)") == "paged_flash_decode.6"
    assert TR.op_name("fusion.1") == "fusion.1"


def test_busy_is_averaged_over_chips_and_clipped_to_the_window():
    ev = [host("bench.step", 100, 200),
          dev("a", 50, 150, "/device:TPU:0"),
          dev("a", 100, 200, "/device:TPU:1")]
    red = TR.reduce(ev)
    assert red.busy_s == pytest.approx(75e-9)


def test_no_device_op_is_an_error():
    with pytest.raises(ValueError):
        TR.reduce([host("bench.step", 0, 10)])
    with pytest.raises(ValueError):
        TR.reduce([dev("a", 0, 10)])


@pytest.mark.parametrize("name", sorted(p.name for p in
                                        DATA.glob("trace_*.json")))
def test_recorded_chip_trace(name):
    rec = json.loads((DATA / name).read_text())
    events = [TR.Event(*e) for e in rec["events"]]
    red = TR.reduce(events)
    want = rec["expect"]
    assert red.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert red.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0.0 < red.busy_s <= red.window_s
    for kernel, seconds in want["kernel_s"].items():
        assert red.kernel_seconds(kernel) == pytest.approx(seconds,
                                                           rel=1e-9)
        assert seconds > 0
