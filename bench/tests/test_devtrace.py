"""The trace reduction on hand-made events and on a recorded chip trace."""
import json
from pathlib import Path

import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data"


def synthetic():
    # device 0: ops [0,10] [5,15] [20,30]; device 1: an all-reduce [20,30]
    # window [0,40]; host: dispatch [14,22], readback [30,38]
    return {
        "devices": {
            "/device:TPU:0": {
                "ops": [["fusion.1", 0, 10], ["fusion.2", 5, 10],
                        ["all-reduce.3", 20, 10]],
                "modules": [["jit_step(1)", 0, 15], ["jit_step(1)", 20, 10],
                            ["jit_other(2)", 15, 5]]},
            "/device:TPU:1": {
                "ops": [["fusion.1", 0, 40]],
                "modules": [["jit_step(1)", 0, 40]]},
        },
        "host": [["bench.window", 0, 40], ["bench.dispatch", 14, 8],
                 ["bench.readback", 30, 8], ["bench.step", 28, 12]],
    }


def test_union_and_clip():
    assert devtrace.union([(5, 15), (0, 10), (20, 30), (30, 31)]) == \
        [(0, 15), (20, 31)]
    assert devtrace.clip([(0, 15), (20, 31)], 10, 25) == [(10, 15), (20, 25)]


def test_busy_idle_program_collectives():
    t = devtrace.Trace(synthetic())
    assert t.window_s == pytest.approx(40e-9)
    assert t.busy_intervals("/device:TPU:0") == [(0, 15), (20, 30)]
    # device 0 busy 25, device 1 busy 40 -> mean 32.5 of 40
    assert t.busy_s() == pytest.approx(32.5e-9)
    assert t.idle_share() == pytest.approx(1 - 32.5 / 40)
    secs, runs = t.program("jit_step")
    assert runs == 2 and secs == pytest.approx((25e-9 + 40e-9) / 2)
    assert t.collective_s() == pytest.approx(5e-9)   # 10 on one of two


def test_gap_attribution():
    t = devtrace.Trace(synthetic())
    assert t.gaps("/device:TPU:0") == [(15, 20), (30, 40)]
    got = t.gap_attribution()
    # [15,20] under dispatch; [30,38] under readback (shorter than step);
    # [38,40] under step only
    assert got == pytest.approx({"bench.dispatch": 5e-9,
                                 "bench.readback": 8e-9, "bench.step": 2e-9})
    b = t.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(10e-9)]
    assert b["idle_gaps"][0][0] == "bench.readback"


def test_window_span_required():
    ev = synthetic()
    ev["host"] = ev["host"][1:]
    with pytest.raises(ValueError):
        devtrace.Trace(ev)


@pytest.mark.skipif(not (DATA / "decode_events.json").exists(),
                    reason="no recorded trace")
def test_recorded_decode_trace():
    ev = json.loads((DATA / "decode_events.json").read_text())
    win = json.loads((DATA / "decode_window.json").read_text())
    t = devtrace.Trace(ev)
    secs, runs = t.program(win["module"])
    assert runs == win["steps"]
    assert 0 < secs < t.window_s
    assert 0 < t.busy_s() <= t.window_s
    assert 0 <= t.idle_share() < 1
    assert t.collective_s() == 0          # one chip
    gaps = t.gap_attribution()
    assert sum(gaps.values()) == pytest.approx(t.window_s - t.busy_s())
    # the pb reads to the same events
    if (DATA / "decode.xplane.pb").exists():
        assert devtrace.events(str(DATA / "decode.xplane.pb")) == ev
