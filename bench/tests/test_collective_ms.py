"""``collective_ms.decode``: the collective ops of a hand-written module and
of a recorded one-chip decode step, counted in the HLO and timed on
hand-made events."""
import json
from pathlib import Path

import pytest

import devtrace
import harness

DATA = Path(__file__).resolve().parent / "data"
M = harness.load_module(harness.BENCH / "metrics" / "collective_ms.decode.py")

HLO = """HloModule jit_step, is_scheduled=true

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%fused_ar (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %ar.in = f32[4]{0} all-reduce(%p0), replica_groups={{0,1}}, to_apply=%add
  ROOT %e = f32[4]{0} exponential(%ar.in)
}

%fused_plain (p0.1: f32[4]) -> f32[4] {
  %p0.1 = f32[4]{0} parameter(0)
  ROOT %n = f32[4]{0} negate(%p0.1)
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x.1 = f32[4]{0} get-tuple-element(%t), index=1
  %all-reduce.1 = f32[4]{0} all-reduce(%x.1), replica_groups={{0,1}}, to_apply=%add
  %fusion.2 = f32[4]{0} fusion(%all-reduce.1), kind=kLoop, calls=%fused_plain
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%i, %fusion.2)
}

%cond (t.1: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  %j = s32[] get-tuple-element(%t.1), index=0
  %c = s32[]{:T(128)} constant(3)
  ROOT %lt = pred[] compare(%j, %c), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[8] {
  %a = f32[4]{0} parameter(0)
  %all-gather-start.1 = (f32[4]{0}, f32[8]{0}) all-gather-start(%a), dimensions={0}, replica_groups={{0,1}}
  %all-gather-done.1 = f32[8]{0} all-gather-done(%all-gather-start.1)
  %fusion.1 = f32[4]{0} fusion(%a), kind=kOutput, calls=%fused_ar
  %zero = s32[] constant(0)
  %tuple.0 = (s32[], f32[4]{0}) tuple(%zero, %fusion.1)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body
  ROOT %copy.1 = f32[8]{0} copy(%all-gather-done.1)
}
"""

# one execution, 10 ns an op: the pair, the fusion, three loop trips
STEP = ["all-gather-start.1", "fusion.1", "while.1", "all-reduce.1",
        "fusion.2", "all-reduce.1", "fusion.2", "all-reduce.1", "fusion.2",
        "all-gather-done.1", "copy.1"]


def _device(t0, steps=2, names=STEP):
    ops, mods, t = [], [], t0
    for _ in range(steps):
        start = t
        for nm in names:
            ops.append([nm, t, 10])
            t += 10
        mods.append(["jit_step(1)", start, t - start])
        t += 100
    return {"ops": ops, "modules": mods}


def _trace(devices):
    return devtrace.Trace({"devices": devices,
                           "host": [["bench.window", 0, 10**6]]})


def test_hlo_counts_each_collective_once_a_trip():
    names, count = M.collectives(HLO)
    assert names == {"all-gather-start.1": False, "all-gather-done.1": True,
                     "fusion.1": False, "all-reduce.1": False}
    assert count == 1 + 1 + 3           # the pair, the fusion, the loop's op
    assert M.trips(HLO) == {"body": ("while.1", 3)}


def test_trace_counts_a_pair_once_and_a_fused_collective():
    t = _trace({"/device:TPU:0": _device(0), "/device:TPU:1": _device(5)})
    ms, per_device, want = M.collective_ms(t, "jit_step", HLO)
    assert per_device == [5, 5] and want == 5
    # start, done, the fusion and three loop all-reduces: 6 events of 10 ns
    assert ms == pytest.approx(60e-6)


def test_events_outside_the_step_or_the_window_do_not_count():
    dev = _device(0, steps=1)
    dev["ops"].append(["all-reduce.1", 10**5, 10])     # between programs
    late = _device(2 * 10**6, steps=1)                  # after the window
    dev["ops"] += late["ops"]
    dev["modules"] += late["modules"]
    ms, per_device, _ = M.collective_ms(_trace({"/device:TPU:0": dev}),
                                        "jit_step", HLO)
    assert per_device == [5] and ms == pytest.approx(60e-6)


def test_a_step_without_collectives_reads_zero():
    plain = HLO.replace("all-reduce(", "negate(").replace(
        "all-gather-start(%a), dimensions={0}, replica_groups={{0,1}}",
        "tuple(%a, %a)").replace("all-gather-done(", "get-tuple-element(")
    names, count = M.collectives(plain)
    assert (names, count) == ({}, 0)


def test_recorded_one_chip_decode_reads_zero():
    d = DATA / "scoped"
    t = devtrace.Trace(json.loads((d / "decode_events.json").read_text()))
    win = json.loads((d / "decode_window.json").read_text())
    ms, per_device, want = M.collective_ms(
        t, win["module"], (d / "decode.hlo.txt").read_text())
    assert (ms, per_device, want) == (0.0, [0.0], 0)
