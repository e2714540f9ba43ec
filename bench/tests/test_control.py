"""The control (bench/control.py) at a size the CPU holds: the reference
computed in a precision below bfloat16 has to rank other tokens first
than the program served, by wider gaps."""
import harness
import control
import tiny


def test_control_reads_above_the_program():
    c = tiny.cell("decode", seed=11, seconds=0.5)
    d = harness.load_module(harness.BENCH / "drivers" / "decode_closed.py")
    r = control.readings(c, d)
    assert r["tokens"] > 0 and set(control.CONTROLS) <= set(r)
    assert r["fp8"] > max(r["served"], 0.1)
    assert r["fp8_disagree"] > r["served_disagree"]
