"""The split of the step program's device time by named scope: the HLO
labels on a hand-written module, the reduction on hand-made events, the
executables rebuilt as the drivers build them, and a recorded chip trace."""
import json
from pathlib import Path

import pytest

import devtrace
import scopes

DATA = Path(__file__).resolve().parent / "data"

HLO = """HloModule jit_step, is_scheduled=true

%fused_a (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %m = f32[4]{0} multiply(%p0, %p0), metadata={op_name="jit(step)/layers/while/body/closed_call/ffn/mul"}
  ROOT %e = f32[4]{0} exponential(%m), metadata={op_name="jit(step)/layers/while/body/closed_call/ffn/exp"}
}

%fused_b (p0.1: f32[4]) -> f32[4] {
  %p0.1 = f32[4]{0} parameter(0)
  %n = f32[4]{0} negate(%p0.1), metadata={op_name="jit(step)/layers/while/body/closed_call/attn/neg"}
  ROOT %s = f32[4]{0} sine(%n), metadata={op_name="jit(step)/layers/while/body/closed_call/attn/kv_write/sin"}
}

%body (t: (s32[], f32[4])) -> (s32[], f32[4]) {
  %t = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[4]{0} get-tuple-element(%t), index=1
  %dot.1 = f32[4]{0} dot(%x, %x), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/layers/while/body/closed_call/attn/dot_general" source_file="m.py" source_line=3}
  %fusion.1 = f32[4]{0} fusion(%dot.1), kind=kLoop, calls=%fused_a
  %fusion.2 = f32[4]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_b, metadata={op_name="jit(step)/layers/while/body/closed_call"}
  %dynamic-slice.1 = f32[4]{0} dynamic-slice(%x, %i), dynamic_slice_sizes={4}, metadata={op_name="jit(step)/layers/while/body/dynamic_slice"}
  %copy.3 = f32[4]{0} copy(%fusion.2)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%i, %copy.3)
}

%cond (t.1: (s32[], f32[4])) -> pred[] {
  %t.1 = (s32[], f32[4]{0}) parameter(0)
  %j = s32[] get-tuple-element(%t.1), index=0
  %c = s32[] constant(2)
  ROOT %lt = pred[] compare(%j, %c), direction=LT
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %gather.1 = f32[4]{0} gather(%a, %a), metadata={op_name="jit(step)/embed/jit(_take)/gather"}
  %zero = s32[] constant(0)
  %tuple.0 = (s32[], f32[4]{0}) tuple(%zero, %gather.1)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body
  %copy.9 = f32[4]{0} copy(%a)
  ROOT %dot.2 = f32[4]{0} dot(%copy.9, %a), lhs_contracting_dims={0}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/head/dot_general"}
}
"""

WANT = {"dot.1": "attn", "fusion.1": "ffn", "fusion.2": "attn/kv_write",
        "dynamic-slice.1": "layers", "copy.3": "layers", "gather.1": "embed",
        "copy.9": "", "dot.2": "head"}


def test_labels_follow_the_four_rules():
    """own op_name; a fusion's shared inner scope (the root's where they
    disagree); a while body's op -> layers; else unscoped."""
    hlo = scopes.parse(HLO)
    assert hlo["loops"] == {"body"}
    assert {n: scopes.label(hlo, n) for n in WANT} == WANT
    assert hlo["ops"]["while.1"]["opcode"] == "while"
    assert hlo["ops"]["tuple.0"]["shape"] == "(s32[], f32[4]{0})"
    assert scopes.has_scopes(hlo)
    assert not scopes.has_scopes(scopes.parse(
        HLO.replace("layers/", "").replace("attn/", "").replace("/ffn", "")
        .replace("/kv_write", "").replace("embed/", "").replace("head/", "")))


def test_scope_path():
    assert scopes.scope_path("jit(s)/layers/while/body/attn/kv_write/x") == \
        "attn/kv_write"
    assert scopes.scope_path("jit(s)/layers/while/body/dynamic_slice") == \
        "layers"
    assert scopes.scope_path("jit(s)/jit(_take)/gather") == ""


def _events(ops, modules, window=(0, 1000)):
    return {"devices": {"/device:TPU:0": {"ops": ops, "modules": modules}},
            "host": [["bench.window", window[0], window[1] - window[0]]]}


# each execution: while.1 spans the body; 10 ns per op but 5 idle at the end
RUN = ["gather.1", "while.1", "dot.1", "fusion.1", "fusion.2",
       "dynamic-slice.1", "copy.3", "copy.9", "dot.2"]


def _run(t0, names=RUN, step=10):
    ops, t = [], t0
    for nm in names:
        if nm == "while.1":
            ops.append([nm, t, 5 * step])
            continue
        ops.append([nm, t, step])
        t += step
    return ops


def test_split_buckets_sum_to_the_program():
    ops = _run(100) + _run(300) + [["other.1", 250, 10]]
    mods = [["jit_step(1)", 100, 85], ["jit_other(2)", 250, 10],
            ["jit_step(1)", 300, 85]]
    t = devtrace.Trace(_events(ops, mods))
    res = scopes.split(t, "jit_step", [scopes.parse(HLO)])
    b = res["buckets"]
    assert b == pytest.approx({"attn": 40e-9, "ffn": 20e-9, "scan": 40e-9,
                               "embed_head": 40e-9, "unscoped": 20e-9})
    assert res["paths"]["attn/kv_write"] == pytest.approx(20e-9)
    assert res["unmatched_s"] == 0
    secs, runs = t.program("jit_step")
    # 80 of 85 ns a run are ops; the rest is idle inside the program
    assert sum(b.values()) == pytest.approx(secs * 80 / 85)
    assert res["idle_s"] == pytest.approx(secs * 5 / 85)
    assert list(res["ops"]) == [("copy.9", "f32[4]{0}")]


def test_split_matches_executables_by_call_order():
    """Two executables share instruction names but not their labels: the
    call order decides; a count of executions other than the calls' splits
    nothing."""
    other = scopes.parse(HLO.replace('head/dot_general', 'ffn/dot_general'))
    mods = [["jit_step(1)", 100, 85], ["jit_step(2)", 300, 85]]
    t = devtrace.Trace(_events(_run(100) + _run(300), mods))
    hlos = [scopes.parse(HLO), other]
    res = scopes.split(t, "jit_step", hlos, order=[1, 0])
    assert res["buckets"]["ffn"] == pytest.approx(30e-9)
    assert res["buckets"]["embed_head"] == pytest.approx(30e-9)
    assert scopes.split(t, "jit_step", hlos, order=[0]) is None


def test_unmatched_ops_are_unscoped_and_counted():
    ops = _run(100, RUN + ["mystery.7"])
    t = devtrace.Trace(_events(ops, [["jit_step(1)", 100, 95]]))
    res = scopes.split(t, "jit_step", [scopes.parse(HLO)])
    assert res["unmatched_s"] == pytest.approx(10e-9)
    assert res["buckets"]["unscoped"] == pytest.approx(20e-9)


def test_stalls_split_long_steps():
    # steps end (read-back) at 100, 200, 300, 600: the last gap is 300
    host = [["bench.window", 0, 700]]
    for a in (0, 100, 200):
        host += [["bench.dispatch", a + 10, 10], ["bench.readback", a + 50, 50]]
    host += [["bench.input", 300, 200], ["bench.readback", 550, 50]]
    mods = [["jit_step(1)", a + 20, 30] for a in (0, 100, 200)] + \
        [["jit_step(1)", 500, 40]]
    ops = [["fusion.1", s, d] for _, s, d in mods]
    t = devtrace.Trace({"devices": {"/device:TPU:0": {"ops": ops,
                                                      "modules": mods}},
                        "host": host})
    (g,) = scopes.stalls(t, "jit_step")
    assert g["gap_ms"] == pytest.approx(300e-6)
    assert g["step_ms"] == pytest.approx(40e-6)
    assert g["idle_ms"] == pytest.approx({"bench.input": 200e-6,
                                          "bench.readback": 50e-6,
                                          "host:other": 10e-6})


def test_scope_ms_reads_nothing_without_scopes(monkeypatch):
    bare = HLO
    for s in ("layers/", "attn/", "/ffn", "/kv_write", "embed/", "head/"):
        bare = bare.replace(s, "")
    def ctx():
        t = devtrace.Trace(_events(_run(100), [["jit_step(1)", 100, 85]]))
        return {"cell": None, "trace": t,
                "window": {"kind": "decode", "module": "jit_step", "steps": 1}}

    monkeypatch.setattr(scopes, "step_hlo", lambda cell, kind: [bare])
    assert scopes.scope_ms(ctx(), "decode", "attn") is None
    monkeypatch.setattr(scopes, "step_hlo", lambda cell, kind: [HLO])
    c = ctx()
    assert scopes.scope_ms(c, "decode", "attn") == pytest.approx(2e-5)
    # the other buckets of the run read the same reduction: no rebuild
    monkeypatch.setattr(scopes, "step_hlo", None)
    assert scopes.scope_ms(c, "decode", "ffn") == pytest.approx(1e-5)
    assert scopes.scope_ms(c, "prefill", "attn") is None


def test_scope_ms_reads_one_scope_by_name(monkeypatch):
    """One scope from the paths the split collects, beside the buckets: a
    scope takes every path that holds it, and one that never ran reads
    nothing."""
    res = {"runs": 2, "buckets": {"attn": 0.006},
           "paths": {"attn": 0.004, "attn/kv_write": 0.002, "mamba": 0.01,
                     "moe": 0.03, "layers": 0.5}}
    monkeypatch.setattr(scopes, "reduce", lambda ctx, kind: res)
    assert scopes.scope_ms({}, "decode", "attn") == pytest.approx(3.0)
    assert scopes.scope_ms({}, "decode", scope="attn") == pytest.approx(3.0)
    assert scopes.scope_ms({}, "decode", scope="kv_write") == pytest.approx(1.0)
    assert scopes.scope_ms({}, "decode", scope="mamba") == pytest.approx(5.0)
    assert scopes.scope_ms({}, "decode", scope="moe") == pytest.approx(15.0)
    assert scopes.scope_ms({}, "decode", scope="rwkv") is None


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_step_hlo_is_what_the_driver_compiled(kind):
    """The executables rebuilt for the reduction are the driver's own: the
    same instructions with the same scopes (the HLO's table of source
    frames names the caller, and differs)."""
    import tiny

    import harness

    cell = tiny.cell(kind)
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    state = driver.setup(cell)
    ran = [state["decode"]] if kind == "decode" else \
        [pre for pre, _ in state["calls"]]
    def view(text):
        return {n: (o["comp"], o["opcode"], o["shape"], o["path"])
                for n, o in scopes.parse(text)["ops"].items()}

    spans = dict(cell.spans)
    assert [view(x) for x in scopes.step_hlo(cell, kind)] == \
        [view(c.as_text()) for c in ran]
    assert cell.spans == spans
    hlo = scopes.parse(ran[-1].as_text())
    # the CPU compiler's own dots (split from batched ones) carry no op_name
    # and count as the scan's; on the chip every dot is the program's
    dots = [scopes.label(hlo, n) for n, o in hlo["ops"].items()
            if o["opcode"] in ("dot", "convolution")]
    assert dots and all(p.split("/")[0] in ("attn", "ffn", "head", "layers")
                        for p in dots)


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_recorded_trace_closes_on_step_ms(kind):
    """Every op of the chip's step programs is found in the HLO of the
    executable that ran it (prefill: the bucket of each call, in the call
    order), and the buckets add up to the programs' device time but for
    the idle time inside them."""
    d = DATA / "scoped"
    if not (d / f"{kind}_events.json").exists():
        pytest.skip("no recorded trace")
    t = devtrace.Trace(json.loads((d / f"{kind}_events.json").read_text()))
    win = json.loads((d / f"{kind}_window.json").read_text())
    texts = sorted(d.glob(f"{kind}*.hlo.txt"))
    hlos = [scopes.parse(f.read_text()) for f in texts]
    order = [b for b, _ in win["calls"]] if kind == "prefill" else None
    res = scopes.split(t, win["module"], hlos, order)
    secs, runs = t.program(win["module"])
    assert runs == (win["steps"] if kind == "decode" else len(win["calls"]))
    assert res["unmatched_s"] == 0
    # the ops of a tiny step are short, so a few % of it is the launch gaps
    # between them: the buckets close on the time in which an op ran
    assert sum(res["buckets"].values()) == pytest.approx(secs - res["idle_s"],
                                                         rel=1e-6)
    assert res["idle_s"] < 0.1 * secs
    assert res["buckets"]["attn"] > 0 and res["buckets"]["ffn"] > 0
    assert res["buckets"]["embed_head"] > 0
    if kind == "decode":
        assert res["paths"]["attn/kv_write"] > 0
