"""Device ms a step of the decode program's collective ops, averaged over the
devices, from the profiler trace and the program's optimized HLO.

An op event is named by its HLO instruction.  A collective reaches the
trace as a plain op (``all-reduce.5``), as an asynchronous pair
(``all-gather-start.2`` / ``all-gather-done.2``) or inside a fusion whose
computation holds one; every such event's time counts, and the pair counts
once.  The log holds
the HLO's count of collectives a step (an op inside the scan over the
blocks counts once a trip) beside the trace's count of collective events a
step on each device: the two have to agree.  0 on one chip, which runs no
collective.
"""
from __future__ import annotations

import bisect
import re

from harness import log
from scopes import parse, step_hlo

COLLECTIVE = re.compile(r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)(-start|-done)?$")
_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_WHILE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .* while\(.*"
                    r"condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CONST = re.compile(r"= s32\[\][^ ]* constant\((\d+)\)")
_LT = re.compile(r"compare\(.*direction=LT")


def trips(text: str) -> dict[str, tuple[str, int]]:
    """{body computation: (the while instruction, trips)} of each while
    loop whose condition is ``counter < constant``, as a scan's is (its
    counter starts at 0)."""
    comps: dict[str, list[str]] = {}
    lines: list[str] = []
    for line in text.splitlines():
        m = _HEAD.match(line)
        if m:
            lines = comps.setdefault(m.group(1), [])
        else:
            lines.append(line)
    out = {}
    for line in text.splitlines():
        m = _WHILE.match(line)
        if not m:
            continue
        cond = comps.get(m.group(2), [])
        bound = [int(c.group(1)) for c in map(_CONST.search, cond) if c]
        if len(bound) == 1 and any(_LT.search(x) for x in cond):
            out[m.group(3)] = (m.group(1), bound[0])
    return out


def collectives(text: str) -> tuple[dict[str, bool], int]:
    """({instruction whose events are collective time: True where it is
    the second half of an asynchronous pair}, collectives a step by the
    HLO, each op inside a while once a trip)."""
    ops = parse(text)["ops"]
    holds = {o["comp"] for o in ops.values() if COLLECTIVE.match(o["opcode"])}
    while True:         # computations that call one that holds a collective
        more = {o["comp"] for o in ops.values() if o["calls"] in holds} - holds
        if not more:
            break
        holds |= more
    up = {o["calls"]: (o["comp"], 1) for o in ops.values() if o["calls"]}
    for body, (name, n) in trips(text).items():
        up[body] = (ops[name]["comp"], n)

    def times(comp):
        if comp not in up:
            return 1
        caller, n = up[comp]
        return n * times(caller)

    fused = {o["calls"] for o in ops.values() if o["opcode"] == "fusion"}
    names, count = {}, 0
    for name, o in ops.items():
        op = o["opcode"]
        if o["comp"] in fused or not (
                COLLECTIVE.match(op) or op == "fusion" and o["calls"] in holds):
            continue        # inside a fusion the fusion is the event
        done = op.endswith("-done")
        names[name] = done
        count += 0 if done else times(o["comp"])
    return names, count


def collective_ms(trace, module: str, text: str):
    """(ms a step averaged over devices, collective events a step on each
    device, the HLO's count a step); None where the program never ran."""
    names, want = collectives(text)
    secs, counts, runs = [], [], 0
    for d in trace.devices:
        dev = trace.ev["devices"][d]
        mods = sorted((s, s + du) for n, s, du in dev["modules"]
                      if n.startswith(module) and trace.w0 <= s < trace.w1)
        runs = max(runs, len(mods))
        starts = [s for s, _ in mods]
        t = n = 0
        for nm, s, du in dev["ops"]:
            done = names.get(nm)
            if done is None:
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i < 0 or s >= mods[i][1]:
                continue        # not inside a step of the window
            t += du
            n += not done
        secs.append(t * 1e-9)
        counts.append(n)
    if not runs:
        return None
    return (1e3 * sum(secs) / len(secs) / runs, [c / runs for c in counts],
            want)


def read(ctx):
    w, t = ctx["window"], ctx["trace"]
    if t is None or w["kind"] != "decode":
        return None
    got = collective_ms(t, w["module"], step_hlo(ctx["cell"], "decode")[0])
    if got is None:
        return None
    ms, per_device, want = got
    log(f"collective_ms.decode: {ms:.6f} ms a step; collective events a step "
        f"by device {per_device}, by the HLO {want}")
    return ms
