"""Records the small traces that ``test_scope_trace.py`` reads, on a chip.

    python bench/tests/record_scopes.py [OUT_DIR]

Runs the decode and the prefill driver on the tiny cell for a fraction of
a second each under the profiler, as the harness's traced runs do, and
writes to OUT_DIR (``bench/tests/data/scoped``), for each ``<kind>``: the
events read from the trace (``<kind>_events.json``), the window's record
(``<kind>_window.json``) and the optimized HLO text of each step
executable that ran (``<kind>.hlo.txt``; prefill ``prefill.<i>.hlo.txt``,
one per bucket in the mix's order).  It needs a TPU.
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import devtrace  # noqa: E402
import harness  # noqa: E402


def portable(text: str) -> str:
    """The HLO text without its table of source frames and without source
    locations in the metadata: names and op_names are what the tests read."""
    text = re.sub(r' (source_file="[^"]*"|source_line=\d+|stack_frame_id=\d+)',
                  "", text)
    keep, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif not (skip and line and line[0].isdigit()):
            skip = False
            keep.append(line)
    return "\n".join(keep) + "\n"


def record(kind: str, out: Path) -> None:
    cell = tiny.cell(kind, seconds=0.05)
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    state = driver.setup(cell)
    d = devtrace.start()
    win = driver.window(cell, state)
    t = devtrace.stop(d)
    (out / f"{kind}_events.json").write_text(json.dumps(t.ev))
    keep = {"steps": win.get("steps"), "module": win["module"],
            "calls": win.get("calls")}
    (out / f"{kind}_window.json").write_text(json.dumps(keep))
    if kind == "decode":
        (out / "decode.hlo.txt").write_text(portable(state["decode"].as_text()))
    else:
        for i, (pre, _) in enumerate(state["calls"]):
            (out / f"prefill.{i}.hlo.txt").write_text(portable(pre.as_text()))
    print(f"{kind}: {t.program(win['module'])[1]} executions of "
          f"{win['module']} in the window")


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_scopes.py: needs a TPU", file=sys.stderr)
        return 1
    out = Path(sys.argv[1] if len(sys.argv) > 1
               else Path(__file__).resolve().parent / "data" / "scoped")
    out.mkdir(parents=True, exist_ok=True)
    for kind in ("decode", "prefill"):
        record(kind, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
