"""Records the small trace that ``test_devtrace.py`` reads, on a chip.

    python bench/tests/record_trace.py [OUT_DIR]

Runs the decode driver on the tiny cell for a fraction of a second under
the profiler and writes ``decode.xplane.pb`` and the events read from it,
``decode_events.json``, to OUT_DIR (``bench/tests/data``).  It needs a TPU.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
import tiny  # noqa: E402

import devtrace  # noqa: E402
import harness  # noqa: E402


def main() -> int:
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 1
    cell = tiny.cell("decode", seconds=0.05)
    driver = harness.load_module(harness.BENCH / "drivers" / "decode_closed.py")
    state = driver.setup(cell)
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    win = driver.window(cell, state)
    jax.profiler.stop_trace()
    (pb,) = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
    out = Path(sys.argv[1] if len(sys.argv) > 1
               else Path(__file__).resolve().parent / "data")
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(pb, out / "decode.xplane.pb")
    ev = devtrace.events(pb)
    (out / "decode_events.json").write_text(json.dumps(ev))
    (out / "decode_window.json").write_text(json.dumps(
        {"steps": win["steps"], "module": win["module"]}))
    shutil.rmtree(d)
    print(f"{os.path.getsize(out / 'decode.xplane.pb')} bytes, "
          f"{win['steps']} steps")
    return 0


if __name__ == "__main__":
    sys.exit(main())
