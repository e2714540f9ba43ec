"""Readings that the limits of ``bench/limits`` are set from.

    python bench/control.py --workload <name> --seeds 1,2,3 --seconds 20

For each seed, in one process: the cell's set-up and a window at its own
load, then the sample of finished requests that a run compares.  Over that
sample it reads the program's number (the widest gap of a served token
below the reference's best, float32 reference) and the controls' (the
same gap for the token that the reference computed in int8, or in fp8,
puts first).
The control is fp8 (e4m3): it reads at least three times every sound
reading of the program in both cells, which int8 does in the decode cell
only.  The control has to come out far above every sound reading of the
program, or the comparison could not tell it from bfloat16.  The benchmark's own
runs do not run this.  One JSON line per seed goes to standard output.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent
CONTROLS = ("int8", "fp8")


def readings(cell, driver, controls=CONTROLS) -> dict:
    import check
    import harness

    state = driver.setup(cell)
    win = driver.window(cell, state)
    sample = driver.sample(cell, state, win)
    del state
    harness.free_device_memory()
    g = check.reference_gaps(cell, sample, controls=controls)
    out = {"seed": cell.seed, "requests": sample["n_requests"],
           "tokens": len(sample["targets"])}
    for k, v in g.items():
        out[k] = check.widest(v)
        out[k + "_disagree"] = int((v > 0).sum())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the controls on the first N seeds only")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(BENCH), str(CHECKOUT / "src")]
    import harness

    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        cell = harness.Cell.find(bench, args.workload, seed=seed,
                                 seconds=args.seconds, trace=False,
                                 t_start=time.perf_counter())
        driver = harness.load_module(
            BENCH / "drivers" / f"{cell.traffic['driver']}.py")
        ctl = CONTROLS if args.control_seeds is None or i < args.control_seeds \
            else ()
        print(json.dumps(readings(cell, driver, ctl)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
