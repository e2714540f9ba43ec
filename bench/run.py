"""Run one cell of the benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout that holds ``BENCHMARK.json``, ``bench/``
and the program under ``src/``.  It uses the chips of the machine it runs
on and exits non-zero, printing no result, where the configuration's
``model_type`` has no ``bench/archs/<model_type>.py``, JAX finds no TPU,
the machine has fewer chips than the cell asks for, or its device kind is
not in ``bench/peaks.json``.  Compiled programs are kept in
``<checkout>/.jax_cache``.

Progress and measurements go to standard error, whose last lines are each
number compared beside its limit.  The last line of standard output is the
result: ``{"correct", "attempted", "failed", "metrics", "device"[,
"breakdown"], "checks"}``; with ``--trace 0`` the metrics are the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (CHECKOUT / "src" / "repro").is_dir():
        return fail(f"no program under {CHECKOUT / 'src'}")
    sys.path[:0] = [str(BENCH), str(CHECKOUT / "src")]
    import harness

    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cell = harness.Cell.find(bench, args.workload, seed=args.seed,
                             seconds=args.seconds, trace=bool(args.trace),
                             t_start=T_START)
    harness.arch(cell)          # refuses a model_type with no file, here

    import jax

    harness.count_compiles()
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < cell.config["chips"]:
        return fail(f"the cell asks for {cell.config['chips']} chips; "
                    f"{len(devices)} are visible")
    harness.peaks_for(devices[0].device_kind)

    out = harness.run(cell, bench, devices)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
