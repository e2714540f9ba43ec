"""One run of one cell: set-up, the measured window, the check, the result.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json``  -- sizes as run, source, cut, deployment;
* ``bench/archs/<model_type>.py``  -- what depends on the architecture: the
  program's ModelConfig, the plain reference and the counts (``arch``);
* ``bench/traffic/<traffic>.json`` -- a mix, read by ``gen.py`` and the
  driver the mix names (``bench/drivers/<driver>.py``);
* ``bench/metrics/<metric>.py``    -- ``read(ctx)`` of one per-layer metric;
* ``bench/limits/<workload>.json`` -- the limit of each number compared.

From the program the harness takes the system under test: ``scope.solve``
-> ``Solution.deploy`` -> ``Deployment.build_steps`` and the seeded
on-device weights (``runtime.serve.init_sharded_params``).
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import re
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


@dataclass
class Cell:
    """What one run of one cell knows before it starts."""
    workload: dict
    config: dict            # the configuration file
    traffic: dict           # the mix file
    limits: dict            # the limit file
    seed: int
    seconds: float
    trace: bool
    t_start: float          # process start, on perf_counter's clock
    spans: dict = field(default_factory=dict)   # host-clock set-up spans

    @property
    def cfg(self) -> dict:
        return self.config["config"]

    @classmethod
    def find(cls, bench: dict, name: str, **kw) -> "Cell":
        wl = next((w for w in bench["workloads"] if w["name"] == name), None)
        if wl is None:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        return cls(workload=wl,
                   config=load_json(BENCH / "configs" / f"{wl['config']}.json"),
                   traffic=load_json(BENCH / "traffic" / f"{wl['traffic']}.json"),
                   limits=load_json(BENCH / "limits" / f"{name}.json"), **kw)


# --------------------------------------------------------------- the program

def arch(cell: Cell):
    """The module of ``bench/archs`` named by the configuration's
    ``config.model_type``: ``program_config(config)`` (the program's
    ModelConfig), ``forward_rows(cfg, key, seqs, rows, quant=None)`` (the
    plain reference) and ``work(cfg, window)`` (FLOPs and bytes of each
    step).  Exits, naming the file it looked for, where there is none."""
    name = cell.cfg.get("model_type", "")
    path = BENCH / "archs" / f"{name}.py"
    if not re.fullmatch(r"[A-Za-z0-9_]+", name) or not path.is_file():
        raise SystemExit(f"{cell.config['name']}: no architecture file "
                         f"{path} for model_type {name!r}")
    return _load_arch(path)


@functools.cache
def _load_arch(path: Path):
    return load_module(path)


def solve_and_build(cell: Cell, mc, phase: str, seq_len: int, batch: int,
                    max_len: int | None, with_decode: bool):
    """scope.solve -> deploy -> make_mesh -> build_steps, timed as solve_s
    (the planner) and build_s (jit objects; compiling comes later)."""
    from repro import scope
    from repro.core import hw

    pkg = cell.config["package"]
    t = time.perf_counter()
    sol = scope.solve(scope.problem(
        scope.WorkloadSpec.lm([mc], seq_len, phase=phase),
        getattr(hw, pkg["preset"])(pkg["chips"], tuple(pkg["mesh"]))))
    dep = sol.deploy(global_batch=batch)
    cell.spans["solve_s"] = time.perf_counter() - t
    mesh = dep.make_mesh()
    steps = dep.build_steps(mesh, batch=batch, max_len=max_len,
                            with_decode=with_decode)[mc.name]
    plan = steps["plan"]
    log(f"plan: strategy={sol.strategy} phase={phase} p1={plan.p1} "
        f"p2={plan.p2} transition_repeat={plan.transition_repeat} "
        f"dp={plan.dp} mesh={dict(mesh.shape)} batch={batch} "
        f"seq_len={seq_len} max_len={max_len}")
    return mesh, steps


def init_weights(cell: Cell, mc, mesh, steps):
    import jax

    from gen import seed_key
    from repro.runtime.serve import init_sharded_params

    t = time.perf_counter()
    params = init_sharded_params(mc, mesh, steps["param_specs"],
                                 jax.numpy.asarray(seed_key(cell.seed)))
    jax.block_until_ready(params)
    cell.spans["init_s"] = time.perf_counter() - t
    return params


def hlo_module_name(compiled) -> str:
    head = compiled.as_text()[:400]
    return head.split("HloModule ", 1)[1].split(",", 1)[0].split()[0]


def free_device_memory() -> None:
    """Drop every array the program left on the devices, before the
    reference runs, so that the reference fits beside nothing."""
    import jax

    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


# ---------------------------------------------------------------- one run

COMPILE_EVENTS = {"/jax/core/compile/backend_compile_duration": "compiles",
                  "/jax/compilation_cache/cache_hits": "cache_hits",
                  "/jax/compilation_cache/cache_misses": "cache_misses"}
EVENTS = {v: 0 for v in COMPILE_EVENTS.values()}


def _count(event, *args, **kw):
    if event in COMPILE_EVENTS:
        EVENTS[COMPILE_EVENTS[event]] += 1


def count_compiles() -> None:
    """Counts, from here on, executables built (compiled or fetched from
    the persistent cache) and persistent-cache hits and misses."""
    import jax

    jax.monitoring.register_event_listener(_count)
    jax.monitoring.register_event_duration_secs_listener(_count)


def peaks_for(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def run(cell: Cell, bench: dict, devices, fault=None) -> dict:
    """Returns the result object; ``fault`` (tests only) wraps the timed
    path of the driver to plant a fault underneath it."""
    import check
    import devtrace as tr

    kind = devices[0].device_kind
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    state = driver.setup(cell, fault=fault)
    cell.spans["setup_s"] = time.perf_counter() - cell.t_start
    log(f"set-up {cell.spans['setup_s']:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in cell.spans.items() if k != "setup_s")
        + f"; in set-up {EVENTS}")

    trace_dir = tr.start() if cell.trace else None
    before = dict(EVENTS)
    gc.collect()
    gc.disable()            # no collector pause inside the window
    try:
        win = driver.window(cell, state)
    finally:
        gc.enable()
    win["compiles"] = EVENTS["compiles"] - before["compiles"]
    log(f"executables built inside the window: {win['compiles']}")
    tdata = tr.stop(trace_dir) if cell.trace else None

    used = state["devices"]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    sample = driver.sample(cell, state, win)
    del state
    free_device_memory()
    t = time.perf_counter()
    checks, failed = check.compare(cell, sample)
    log(f"reference over {len(sample['seqs'])} requests, "
        f"{len(sample['targets'])} served tokens: "
        f"{time.perf_counter() - t:.3f} s")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    wl = cell.workload["name"]
    if cell.trace:
        ctx = {"cell": cell, "window": win, "trace": tdata,
               "peaks": peaks_for(kind), "chips": len(used)}
        metrics = {}
        for m in bench["per_layer"]:
            if wl not in m.get("workloads", [wl]):
                continue
            v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        have = dict(win["metrics"], setup_s=cell.spans["setup_s"])
        metrics = {m["name"]: {"value": have[m["name"]], "unit": m["unit"]}
                   for m in bench["end_to_end"]
                   if wl in m.get("workloads", [wl])}
    out = {
        "correct": bool(correct),
        "attempted": win["attempted"],
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": devices[0].platform, "kind": kind,
                   "count": len(used), "memory_peak_bytes": int(peak)},
    }
    if tdata is not None:
        out["device"]["busy_s"] = tdata.busy_s()
        out["device"]["window_s"] = tdata.window_s
        out["breakdown"] = tdata.breakdown()
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}: {c['value']!r} (limit {c['limit']!r}) "
            f"{'pass' if c['value'] <= c['limit'] else 'FAIL'}")
    return out
