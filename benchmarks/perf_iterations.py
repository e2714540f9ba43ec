import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# must precede any jax import (see repro.launch.dryrun)

"""SSPerf hillclimb driver: hypothesis -> change -> re-lower -> measure.

For a chosen (arch x shape) cell, evaluates the baseline plan plus a set of
candidate changes (each one knob), re-runs the dry-run, and reports the three
roofline terms per variant.  Results feed EXPERIMENTS.md SSPerf.

  PYTHONPATH=src python -m benchmarks.perf_iterations --cell granite-3-8b:train_4k
"""
import argparse
import dataclasses
import json

from repro.configs import SHAPES, registry
from repro.launch.dryrun import run_cell
from repro.runtime.planner import plan_for_cell
from repro.runtime.sharding import ShardPlan

OUT_DIR = os.path.join(os.path.dirname(__file__), "results", "perf")


def variant_plan(base: ShardPlan, **kw) -> ShardPlan:
    return dataclasses.replace(base, **kw)


def run_variant(arch, shape, label, hypothesis, plan=None, cfg_patch=None,
                multi_pod=False):
    """Run one variant; cfg_patch temporarily replaces the registry config."""
    cfg0 = registry.ARCHS[arch]
    accum = (cfg_patch or {}).get("accum_steps", 1)
    try:
        if cfg_patch:
            registry.ARCHS[arch] = dataclasses.replace(cfg0, **cfg_patch)
        res = run_cell(arch, shape, multi_pod, plan_override=plan,
                       force_accum1=(accum == 1))
    finally:
        registry.ARCHS[arch] = cfg0
    if accum > 1:
        # the accumulation scan body is counted once; one body = one
        # microbatch => scale the whole-batch terms by A (temp memory is the
        # real per-microbatch footprint, which is accum's point)
        for k in ("flops", "bytes", "collective_bytes"):
            res["corrected"][k] *= accum
        for k in ("compute_s", "memory_s", "collective_s", "bound_s"):
            res["roofline"][k] *= accum
    r = res["roofline"]
    mem = res["memory_analysis"]
    dse_meta = res["plan"].get("dse_meta", {})
    row = {
        "label": label,
        "hypothesis": hypothesis,
        "plan": res["plan"],
        # DSE cost of producing this plan (FastCostModel; the memoized
        # engine from fastcost.py -- see BENCH_search_time.json for the
        # before/after sweep comparison).
        "dse_s": dse_meta.get("dse_s"),
        "dse_engine": dse_meta.get("dse_engine"),
        "compute_s": r["compute_s"],
        "memory_s": r["memory_s"],
        "collective_s": r["collective_s"],
        "bound_s": r["bound_s"],
        "dominant": r["dominant"],
        "temp_bytes": mem.get("temp_size_bytes"),
        "arg_bytes": mem.get("argument_size_bytes"),
        "flops": res["corrected"]["flops"],
        "hlo_bytes_accessed": res["corrected"]["bytes"],
        "collective_bytes": res["corrected"]["collective_bytes"],
    }
    print(f"  {label:34s} comp={r['compute_s']:.3f}s mem={r['memory_s']:.3f}s "
          f"coll={r['collective_s']:.3f}s bound={r['bound_s']:.3f}s "
          f"[{r['dominant']}] temp={(mem.get('temp_size_bytes') or 0)/2**30:.1f}GiB",
          flush=True)
    return row


def variants_for(arch: str, shape: str, axes, model_axis: int):
    """The enumerated candidate changes with their napkin-math hypotheses."""
    cfg = registry.ARCHS[arch]
    S, B, kind = SHAPES[shape]
    base = plan_for_cell(cfg, S, B, axes, model_axis=model_axis, kind=kind)
    R = cfg.pattern_repeats
    out = [("baseline(dse)", "paper-faithful DSE plan", base, None)]
    if kind != "decode":
        for t, tag in [(0, "all-ISP"), (R, "all-WSP"), (R // 2, "half")]:
            p1 = "WSP" if t > 0 else "ISP"
            p2 = "ISP" if t < R else "WSP"
            tr = None if t in (0, R) else t
            if (p1, p2, tr) == (base.p1, base.p2, base.transition_repeat):
                continue
            out.append((
                f"transition={tag}",
                "move WSP->ISP point: trades weight-gather traffic (WSP) "
                "against activation all-reduce (ISP)",
                variant_plan(base, p1=p1, p2=p2, transition_repeat=tr),
                None,
            ))
        out.append((
            "remat=off",
            "recompute costs ~1/3 extra flops + bytes; off => compute/memory "
            "terms drop, temp memory grows",
            base, {"remat": False},
        ))
        acc = cfg.accum_steps
        out.append((
            f"accum={max(2, acc * 2)}",
            "more microbatches: temp activation memory shrinks ~2x, "
            "roofline terms unchanged (same math)",
            base, {"accum_steps": max(2, acc * 2)},
        ))
        if cfg.moe is not None:
            out.append((
                "ep=off",
                "replicated experts: kills the EP all-to-all but multiplies "
                "weight memory by n_experts/model_axis",
                variant_plan(base, ep=False), None,
            ))
        out.append((
            "zero=off",
            "optimizer state replicated over data: argument bytes grow, "
            "removes the ZeRO gather collectives",
            variant_plan(base, zero=False), None,
        ))
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, help="arch:shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--only", default=None, help="comma list of variant labels")
    args = ap.parse_args()
    arch, shape = args.cell.split(":")
    axes = ("pod", "data", "model") if args.multi_pod else ("data", "model")

    print(f"== perf iterations for {arch} x {shape} ==", flush=True)
    rows = []
    for label, hyp, plan, patch in variants_for(arch, shape, axes, 16):
        if args.only and label not in args.only.split(","):
            continue
        try:
            rows.append(run_variant(arch, shape, label, hyp, plan, patch,
                                    args.multi_pod))
        except Exception as e:  # noqa: BLE001
            print(f"  {label:34s} FAILED: {str(e)[:160]}", flush=True)
            rows.append({"label": label, "hypothesis": hyp, "error": str(e)[:400]})

    os.makedirs(OUT_DIR, exist_ok=True)
    out_path = os.path.join(OUT_DIR, f"{arch}__{shape}.json")
    with open(out_path, "w") as f:
        json.dump(rows, f, indent=1)
    print(f"wrote {out_path}")


if __name__ == "__main__":
    main()
