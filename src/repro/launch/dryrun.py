import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax-importing module: jax locks the
# device count at first init, and the production dry-run needs 512 CPU
# placeholder devices to build the 16x16 and 2x16x16 meshes.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell.

The target is XLA's CPU backend on 512 placeholder devices, not the TPU
compiler.  For each cell this shows:
  * the sharding plan is coherent (GSPMD partitions every op),
  * the CPU backend's per-device memory_analysis (not a TPU HBM fit),
  * and the roofline inputs (cost_analysis + HLO collective bytes).
What the TPU compiler accepts is checked by tests/test_tpu_compile.py.

Usage:
  python -m repro.launch.dryrun --arch granite-3-8b --shape train_4k
  python -m repro.launch.dryrun --all                  # single-pod 16x16
  python -m repro.launch.dryrun --all --multi-pod      # 2 pods, 512 chips
"""
import argparse
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, get_config
from repro.configs.registry import cells
from repro.launch.hlo_analysis import collective_stats, roofline_terms
from repro.launch.mesh import make_production_mesh
from repro.models import init_kv_cache, init_params
from repro.optim import make_optimizer
from repro.runtime.planner import plan_for_cell
from repro.runtime.serve import build_decode_step, build_prefill_step
from repro.runtime.train import build_train_step

I32 = jnp.int32
BF16 = jnp.bfloat16


def input_specs(arch: str, shape: str):
    """ShapeDtypeStruct stand-ins for every model input of the cell."""
    cfg = get_config(arch)
    S, B, kind = SHAPES[shape]
    sds = jax.ShapeDtypeStruct
    if kind in ("train", "prefill"):
        batch = {}
        if cfg.frontend == "audio_stub":
            batch["frontend_embeds"] = sds((B, S, cfg.d_model), BF16)
        elif cfg.frontend == "vision_stub":
            batch["tokens"] = sds((B, S - cfg.frontend_tokens), I32)
            batch["frontend_embeds"] = sds((B, cfg.frontend_tokens, cfg.d_model), BF16)
        else:
            batch["tokens"] = sds((B, S), I32)
        if kind == "train":
            batch["labels"] = sds((B, S), I32)
        return batch
    # decode: one token against an S-long cache
    caches = jax.eval_shape(lambda: init_kv_cache(cfg, B, S, BF16))
    return {
        "token": sds((B, 1), I32),
        "position": sds((B,), I32),
        "caches": caches,
    }


def _lower_cell(cfg, arch, shape, mesh, plan, S, B, kind, params_s, specs):
    if kind == "train":
        step, _ = build_train_step(cfg, mesh, plan)
        init_fn, _u = make_optimizer(cfg.optimizer)
        opt_s = jax.eval_shape(init_fn, params_s)
        return step.lower(params_s, opt_s, specs)
    if kind == "prefill":
        step, _ = build_prefill_step(cfg, mesh, plan)
        if cfg.frontend == "audio_stub":
            return step.lower(params_s, specs["frontend_embeds"])
        if cfg.frontend == "vision_stub":
            return step.lower(params_s, specs["tokens"], specs["frontend_embeds"])
        return step.lower(params_s, specs["tokens"])
    step, _ = build_decode_step(cfg, mesh, plan, batch=B, max_len=S)
    return step.lower(params_s, specs["token"], specs["position"], specs["caches"])


def run_cell(arch: str, shape: str, multi_pod: bool, use_dse: bool = True,
             plan_override=None, scan_correct: bool = True,
             force_accum1: bool = True) -> dict:
    cfg = get_config(arch)
    if force_accum1 and cfg.accum_steps != 1:
        # The grad-accumulation lax.scan body is also trip-counted once by
        # cost_analysis; lower with accum=1 so roofline terms are per full
        # batch (accum is purely a temp-memory knob -- see SSPerf).
        import dataclasses as _dc
        cfg = _dc.replace(cfg, accum_steps=1)
    S, B, kind = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)
    chips = mesh.size
    plan = plan_override or plan_for_cell(
        cfg, S, B, axes, model_axis=mesh.shape["model"], kind=kind,
        use_dse=use_dse,
    )
    dp_size = 1
    for a in axes:
        if a in ("pod", "data"):
            dp_size *= mesh.shape[a]
    if B % dp_size != 0:
        import dataclasses
        plan = dataclasses.replace(plan, use_dp=False)
    specs = input_specs(arch, shape)
    params_s = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))

    t0 = time.time()
    lowered = _lower_cell(cfg, arch, shape, mesh, plan, S, B, kind, params_s, specs)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    try:
        mem = compiled.memory_analysis()
        mem_d = {
            "argument_size_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_size_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_size_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_size_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # noqa: BLE001
        mem_d = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        cost_d = {k: float(v) for k, v in cost.items()
                  if isinstance(v, (int, float))} if cost else {}
    except Exception as e:  # noqa: BLE001
        cost_d = {"error": str(e)}
    hlo = compiled.as_text()
    coll = collective_stats(hlo)

    flops = cost_d.get("flops", 0.0)
    bytes_ = cost_d.get("bytes accessed", 0.0)
    coll_bytes = coll.total_bytes
    scan_info = {"corrected": False}
    R = cfg.pattern_repeats
    if scan_correct and R > 1:
        # XLA cost_analysis counts a while-loop body ONCE regardless of trip
        # count.  Re-lower with scan unroll=2 (each scan body duplicated once,
        # compile stays cheap) and extrapolate:
        #   true ~ u1 + (R - n_scans)/n_scans * (u2 - u1)
        # where n_scans is 1 (single zone) or 2 (WSP->ISP split).
        import dataclasses as _dc
        cfg2 = _dc.replace(cfg, scan_unroll=2)
        low2 = _lower_cell(cfg2, arch, shape, mesh, plan, S, B, kind, params_s, specs)
        comp2 = low2.compile()
        cost2 = comp2.cost_analysis() or {}
        coll2 = collective_stats(comp2.as_text())
        n_scans = 2 if plan.transition_repeat not in (None, 0, R) else 1
        scale = (R - n_scans) / n_scans
        d_fl = max(0.0, float(cost2.get("flops", 0.0)) - flops)
        d_by = max(0.0, float(cost2.get("bytes accessed", 0.0)) - bytes_)
        d_co = max(0.0, coll2.total_bytes - coll_bytes)
        scan_info = {
            "corrected": True, "n_scans": n_scans,
            "u1_flops": flops, "body_flops": d_fl,
        }
        flops = flops + scale * d_fl
        bytes_ = bytes_ + scale * d_by
        coll_bytes = coll_bytes + scale * d_co
    # NOTE: the partitioned HLO is per-device, so flops/bytes/collective
    # byte counts are already per chip.
    terms = roofline_terms(flops, bytes_, coll_bytes, chips)
    result = {
        "arch": arch, "shape": shape, "kind": kind,
        "mesh": {"axes": list(axes), "shape": [mesh.shape[a] for a in axes],
                 "chips": chips},
        "plan": {"p1": plan.p1, "p2": plan.p2,
                 "transition_repeat": plan.transition_repeat,
                 "dse_meta": {k: v for k, v in plan.meta.items()}},
        "lower_s": t_lower, "compile_s": t_compile,
        "memory_analysis": mem_d,
        "cost_analysis": {k: cost_d.get(k) for k in
                          ("flops", "bytes accessed", "optimal_seconds")
                          if k in cost_d},
        "corrected": {"flops": flops, "bytes": bytes_,
                      "collective_bytes": coll_bytes, **scan_info},
        "collectives": coll.to_dict(),
        "roofline": terms,
        "hlo_bytes": len(hlo),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), default=None)
    ap.add_argument("--shape", choices=sorted(SHAPES), default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-dse", action="store_true")
    ap.add_argument("--out-dir", default="benchmarks/results/dryrun")
    args = ap.parse_args()

    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]

    os.makedirs(args.out_dir, exist_ok=True)
    mesh_tag = "pod2x16x16" if args.multi_pod else "pod16x16"
    n_devices = len(jax.devices())
    assert n_devices >= (512 if args.multi_pod else 256), n_devices

    failures = []
    for arch, shape in todo:
        tag = f"{arch}__{shape}__{mesh_tag}"
        out_path = os.path.join(args.out_dir, tag + ".json")
        print(f"=== {tag}", flush=True)
        try:
            res = run_cell(arch, shape, args.multi_pod, use_dse=not args.no_dse)
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
            r = res["roofline"]
            print(
                f"    ok: compile={res['compile_s']:.1f}s "
                f"flops={res['cost_analysis'].get('flops', 0):.3e} "
                f"coll={res['collectives']['total_bytes']:.3e}B "
                f"dominant={r['dominant']}",
                flush=True,
            )
        except Exception as e:  # noqa: BLE001
            failures.append((tag, str(e)))
            traceback.print_exc()
    if failures:
        print(f"FAILED {len(failures)} cells:")
        for tag, err in failures:
            print(f"  {tag}: {err[:200]}")
        raise SystemExit(1)
    print("all cells passed")


if __name__ == "__main__":
    main()
