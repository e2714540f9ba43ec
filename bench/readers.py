"""Arithmetic shared by the per-layer metric readers of ``bench/metrics``
(``read(ctx)`` each).

``ctx``: ``cell`` (harness.Cell), ``window`` (the driver's window record),
``trace`` (devtrace.Trace, or None without ``--trace 1``), ``peaks`` (this
device's row of peaks.json) and ``chips``.
"""
from __future__ import annotations

from harness import arch


def work(ctx) -> list[tuple[float, float]]:
    """(FLOPs, bytes) the model needs for each step or call of the window,
    by the architecture's counts (``work`` of ``bench/archs``)."""
    cell = ctx["cell"]
    return arch(cell).work(cell.cfg, ctx["window"])


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and which."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")


def roofline(ctx, kind: str):
    """Least time of the window's steps over the step program's device
    time, in %; each step's least time is its larger bound."""
    w, t = ctx["window"], ctx["trace"]
    if t is None or w["kind"] != kind:
        return None
    dev_s, runs = t.program(w["module"])
    if not runs or dev_s <= 0:
        return None
    pk = ctx["peaks"]
    least = [least_time(f, b, pk["bf16_flops_per_s"] * ctx["chips"],
                        pk["hbm_bytes_per_s"] * ctx["chips"])[0]
             for f, b in work(ctx)]
    # mean over host-counted steps against mean over traced executions
    return 100.0 * (sum(least) / len(least)) / (dev_s / runs)


def mfu(ctx, kind: str):
    w = ctx["window"]
    if w["kind"] != kind:
        return None
    flops = sum(f for f, _ in work(ctx))
    peak = ctx["peaks"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (w["wall_s"] * peak)


def step_ms(ctx, kind: str):
    w, t = ctx["window"], ctx["trace"]
    if t is None or w["kind"] != kind:
        return None
    dev_s, runs = t.program(w["module"])
    return 1e3 * dev_s / runs if runs else None


def idle_share(ctx, kind: str):
    w, t = ctx["window"], ctx["trace"]
    if t is None or w["kind"] != kind:
        return None
    return 100.0 * t.idle_share()
