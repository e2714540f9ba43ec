"""Profiler trace -> the numbers the per-layer metrics read.

Two stages, so that the second can be checked on a small recorded trace:

1. :func:`events` reads a ``.xplane.pb`` with nothing but JAX
   (``jax.profiler.ProfileData``): per device, its op events (line ``XLA
   Ops``) and its program events (line ``XLA Modules``); on the host, the
   harness's own spans (``jax.profiler.TraceAnnotation`` named ``bench.*``).
2. :class:`Trace` reduces those lists: busy intervals and their union,
   idle share, per-program device time, collective time, and idle gaps
   attributed to what the host was doing.

Times are nanoseconds on the trace's clock, which the profiler aligns
between host and devices.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import tempfile

COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)")
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
WINDOW = "bench.window"


def start() -> str:
    """Starts the profiler without its Python function tracer, whose events
    would slow the host loop and fill the host buffer."""
    import jax

    d = tempfile.mkdtemp(prefix="bench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(d, profiler_options=opts)
    return d


def stop(d: str) -> "Trace":
    import sys
    import time

    import jax

    t = time.perf_counter()
    jax.profiler.stop_trace()
    try:
        (path,) = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))
        tr = Trace(events(path))
        print(f"trace: {os.path.getsize(path)} bytes, "
              f"{sum(len(v['ops']) for v in tr.ev['devices'].values())} device "
              f"ops, read in {time.perf_counter() - t:.3f} s",
              file=sys.stderr, flush=True)
        return tr
    finally:
        shutil.rmtree(d, ignore_errors=True)


def events(path: str) -> dict:
    """{"devices": {plane: {"ops": [[name, start, dur]], "modules": [...]}},
    "host": [[name, start, dur]]} from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            dev = out["devices"].setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] += [[short(e.name), e.start_ns, e.duration_ns]
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith("bench.")]
    return out


def short(name: str) -> str:
    """An op event is named by its HLO line ("%all-reduce.3 = f32[..] ...");
    keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list[tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


class Trace:
    def __init__(self, ev: dict):
        self.ev = ev
        win = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW]
        if len(win) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(win)}")
        self.w0, self.w1 = win[0]
        self.devices = sorted(ev["devices"])

    @property
    def window_s(self) -> float:
        return (self.w1 - self.w0) * 1e-9

    def _ops(self, dev: str):
        return [(s, s + d) for _, s, d in self.ev["devices"][dev]["ops"]]

    def busy_intervals(self, dev: str) -> list[tuple[float, float]]:
        return clip(union(self._ops(dev)), self.w0, self.w1)

    def busy_s(self) -> float:
        """Seconds in the window in which an op ran, averaged over devices."""
        tot = [sum(e - s for s, e in self.busy_intervals(d)) for d in self.devices]
        return sum(tot) / len(tot) * 1e-9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def program(self, prefix: str) -> tuple[float, int]:
        """(seconds, executions) of the programs whose name starts with
        ``prefix``, inside the window, averaged over devices."""
        secs, runs = [], []
        for d in self.devices:
            mods = [(s, s + du) for n, s, du in self.ev["devices"][d]["modules"]
                    if n.startswith(prefix)]
            mods = clip(mods, self.w0, self.w1)
            secs.append(sum(e - s for s, e in mods) * 1e-9)
            runs.append(len(mods))
        return sum(secs) / len(secs), max(runs)

    def collective_s(self) -> float:
        """Seconds of collective ops in the window, averaged over devices."""
        tot = []
        for d in self.devices:
            iv = [(s, s + du) for n, s, du in self.ev["devices"][d]["ops"]
                  if COLLECTIVE.match(n)]
            tot.append(sum(e - s for s, e in clip(iv, self.w0, self.w1)))
        return sum(tot) / len(tot) * 1e-9

    def gaps(self, dev: str) -> list[tuple[float, float]]:
        """Idle intervals of ``dev`` inside the window."""
        out, t = [], self.w0
        for s, e in self.busy_intervals(dev):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.w1:
            out.append((t, self.w1))
        return out

    def gap_attribution(self) -> dict[str, float]:
        """Idle seconds of the first device, split by the innermost host
        span (``bench.*``, not the window) that covers each part of a gap;
        the rest is ``host:other``."""
        spans = sorted((s, s + d, n) for n, s, d in self.ev["host"]
                       if n != WINDOW)
        starts = [s for s, _, _ in spans]
        longest = max((e - s for s, e, _ in spans), default=0)
        out: dict[str, float] = {}
        for gs, ge in self.gaps(self.devices[0]):
            lo = bisect.bisect_left(starts, gs - longest)
            hi = bisect.bisect_right(starts, ge)
            near = [x for x in spans[lo:hi] if x[1] > gs]
            # cut the gap at every span edge inside it; label each piece by
            # the shortest span that covers it
            cuts = sorted({gs, ge} | {x for s, e, _ in near for x in (s, e)
                                      if gs < x < ge})
            for a, b in zip(cuts, cuts[1:]):
                cover = [(e - s, n) for s, e, n in near if s <= a and e >= b]
                name = min(cover)[1] if cover else "host:other"
                out[name] = out.get(name, 0.0) + (b - a) * 1e-9
        return out

    def top_ops(self, n: int = 10) -> list[list]:
        """Ops with the most device time in the window (first device)."""
        tot: dict[str, float] = {}
        d = self.devices[0]
        for name, s, du in self.ev["devices"][d]["ops"]:
            if CONTAINER.match(name):       # spans the ops of its body
                continue
            for a, b in clip([(s, s + du)], self.w0, self.w1):
                tot[name] = tot.get(name, 0.0) + (b - a) * 1e-9
        return [[k, v] for k, v in sorted(tot.items(), key=lambda x: -x[1])[:n]]

    def breakdown(self) -> dict:
        gaps = sorted(self.gap_attribution().items(), key=lambda x: -x[1])
        return {"device_ops": self.top_ops(),
                "idle_gaps": [[k, v] for k, v in gaps[:10]]}
