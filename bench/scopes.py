"""Device time of the step program, split by the model's named scopes.

The program runs the parts of a step under fixed ``jax.named_scope`` names
(``models/model.py``): ``embed``, ``layers`` (the scan over the block
stack), per block the sequence mixer ``attn`` / ``mamba`` / ``rwkv``
(``attn/kv_write`` around the decode cache write) and the channel mixer
``ffn`` / ``moe``, then ``head``.  A name reaches the compiled program only
as the ``op_name`` metadata of its ops, and the profiler's op events carry
no metadata: an op event is looked up by instruction name in the optimized
HLO text of the executable that ran it, and labelled, in this order,

1. by the innermost program scope in its own ``op_name``;
2. for a fusion without one, or with none below ``layers`` (its
   ``op_name`` is then what its ops share, no more), by the scope that the
   ``op_name``s inside its fused computation share (the fused root's where
   they disagree);
3. for an op of a while body (the scan over the blocks), ``layers``;
4. else ``unscoped``: XLA's own copies land here.

Containers (``while``, ``conditional``, ``call``) span the ops of their
bodies and are skipped.  The labels fall into five buckets, which add up to
the program's device time but for the time inside it in which no op runs:
``attn`` (the sequence mixer, with ``kv_write``), ``ffn`` (``ffn``,
``moe``), ``scan`` (``layers`` outside any block), ``embed_head`` and
``unscoped`` (which also takes the ops not found in the HLO).

The run does not keep its executables, so the reader builds them again
the way the driver did, which the persistent compile cache answers; a
program without the scopes reads nothing.  Only ``--trace 1`` runs read
this.
"""
from __future__ import annotations

import bisect
import re
import statistics
import time
from dataclasses import replace

import devtrace
from harness import arch, log, solve_and_build

SCOPES = {"embed", "layers", "attn", "kv_write", "mamba", "rwkv", "ffn",
          "moe", "head"}
BLOCK = {"attn", "kv_write", "mamba", "rwkv", "ffn", "moe"}
BUCKET = {"attn": "attn", "kv_write": "attn", "mamba": "attn",
          "rwkv": "attn", "ffn": "ffn", "moe": "ffn", "embed": "embed_head",
          "head": "embed_head", "layers": "scan", "": "unscoped"}
BUCKETS = ("attn", "ffn", "scan", "embed_head", "unscoped")
CONTAINERS = {"while", "conditional", "call"}

_COMP = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\b(calls|body|to_apply|branch_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)")


# ------------------------------------------------------------ the HLO text

def _split(rhs: str) -> tuple[str, str, str]:
    """(shape, opcode, rest) of the right-hand side of an instruction."""
    if rhs.startswith("("):                 # tuple shape: balanced parens
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        shape, rest = rhs[:i + 1], rhs[i + 2:]
    else:
        shape, _, rest = rhs.partition(" ")
    opcode, _, rest = rest.partition("(")
    return shape, opcode, rest


def scope_path(op_name: str) -> str:
    """The program scopes in an ``op_name``, outermost first, joined by
    "/"; ``layers`` is dropped where a block scope follows it."""
    path = [c for c in op_name.split("/") if c in SCOPES]
    if len(path) > 1 and path[0] == "layers" and path[1] in BLOCK:
        path = path[1:]
    return "/".join(path)


def parse(text: str) -> dict:
    """"ops" {instruction name: {"comp", "opcode", "shape", "path",
    "calls"}} of one optimized HLO module; "roots" {computation: root};
    "paths" {computation: the scope paths of its ops}; "loops" (the
    computations that run inside a while)."""
    ops, roots, comp = {}, {}, None
    bodies, callees = set(), {}
    for line in text.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
            continue
        if comp is None or " = " not in line or not line.startswith(" "):
            continue
        lhs, rhs = line.strip().split(" = ", 1)
        root = lhs.startswith("ROOT ")
        name = lhs.removeprefix("ROOT ").lstrip("%")
        shape, opcode, rest = _split(rhs)
        called = {k: [c.strip().lstrip("%") for c in v.strip("{}").split(",")]
                  for k, v in _CALLS.findall(rest)}
        on = _OP_NAME.search(rest)
        ops[name] = {"comp": comp, "opcode": opcode, "shape": shape,
                     "path": scope_path(on.group(1)) if on else "",
                     "calls": called.get("calls", [None])[0]}
        if root:
            roots[comp] = name
        if opcode == "while":
            bodies.update(called.get("body", []))
        if opcode in ("call", "conditional"):
            callees.setdefault(comp, []).extend(
                called.get("to_apply", []) + called.get("branch_computations", []))
    paths = {}
    for o in ops.values():
        if o["path"]:
            paths.setdefault(o["comp"], set()).add(o["path"])
    loops, todo = set(), list(bodies)
    while todo:
        c = todo.pop()
        if c not in loops:
            loops.add(c)
            todo += callees.get(c, [])
    return {"ops": ops, "roots": roots, "paths": paths, "loops": loops}


def label(hlo: dict, name: str) -> str:
    """The scope path of one instruction by the four rules above ("" for
    unscoped)."""
    op = hlo["ops"][name]
    if op["path"] not in ("", "layers"):
        return op["path"]
    fused = _fused_path(hlo, op["calls"]) if op["opcode"] == "fusion" else ""
    if fused:
        return fused
    return "layers" if op["path"] or op["comp"] in hlo["loops"] else ""


def _fused_path(hlo: dict, comp: str | None) -> str:
    if comp is None:
        return ""
    inside = hlo["paths"].get(comp, set())
    if len(inside) == 1:
        return next(iter(inside))
    root = hlo["roots"].get(comp)
    return hlo["ops"][root]["path"] if inside and root else ""


def has_scopes(hlo: dict) -> bool:
    return any(o["path"] for o in hlo["ops"].values())


# ------------------------------------------------------------- the reduction

def split(trace: "devtrace.Trace", prefix: str, hlos: list[dict],
          order: list[int] | None = None) -> dict | None:
    """Seconds of the programs named ``prefix`` inside the window, by
    bucket and by scope path, averaged over devices; "idle_s", the time
    inside them in which no op ran.

    ``hlos`` holds one parsed executable per shape the driver compiled;
    where there are several, ``order`` gives for each execution in the
    window in turn the index of its executable (prefill: the bucket of each
    call).  None where a device ran another count of executions."""
    labels = [{} for _ in hlos]
    n = len(trace.devices)
    out = {"buckets": dict.fromkeys(BUCKETS, 0.0), "paths": {}, "ops": {},
           "unmatched_s": 0.0, "ops_s": 0.0, "idle_s": 0.0}
    for d in trace.devices:
        dev = trace.ev["devices"][d]
        mods = sorted((s, s + du) for nm, s, du in dev["modules"]
                      if nm.startswith(prefix))
        mods = devtrace.clip(mods, trace.w0, trace.w1)
        ops = sorted((s, s + du, nm) for nm, s, du in dev["ops"])
        starts = [s for s, _, _ in ops]
        if order is not None and len(order) != len(mods):
            return None
        for k, (ms, me) in enumerate(mods):
            inside = ops[bisect.bisect_left(starts, ms):
                         bisect.bisect_left(starts, me)]
            x = order[k] if order is not None else 0
            hlo, memo = hlos[x], labels[x]
            busy = []
            for s, e, nm in inside:
                op = hlo["ops"].get(nm)
                if (op["opcode"] if op else nm.split(".")[0]) in CONTAINERS:
                    continue
                busy.append((s, min(e, me)))
                sec = (min(e, me) - s) * 1e-9 / n
                if op is None:
                    path = ""
                    out["unmatched_s"] += sec
                else:
                    if nm not in memo:
                        memo[nm] = label(hlo, nm)
                    path = memo[nm]
                out["ops_s"] += sec
                b = BUCKET[path.rsplit("/", 1)[-1]]
                out["buckets"][b] += sec
                key = path or "unscoped"
                out["paths"][key] = out["paths"].get(key, 0.0) + sec
                if not path:
                    shape = op["shape"] if op else "?"
                    out["ops"][(nm, shape)] = out["ops"].get((nm, shape), 0.0) + sec
            busy_ns = sum(e - s for s, e in devtrace.union(busy))
            out["idle_s"] += (me - ms - busy_ns) * 1e-9 / n
    return out


def stalls(trace: "devtrace.Trace", prefix: str, most: int = 10) -> list[dict]:
    """The ``most`` longest decode steps whose gap (from one step's
    read-back to the next's end, the first from the window's start) is over
    twice the median: for each, the step program's device time in it and
    its idle time by host span (``bench.*``), on the first device."""
    ends = sorted(s + d for n, s, d in trace.ev["host"]
                  if n == "bench.readback" and trace.w0 <= s + d <= trace.w1)
    if len(ends) < 3:
        return []
    gaps = list(zip([trace.w0] + ends[:-1], ends))
    med = statistics.median(b - a for a, b in gaps)
    long = sorted((g for g in gaps if g[1] - g[0] > 2 * med),
                  key=lambda g: g[0] - g[1])
    dev = trace.ev["devices"][trace.devices[0]]
    host = [h for h in trace.ev["host"] if h[0] != devtrace.WINDOW]
    out = []
    for a, b in long[:most]:
        mods = devtrace.clip([(s, s + du) for nm, s, du in dev["modules"]
                              if nm.startswith(prefix)], a, b)
        sub = devtrace.Trace({"devices": {trace.devices[0]: dev},
                              "host": host + [[devtrace.WINDOW, a, b - a]]})
        out.append({"at_s": (a - trace.w0) * 1e-9, "gap_ms": (b - a) * 1e-6,
                    "median_ms": med * 1e-6,
                    "step_ms": sum(e - s for s, e in mods) * 1e-6,
                    "idle_ms": {k: v * 1e3 for k, v in sorted(
                        sub.gap_attribution().items(), key=lambda x: -x[1])}})
    return out


# ------------------------------------------------------ the executables

def step_hlo(cell, kind: str) -> list[str]:
    """Optimized HLO text of the step executables a run of ``cell``
    compiles (decode: one; prefill: one per bucket, in the mix's order),
    built as the driver builds them, on abstract arguments."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import init_kv_cache, init_params
    from repro.runtime.sharding import to_shardings

    def shaped(mesh, specs, make):
        return jax.tree.map(
            lambda s, x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            to_shardings(mesh, specs), jax.eval_shape(make))

    mc, tr = arch(cell).program_config(cell.config), cell.traffic
    quiet = replace(cell, spans={})        # keep the run's set-up spans
    if kind == "decode":
        B, S = cell.config["decode_slots"], tr["max_len"]
        mesh, steps = solve_and_build(quiet, mc, "decode", S, B, S, True)
    else:
        mesh, steps = solve_and_build(quiet, mc, "prefill", tr["plan_seq_len"],
                                      tr["plan_batch"], None, False)
    dp = steps["plan"].dp
    params = shaped(mesh, steps["param_specs"],
                    lambda: init_params(mc, jax.random.PRNGKey(0)))
    tok_sh = NamedSharding(mesh, P(dp, None))
    if kind == "decode":
        caches = shaped(mesh, steps["cache_specs"],
                        lambda: init_kv_cache(mc, B, S))
        return [steps["decode"].lower(
            params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh),
            jax.ShapeDtypeStruct((B,), jnp.int32,
                                 sharding=NamedSharding(mesh, P(dp))),
            caches).compile().as_text()]
    return [steps["prefill"].lower(
        params, jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_sh)
    ).compile().as_text() for S, B in tr["buckets"]]


# ------------------------------------------------------------- the metrics

def reduce(ctx, kind: str):
    """The split of this run's step program, computed once per run (kept
    in ``ctx``) and logged: None where the run is untraced, of another
    kind, or its program has no named scopes."""
    w, t = ctx["window"], ctx["trace"]
    if t is None or w["kind"] != kind:
        return None
    done = ctx.setdefault("scopes", {})
    if kind in done:
        return done[kind]
    t0 = time.perf_counter()
    hlos = [parse(x) for x in step_hlo(ctx["cell"], kind)]
    t1 = time.perf_counter()
    res = None
    if not any(has_scopes(h) for h in hlos):
        log(f"scopes.{kind}: the step program has no named scopes")
    else:
        order = [b for b, _ in w["calls"]] if kind == "prefill" else None
        res = split(t, w["module"], hlos, order)
        if res is None:
            log(f"scopes.{kind}: the program ran other than "
                f"{len(order)} times, once a call: not split")
        else:
            res["runs"] = t.program(w["module"])[1]
            _log(kind, t, w["module"], res, t1 - t0,
                 time.perf_counter() - t1)
    done[kind] = res
    return res


def _log(kind, t, module, res, build_s, reduce_s):
    step_s = t.program(module)[0]
    tot = sum(res["buckets"].values())
    log(f"scopes.{kind}: HLO built and parsed in {build_s:.3f} s, reduced in "
        f"{reduce_s:.3f} s; op time not found in the HLO "
        f"{100 * res['unmatched_s'] / max(res['ops_s'], 1e-12):.4f}%")
    log(f"scopes.{kind}: buckets (s in window) " + ", ".join(
        f"{b} {v:.6f}" for b, v in res["buckets"].items())
        + f"; sum {tot:.6f} of program {step_s:.6f} s, residual "
        f"{100 * (step_s - tot) / max(step_s, 1e-12):.3f}%, of which idle "
        f"inside the program {100 * res['idle_s'] / max(step_s, 1e-12):.3f}%")
    log(f"scopes.{kind}: by scope path (s) " + ", ".join(
        f"{k} {v:.6f}" for k, v in sorted(res["paths"].items(),
                                          key=lambda x: -x[1])))
    top = sorted(res["ops"].items(), key=lambda x: -x[1])[:5]
    log(f"scopes.{kind}: top unscoped ops " + "; ".join(
        f"{nm} {shape} {v:.6f} s" for (nm, shape), v in top))
    if kind == "decode":
        for g in stalls(t, module):
            log(f"scopes.decode: long step {g}")


def scope_ms(ctx, kind: str, bucket: str | None = None, *,
             scope: str | None = None):
    """Device ms per execution of the step program, averaged over devices
    (as ``step_ms``), of ``bucket``'s ops, or of the ops under the one
    named ``scope`` (every scope path that holds it: ``attn`` takes
    ``attn/kv_write``); None where the program ran none of them."""
    res = reduce(ctx, kind)
    if not res or not res["runs"]:
        return None
    if scope is None:
        return 1e3 * res["buckets"][bucket] / res["runs"]
    secs = [v for p, v in res["paths"].items() if scope in p.split("/")]
    return 1e3 * sum(secs) / res["runs"] if secs else None
