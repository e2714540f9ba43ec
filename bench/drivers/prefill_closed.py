"""Closed-loop prefill instance (mix ``driver: prefill_closed``).

Set-up: solve and deploy the prefill phase, build its prefill step,
compile it once for each bucket's shape, draw the weights on the device
and run each bucket once.

Window: prompts are drawn one after another; each waits in its bucket
(the smallest that holds it, padded at the end) until the bucket holds its
batch, then the call is issued through the deployment's prefill step.  The
first token of each prompt (greedy over the real vocabulary at its last
real position) is brought to the host before the next call: one call at a
time.  A prompt's time to first token runs from its call's issue, input
transfer included, to that read-back.
"""
from __future__ import annotations

import time

import numpy as np

from gen import Stream, bucket_of, rng, tokens
from harness import arch, hlo_module_name, init_weights, log, solve_and_build


def _identity(name, fn):
    return fn


def setup(cell, fault=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    fault = fault or _identity
    tr, cfg = cell.traffic, cell.cfg
    mc = arch(cell).program_config(cell.config)
    V = cfg["vocab_size"]
    mesh, steps = solve_and_build(cell, mc, "prefill", tr["plan_seq_len"],
                                  tr["plan_batch"], None, False)
    plan = steps["plan"]
    params = init_weights(cell, mc, mesh, steps)
    tok_sh = NamedSharding(mesh, P(plan.dp, None))
    out_sh = NamedSharding(mesh, P(plan.dp, None, "model"))

    def first(logits, last):
        rows = logits[jnp.arange(logits.shape[0]), last, :V]
        return jnp.argmax(rows, -1).astype(jnp.int32)

    t = time.perf_counter()
    calls, module = [], None
    for S, B in tr["buckets"]:
        pre = steps["prefill"].lower(
            params, jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=tok_sh)
        ).compile()
        fst = jax.jit(first).lower(
            jax.ShapeDtypeStruct((B, S, mc.padded_vocab), jnp.float32,
                                 sharding=out_sh),
            jax.ShapeDtypeStruct((B,), jnp.int32)).compile()
        module = module or hlo_module_name(pre)
        calls.append((fault("prefill", pre), fault("first", fst)))
    cell.spans["compile_s"] = time.perf_counter() - t

    state = {"devices": list(mesh.devices.flat), "params": params,
             "calls": calls, "tok_sh": tok_sh,
             "module": module,
             "lengths": Stream(tr["prompt_len"], tr["pool"],
                               rng(cell.seed, "prompt_len")),
             "tokens": rng(cell.seed, "prompt_tokens")}
    t = time.perf_counter()
    for b, (S, B) in enumerate(tr["buckets"]):
        _call(state, [np.zeros(1, np.int32)] * B, S, b)
    cell.spans["warm_s"] = time.perf_counter() - t
    log(f"prefill: buckets {tr['buckets']}; module {state['module']}")
    return state


def _call(state, prompts, S, b):
    import jax
    from jax.profiler import TraceAnnotation

    pre, fst = state["calls"][b]
    with TraceAnnotation("bench.input"):
        toks = np.zeros((len(prompts), S), np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p
        last = np.array([len(p) - 1 for p in prompts], np.int32)
        toks_d = jax.device_put(toks, state["tok_sh"])
    with TraceAnnotation("bench.dispatch"):
        logits = pre(state["params"], toks_d)
        out = fst(logits, last)
    with TraceAnnotation("bench.readback"):
        out = np.asarray(out)
    del logits
    return out


def window(cell, state) -> dict:
    from jax.profiler import TraceAnnotation

    buckets = cell.traffic["buckets"]
    V = cell.cfg["vocab_size"]
    pending = [[] for _ in buckets]
    served = []                          # (prompt tokens, first token, ttft)
    calls, ends = [], []
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with TraceAnnotation("bench.host"):
                n = next(state["lengths"])
                b = bucket_of(n, buckets)
                pending[b].append(tokens(state["tokens"], n, V))
            if len(pending[b]) < buckets[b][1]:
                continue
            prompts, pending[b] = pending[b], []
            t_issue = time.perf_counter()
            first = _call(state, prompts, buckets[b][0], b)
            t = time.perf_counter()
            served += [(p, int(f), t - t_issue) for p, f in zip(prompts, first)]
            calls.append((b, [len(p) for p in prompts]))
            ends.append(t)
            if t - t0 >= cell.seconds:
                break
    wall = ends[-1] - t0
    real = sum(len(p) for p, _, _ in served)
    ttft = np.array([x for _, _, x in served])
    log(f"window: {len(calls)} calls, {len(served)} prompts, {real} real "
        f"tokens in {wall:.6f} s; calls per bucket "
        f"{np.bincount([b for b, _ in calls], minlength=len(buckets)).tolist()}"
        f"; ttft samples {len(ttft)}")
    return {
        "t0": t0, "t1": ends[-1], "kind": "prefill", "calls": calls,
        "wall_s": wall, "served": served, "attempted": len(served),
        "module": state["module"],
        "metrics": {"prefill_tok_s": real / wall,
                    "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3},
    }


def sample(cell, state, win) -> dict:
    """The longest prompt served and others drawn from the seed, up to
    ``sample_requests``; one served token (the first) each."""
    served = win["served"]
    if not served:
        return {"seqs": [], "rows": [], "targets": [], "n_requests": 0}
    r = rng(cell.seed, "sample")
    longest = max(range(len(served)), key=lambda i: len(served[i][0]))
    rest = [i for i in range(len(served)) if i != longest]
    n = min(len(rest), cell.traffic["sample_requests"] - 1)
    pick = [longest] + [rest[i] for i in r.choice(len(rest), n, replace=False)]
    seqs = [served[i][0] for i in pick]
    return {"seqs": seqs, "rows": [(k, len(s) - 1) for k, s in enumerate(seqs)],
            "targets": [served[i][1] for i in pick], "n_requests": len(pick)}
