"""Closed-loop decode instance at its KV capacity (mix ``driver:
decode_closed``).

Set-up: solve and deploy the decode phase, build the decode step for
``decode_slots`` slots and ``max_len`` positions, draw the weights on the
device, and write every slot's prompt K/V into the cache with the model's
own ``forward(..., collect_cache=True)`` under the deployment's shardings
(the runtime has no prefill step that returns the cache).

Window: every step feeds one token per slot at its own position through
the deployment's decode step, takes the greedy token over the real
vocabulary and brings it to the host, as a streaming server must.  A slot
that has served its request's output length starts the next request over
the same cached prompt: its position rewinds to the prompt's last token.
"""
from __future__ import annotations

import time

import numpy as np

from gen import Stream, quantile_pool, rng, tokens
from harness import (hlo_module_name, init_weights, log, program_config,
                     solve_and_build)


def _identity(name, fn):
    return fn


def _fill_fn(mc, mesh, plan, steps, prompt_pad, chunk):
    """jit: (params, tokens [chunk, prompt_pad], slots [chunk], caches) ->
    caches with those slots' first prompt_pad positions written."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import forward
    from repro.runtime.sharding import make_constrain, to_shardings

    c1 = make_constrain(mesh, plan, zone=1)
    c2 = make_constrain(mesh, plan, zone=2)
    p_sh = to_shardings(mesh, steps["param_specs"])
    k_sh = to_shardings(mesh, steps["cache_specs"])

    def fill(params, toks, slots, caches):
        _, kv = forward(params, mc, toks, constrain=c1, constrain2=c2,
                        transition_repeat=plan.transition_repeat,
                        collect_cache=True)
        if plan.transition_repeat is not None and c2 is not None:
            kv = jax.tree.map(lambda *z: jnp.concatenate(z, 0), *kv)
        return jax.tree.map(
            lambda c, new: c.at[:, slots, :prompt_pad].set(new.astype(c.dtype)),
            caches, kv)

    rep = NamedSharding(mesh, P())
    return jax.jit(fill, in_shardings=(p_sh, rep, rep, k_sh),
                   out_shardings=k_sh, donate_argnums=(3,))


def setup(cell, fault=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.runtime.serve import init_sharded_cache

    fault = fault or _identity
    tr, cfg = cell.traffic, cell.cfg
    mc = program_config(cell.config)
    B, S, V = cell.config["decode_slots"], tr["max_len"], cfg["vocab_size"]
    mesh, steps = solve_and_build(cell, mc, "decode", S, B, S, True)
    plan = steps["plan"]
    params = init_weights(cell, mc, mesh, steps)
    caches = init_sharded_cache(mc, mesh, steps["cache_specs"], B, S)

    lens = rng(cell.seed, "prompt_len").permutation(
        quantile_pool(tr["prompt_len"], B))
    r = rng(cell.seed, "prompt_tokens")
    prompts = [tokens(r, int(n), V) for n in lens]
    pad = min(S, -(-int(tr["prompt_len"]["hi"]) // 128) * 128)
    chunk = tr["fill_chunk"]

    tok_sh = NamedSharding(mesh, P(plan.dp, None))
    pos_sh = NamedSharding(mesh, P(plan.dp))
    t = time.perf_counter()
    fill = _fill_fn(mc, mesh, plan, steps, pad, chunk).lower(
        params, jax.ShapeDtypeStruct((chunk, pad), jnp.int32),
        jax.ShapeDtypeStruct((chunk,), jnp.int32), caches).compile()
    decode = steps["decode"].lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=pos_sh),
        caches).compile()
    def greedy(logits):
        return jnp.argmax(logits[:, -1, :V], -1).astype(jnp.int32)

    logits_sh = NamedSharding(mesh, P(plan.dp, None, "model"))
    greedy = jax.jit(greedy).lower(jax.ShapeDtypeStruct(
        (B, 1, mc.padded_vocab), jnp.float32, sharding=logits_sh)).compile()
    cell.spans["compile_s"] = time.perf_counter() - t

    t = time.perf_counter()
    for i in range(0, B, chunk):
        toks = np.zeros((chunk, pad), np.int32)
        for j, p in enumerate(prompts[i:i + chunk]):
            toks[j, :len(p)] = p
        caches = fill(params, toks, np.arange(i, i + chunk, dtype=np.int32),
                      caches)
    jax.block_until_ready(caches)
    cell.spans["fill_s"] = time.perf_counter() - t

    state = {
        "devices": list(mesh.devices.flat), "params": params,
        "caches": caches, "decode": fault("decode", decode),
        "greedy": fault("greedy", greedy), "tok_sh": tok_sh,
        "pos_sh": pos_sh, "prompts": prompts, "module": hlo_module_name(decode),
        "out_lens": Stream(tr["output_len"], tr["pool"],
                           rng(cell.seed, "output_len")),
    }
    # warm: the window's first step, twice (it rewrites the same position)
    t = time.perf_counter()
    for _ in range(2):
        tok, pos = _first_inputs(prompts)
        _step(state, tok, pos)
    cell.spans["warm_s"] = time.perf_counter() - t
    log(f"decode: {B} slots, max_len {S}, prompt lengths {sorted(lens)}; "
        f"module {state['module']}")
    return state


def _first_inputs(prompts):
    tok = np.array([p[-1] for p in prompts], np.int32)
    pos = np.array([len(p) - 1 for p in prompts], np.int32)
    return tok, pos


def _step(state, tok, pos):
    import jax
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.input"):
        tok_d = jax.device_put(tok[:, None], state["tok_sh"])
        pos_d = jax.device_put(pos, state["pos_sh"])
    with TraceAnnotation("bench.dispatch"):
        logits, state["caches"] = state["decode"](
            state["params"], tok_d, pos_d, state["caches"])
        nxt = state["greedy"](logits)
    with TraceAnnotation("bench.readback"):
        return np.asarray(nxt)


def window(cell, state) -> dict:
    from jax.profiler import TraceAnnotation

    prompts, out_lens = state["prompts"], state["out_lens"]
    B = len(prompts)
    tok, pos = _first_inputs(prompts)
    want = np.array(out_lens.take(B))
    served = [[] for _ in range(B)]
    finished = []                    # (slot, served tokens, rewound)
    starts = np.zeros(B, np.int64)   # requests started per slot
    arrivals, step_pos = [], []
    with TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            step_pos.append(pos.copy())
            nxt = _step(state, tok, pos)
            t = time.perf_counter()
            arrivals.append(t)
            with TraceAnnotation("bench.host"):
                for b in range(B):
                    served[b].append(int(nxt[b]))
                    if len(served[b]) == want[b]:
                        finished.append((b, served[b], starts[b] > 0))
                        served[b] = []
                        starts[b] += 1
                        want[b] = next(out_lens)
                        tok[b], pos[b] = prompts[b][-1], len(prompts[b]) - 1
                    else:
                        tok[b], pos[b] = nxt[b], pos[b] + 1
            if t - t0 >= cell.seconds:
                break
    steps = len(arrivals)
    gaps = np.diff(np.array([t0] + arrivals))
    wall = arrivals[-1] - t0
    med = float(np.median(gaps))
    log(f"window: {steps} steps, {steps * B} tokens in {wall:.6f} s; "
        f"{len(finished)} requests finished; tpot samples {len(gaps)}; "
        f"step gap median {med * 1e3:.3f} ms, max {gaps.max() * 1e3:.3f} ms, "
        f"{int((gaps > 2 * med).sum())} over twice the median "
        f"({float((gaps - med)[gaps > 2 * med].sum()):.3f} s beyond it)")
    return {
        "t0": t0, "t1": arrivals[-1], "kind": "decode",
        "steps": steps, "slots": B, "step_pos": step_pos, "wall_s": wall,
        "finished": finished, "attempted": len(finished),
        "metrics": {"decode_tok_s": steps * B / wall,
                    "tpot_p95_ms": float(np.percentile(gaps, 95)) * 1e3},
        "module": state["module"],
    }


def sample(cell, state, win) -> dict:
    """The longest finished request, then one drawn from the seed in each
    other group of neighbouring slots (``sample_requests`` groups), so that
    every part of the batch is compared; one that started after a rewind
    among them."""
    done, prompts = win["finished"], state["prompts"]
    if not done:
        return {"seqs": [], "rows": [], "targets": [], "n_requests": 0}
    r = rng(cell.seed, "sample")
    groups = np.array_split(np.arange(len(prompts)),
                            cell.traffic["sample_requests"])
    group_of = {int(b): g for g, slots in enumerate(groups) for b in slots}
    pick = [max(range(len(done)), key=lambda i: len(done[i][1]))]
    for g in range(len(groups)):
        if g == group_of[done[pick[0]][0]]:
            continue
        cand = [i for i in range(len(done)) if group_of[done[i][0]] == g]
        rewound = [i for i in cand if done[i][2]]
        if rewound and not any(done[i][2] for i in pick):
            cand = rewound
        if cand:
            pick.append(cand[int(r.integers(len(cand)))])
    seqs, rows, targets = [], [], []
    for k, i in enumerate(pick):
        b, out, _ = done[i]
        p = prompts[b]
        seqs.append(np.concatenate([p, np.array(out[:-1], np.int32)]))
        rows += [(k, len(p) - 1 + j) for j in range(len(out))]
        targets += out
    return {"seqs": seqs, "rows": rows, "targets": targets,
            "n_requests": len(pick)}
