"""Closed-loop decode instance at its KV capacity (mix ``driver:
decode_closed``).

Set-up: solve and deploy the decode phase, build the decode step for
``decode_slots`` slots and ``max_len`` positions, draw the weights on the
device, and write every slot's prompt into the cache with the model's own
``forward(..., collect_cache=True)`` under the deployment's shardings (the
runtime has no prefill step that returns the cache).

The cache holds one entry per position of the model's expanded block
pattern, sorted here by the block kind that owns it.  A K/V entry
(``attn``, ``local``) is written by position.  Any other kind's entry is a
recurrent state with no positions axis: it is written whole for its slot,
from a forward over the prompt at its own length, so that one fill call
takes prompts of one length, and each length its own fill program.  Such a
state takes each token once: the fill consumes all but the prompt's last
token, which the window's first step feeds.  A cache of K/V entries alone
is filled as before: whole prompts padded to one length, ``fill_chunk`` at
a time.

Window: every step feeds one token per slot at its own position through
the deployment's decode step, takes the greedy token over the real
vocabulary and brings it to the host, as a streaming server must.  A slot
that has served its request's output length starts the next request over
the same cached prompt: its position rewinds to the prompt's last token,
and its state entries are restored from a snapshot of what the fill left,
kept on the device: between steps, one small jitted call per rewound slot
writes that slot's rows in place (``bench.rewind``, inside the window).
"""
from __future__ import annotations

import time

import numpy as np

from gen import Stream, quantile_pool, rng, tokens
from harness import arch, hlo_module_name, init_weights, log, solve_and_build

KV_KINDS = ("attn", "local")     # block kinds whose cache is K/V by position


def _identity(name, fn):
    return fn


def _fill_fn(mc, mesh, plan, steps, length, chunk):
    """jit: (params, tokens [chunk, length], slots [chunk], caches) ->
    caches with those slots written from a forward over the tokens: K/V
    entries at their first ``length`` positions, state entries whole."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.models import forward
    from repro.runtime.sharding import make_constrain, to_shardings

    c1 = make_constrain(mesh, plan, zone=1)
    c2 = make_constrain(mesh, plan, zone=2)
    p_sh = to_shardings(mesh, steps["param_specs"])
    k_sh = to_shardings(mesh, steps["cache_specs"])

    def write(kind, slots):
        rows = (slice(None), slots) + ((slice(length),) if kind in KV_KINDS
                                       else ())
        return lambda c, new: c.at[rows].set(new.astype(c.dtype))

    def fill(params, toks, slots, caches):
        _, kv = forward(params, mc, toks, constrain=c1, constrain2=c2,
                        transition_repeat=plan.transition_repeat,
                        collect_cache=True)
        if plan.transition_repeat is not None and c2 is not None:
            kv = jax.tree.map(lambda *z: jnp.concatenate(z, 0), *kv)
        return tuple(jax.tree.map(write(kind, slots), c, new)
                     for kind, c, new in zip(mc.expanded_pattern, caches, kv))

    rep = NamedSharding(mesh, P())
    return jax.jit(fill, in_shardings=(p_sh, rep, rep, k_sh),
                   out_shardings=k_sh, donate_argnums=(3,))


def _restore_fn(mesh, steps, states):
    """jit: (state entries, their snapshot, slot) -> the entries with that
    slot's rows taken from the snapshot, written in place."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.runtime.sharding import to_shardings

    sh = tuple(to_shardings(mesh, steps["cache_specs"][i]) for i in states)

    def restore(cur, snap, slot):
        def put(c, s):
            row = jax.lax.dynamic_index_in_dim(s, slot, 1)
            return jax.lax.dynamic_update_index_in_dim(c, row, slot, 1)
        return jax.tree.map(put, cur, snap)

    return jax.jit(restore, in_shardings=(sh, sh, NamedSharding(mesh, P())),
                   out_shardings=sh, donate_argnums=(0,))


def _fill_calls(prompts, chunk, pad):
    """[(length, slots)] of the fill calls, each of at most ``chunk``
    slots: whole prompts padded to ``pad``, or, where ``pad`` is None (the
    cache holds states), prompts grouped by the length the fill consumes."""
    if pad is not None:
        return [(pad, list(range(i, min(i + chunk, len(prompts)))))
                for i in range(0, len(prompts), chunk)]
    by_len: dict[int, list[int]] = {}
    for b, p in enumerate(prompts):
        by_len.setdefault(len(p) - 1, []).append(b)
    return [(n, slots[i:i + chunk]) for n, slots in sorted(by_len.items())
            for i in range(0, len(slots), chunk)]


def setup(cell, fault=None):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.runtime.serve import init_sharded_cache

    fault = fault or _identity
    tr, cfg = cell.traffic, cell.cfg
    mc = arch(cell).program_config(cell.config)
    B, S, V = cell.config["decode_slots"], tr["max_len"], cfg["vocab_size"]
    mesh, steps = solve_and_build(cell, mc, "decode", S, B, S, True)
    plan = steps["plan"]
    params = init_weights(cell, mc, mesh, steps)
    caches = init_sharded_cache(mc, mesh, steps["cache_specs"], B, S)
    states = [i for i, k in enumerate(mc.expanded_pattern) if k not in KV_KINDS]

    lens = rng(cell.seed, "prompt_len").permutation(
        quantile_pool(tr["prompt_len"], B))
    r = rng(cell.seed, "prompt_tokens")
    prompts = [tokens(r, int(n), V) for n in lens]
    pad = min(S, -(-int(tr["prompt_len"]["hi"]) // 128) * 128)
    chunk = tr["fill_chunk"]
    calls = _fill_calls(prompts, chunk, None if states else pad)

    tok_sh = NamedSharding(mesh, P(plan.dp, None))
    pos_sh = NamedSharding(mesh, P(plan.dp))
    t = time.perf_counter()
    fills = {n: _fill_fn(mc, mesh, plan, steps, n, chunk).lower(
        params, jax.ShapeDtypeStruct((chunk, n), jnp.int32),
        jax.ShapeDtypeStruct((chunk,), jnp.int32), caches).compile()
        for n in sorted({n for n, _ in calls})}
    decode = steps["decode"].lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=pos_sh),
        caches).compile()
    def greedy(logits):
        return jnp.argmax(logits[:, -1, :V], -1).astype(jnp.int32)

    logits_sh = NamedSharding(mesh, P(plan.dp, None, "model"))
    greedy = jax.jit(greedy).lower(jax.ShapeDtypeStruct(
        (B, 1, mc.padded_vocab), jnp.float32, sharding=logits_sh)).compile()
    restore = None
    if states:
        entries = tuple(caches[i] for i in states)
        restore = _restore_fn(mesh, steps, states).lower(
            entries, entries, jax.ShapeDtypeStruct((), jnp.int32)).compile()
    cell.spans["compile_s"] = time.perf_counter() - t

    t = time.perf_counter()
    for n, slots in calls:
        # a short call repeats its last prompt into the same slot
        slots = slots + slots[-1:] * (chunk - len(slots))
        toks = np.zeros((chunk, n), np.int32)
        for j, b in enumerate(slots):
            p = prompts[b][:n]
            toks[j, :len(p)] = p
        caches = fills[n](params, toks, np.array(slots, np.int32), caches)
    jax.block_until_ready(caches)
    snap = tuple(jax.tree.map(jnp.copy, caches[i]) for i in states)
    cell.spans["fill_s"] = time.perf_counter() - t

    state = {
        "devices": list(mesh.devices.flat), "params": params,
        "caches": caches, "decode": fault("decode", decode),
        "greedy": fault("greedy", greedy), "tok_sh": tok_sh,
        "pos_sh": pos_sh, "prompts": prompts, "module": hlo_module_name(decode),
        "out_lens": Stream(tr["output_len"], tr["pool"],
                           rng(cell.seed, "output_len")),
        "states": states, "snap": snap, "restore": restore,
    }
    # warm: the window's first step, twice (it rewrites the same position);
    # the states, which took its token twice, go back to the fill's
    t = time.perf_counter()
    for _ in range(2):
        tok, pos = _first_inputs(prompts)
        _step(state, tok, pos)
    if states:
        _rewind(state, range(B))
        jax.block_until_ready(state["caches"])
    cell.spans["warm_s"] = time.perf_counter() - t
    log(f"decode: {B} slots, max_len {S}, prompt lengths {sorted(lens)}; "
        f"{len(fills)} fill programs, state entries {states}; "
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


def _rewind(state, slots):
    """The state entries of ``slots`` back to the fill's snapshot."""
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.rewind"):
        caches = list(state["caches"])
        new = tuple(caches[i] for i in state["states"])
        for b in slots:
            new = state["restore"](new, state["snap"], np.int32(b))
        for i, c in zip(state["states"], new):
            caches[i] = c
        state["caches"] = tuple(caches)


def window(cell, state) -> dict:
    from jax.profiler import TraceAnnotation

    prompts, out_lens = state["prompts"], state["out_lens"]
    B = len(prompts)
    tok, pos = _first_inputs(prompts)
    want = np.array(out_lens.take(B))
    served = [[] for _ in range(B)]
    finished = []                    # (slot, served tokens, rewound)
    starts = np.zeros(B, np.int64)   # requests started per slot
    rewound = []                     # slots that start a request next step
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
                        rewound.append(b)
                    else:
                        tok[b], pos[b] = nxt[b], pos[b] + 1
            if t - t0 >= cell.seconds:
                break
            if rewound:
                if state["states"]:
                    _rewind(state, rewound)
                rewound = []
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
