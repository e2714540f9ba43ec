import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
# Four-device CPU worker: a tiny Granite decoding through solve -> deploy ->
# build_steps on a four-chip v5e package, against the benchmark's plain
# reference.  ``python check_sharded_decode.py <split>``, one case a split
# of the K/V cache over the 4-way model axis:
#   heads      32 KV heads, 8 a device, 4 slots
#   slots      4 KV heads, 1 a device, 4 slots: 1 slot a device
#   positions  2 KV heads, 2 slots: 16 of max_len 64 positions a device
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.join(HERE, "..", "..")
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

from gen import seed_key                                 # noqa: E402
from harness import load_module                          # noqa: E402
from repro import scope                                  # noqa: E402
from repro.core.hw import tpu_v5e                        # noqa: E402
from repro.models import forward                         # noqa: E402
from repro.runtime.serve import (init_sharded_cache,     # noqa: E402
                                 init_sharded_params)
from repro.runtime.sharding import (make_constrain,      # noqa: E402
                                    to_shardings)

GRANITE = load_module(Path(ROOT, "bench", "archs", "granite.py"))
T, STEPS, SEED = 64, 60, 2**33 + 5
CASES = {"heads": (32, 4), "slots": (4, 4), "positions": (2, 2)}  # KV, slots
# A bfloat16 program against a float32 reference: every matmul of the
# program rounds its output to bfloat16 (relative 2**-8), and sound runs of
# this model differ from the reference by at most 0.063 in a logit in each
# case.  New rows written one position late widen the gap to 4.8 to 5.6,
# and slots sent to the wrong device to 5.9: the tolerance lies 4x above
# the one and 19x below the other.
TOL = 0.25
_MOVE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\([^)]*\)|\S+) "
                   r"(all-gather|all-to-all|all-reduce)(?:-start)?\(")
_DIMS = re.compile(r"\w+\[([\d,]*)\]")


def config(kv_heads: int) -> dict:
    c = {"model_type": "granite", "hidden_size": 256,
         "intermediate_size": 256, "hidden_act": "silu",
         "num_hidden_layers": 2, "num_attention_heads": 32,
         "num_key_value_heads": kv_heads, "vocab_size": 500,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": False, "attention_multiplier": 8 ** -0.5,
         "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "logits_scaling": 1.0}
    return {"name": f"tiny-kv{kv_heads}", "program_arch": "granite-3-8b",
            "dtype": "bfloat16", "config": c}


def schedule(rng, vocab, B):
    """(tokens [STEPS, B], positions [STEPS, B], the reference's sequences,
    and for each step and slot the (sequence, position) its logits are):
    every slot but the last writes positions 0..59, crossing every shard
    boundary; the last writes 0..39, then rewinds to 10, back across two
    boundaries, and writes a new continuation over 10..29."""
    seqs = [rng.integers(0, vocab, STEPS) for _ in range(B - 1)]
    first = rng.integers(0, vocab, 40)
    again = np.concatenate([first[:10], rng.integers(0, vocab, 20)])
    seqs += [first, again]
    tok = np.zeros((STEPS, B), np.int32)
    pos = np.zeros((STEPS, B), np.int32)
    rows = []
    for t in range(STEPS):
        row = []
        for b in range(B - 1):
            tok[t, b], pos[t, b] = seqs[b][t], t
            row.append((b, t))
        s, p = (B - 1, t) if t < 40 else (B, t - 30)
        tok[t, B - 1], pos[t, B - 1] = seqs[s][p], p
        row.append((s, p))
        rows.append(row)
    return tok, pos, seqs, rows


def moves(exe, limit, kinds, weights=frozenset()):
    """The collectives of ``kinds`` in a compiled program that move
    ``limit`` elements or more (the CPU moves bfloat16 as float32), or whose
    result has a weight's dimensions."""
    out = []
    for m in map(_MOVE.match, exe.as_text().splitlines()):
        if not m or m.group(2) not in kinds:
            continue
        dims = [tuple(int(d) for d in x.split(",") if d)
                for x in _DIMS.findall(m.group(1))]
        if sum(map(np.prod, dims)) >= limit or set(dims) & weights:
            out.append(m.group(0))
    return out


def main(split: str) -> None:
    assert len(jax.devices()) == 4, jax.devices()
    kv_heads, B = CASES[split]
    doc = config(kv_heads)
    mc = GRANITE.program_config(doc)
    sol = scope.solve(scope.problem(
        scope.WorkloadSpec.lm([mc], T, phase="decode"), tpu_v5e(4, (1, 4))))
    dep = sol.deploy(global_batch=B)
    mesh = dep.make_mesh()
    steps = dep.build_steps(mesh, batch=B, max_len=T)[mc.name]
    plan = steps["plan"]
    assert dict(mesh.shape) == {"data": 1, "model": 4}, mesh.shape
    assert (plan.p1, plan.p2, plan.transition_repeat) == ("ISP", "ISP", None)

    params = init_sharded_params(mc, mesh, steps["param_specs"],
                                 jnp.asarray(seed_key(SEED)))
    caches = init_sharded_cache(mc, mesh, steps["cache_specs"], B, T)
    k = caches[0]["k"]
    L, hd = mc.n_layers, mc.head_dim
    want, shard = {
        "heads": (P(None, plan.dp, None, "model", None),
                  (L, B, T, kv_heads // 4, hd)),
        "slots": (P(None, (*plan.dp, "model"), None, None, None),
                  (L, B // 4, T, kv_heads, hd)),
        "positions": (P(None, plan.dp, "model", None, None),
                      (L, B, T // 4, kv_heads, hd)),
    }[split]
    assert k.sharding.spec == want, k.sharding.spec
    assert {s.data.shape for s in k.addressable_shards} == {shard}

    # no op of the decode step moves as much as one layer's cache, nor
    # gathers a weight; the fill of two prompts' K/V into the cache, as the
    # benchmark's set-up writes it, gathers or exchanges nothing larger than
    # those prompts' own K/V (over positions: than the two slots' rows at
    # every position, which a write of a prompt shorter than max_len gathers
    # where positions are split)
    layer = int(np.prod(k.shape[1:]))
    weights = {d for a in jax.tree.leaves(params)
               for d in (a.shape, a.shape[1:]) if len(d) >= 2}
    tok_sh = jax.sharding.NamedSharding(mesh, P(plan.dp, None))
    pos_sh = jax.sharding.NamedSharding(mesh, P(plan.dp))
    decode = steps["decode"].lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=pos_sh),
        caches).compile()
    big = moves(decode, layer, ("all-gather", "all-to-all", "all-reduce"),
                weights)
    assert not big, ("decode", big)
    n = 48
    fill = jax.jit(
        lambda params, toks, slots, c: jax.tree.map(
            lambda x, new: x.at[:, slots, :n].set(new.astype(x.dtype)), c,
            forward(params, mc, toks, constrain=make_constrain(mesh, plan, 1),
                    collect_cache=True)[1][0]),
        in_shardings=(to_shardings(mesh, steps["param_specs"]), None, None,
                      jax.tree.map(lambda x: x.sharding, caches[0])),
        out_shardings=jax.tree.map(lambda x: x.sharding, caches[0]),
        donate_argnums=(3,))
    filled = fill.lower(params, jax.ShapeDtypeStruct((2, n), jnp.int32),
                        jax.ShapeDtypeStruct((2,), jnp.int32),
                        caches[0]).compile()
    rows = T if split == "positions" else n
    big = moves(filled, L * 2 * rows * kv_heads * hd + 1,
                ("all-gather", "all-to-all"))
    assert not big, ("fill", big)

    tok, pos, seqs, rows = schedule(np.random.default_rng(SEED), mc.vocab, B)
    got = []
    for t in range(STEPS):
        logits, caches = decode(params, jax.device_put(tok[t][:, None], tok_sh),
                                jax.device_put(pos[t], pos_sh), caches)
        got.append(np.asarray(logits[:, 0, :mc.vocab], np.float32))
    flat = [r for row in rows for r in row]
    ref = np.asarray(GRANITE.forward_rows(doc["config"], seed_key(SEED),
                                          seqs, flat))
    err = np.abs(np.concatenate(got) - ref).max(-1).reshape(STEPS, B)
    print(f"{split}: widest logit error {err.max():.5f} (tolerance {TOL}); "
          f"before the rewind {err[:40, -1].max():.5f}, after it "
          f"{err[40:, -1].max():.5f}")
    assert err.max() < TOL, err.max(1)
    print("OK")


if __name__ == "__main__":
    main(sys.argv[1])
