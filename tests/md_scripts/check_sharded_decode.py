import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
# Four-device CPU worker: a tiny Granite decoding through solve -> deploy ->
# build_steps on a four-chip v5e package, against the benchmark's plain
# reference.  ``python check_sharded_decode.py <kv_heads>``: 4 KV heads
# divide the 4-way model axis (the cache splits over heads), 2 do not (it
# splits over positions, 16 of max_len 64 a device).
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
from repro.launch.hlo_analysis import shape_bytes        # noqa: E402
from repro.runtime.serve import (init_sharded_cache,     # noqa: E402
                                 init_sharded_params)

GRANITE = load_module(Path(ROOT, "bench", "archs", "granite.py"))
B, T, STEPS, SEED = 4, 64, 60, 2**33 + 5
# A bfloat16 program against a float32 reference: every matmul of the
# program rounds its output to bfloat16 (relative 2**-8), and sound runs of
# this model differ from the reference by at most 0.06 in a logit.  A row
# written one position off at a shard boundary, or one stale row let
# through the mask, moves them by 0.6 to 2.4: the tolerance lies 4x above
# the one and 2.5x below the other.
TOL = 0.25
_MOVE = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (\S+) "
                   r"(all-gather|all-gather-start|all-to-all)\(")


def config(kv_heads: int) -> dict:
    c = {"model_type": "granite", "hidden_size": 128,
         "intermediate_size": 256, "hidden_act": "silu",
         "num_hidden_layers": 2, "num_attention_heads": 8,
         "num_key_value_heads": kv_heads, "vocab_size": 500,
         "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
         "tie_word_embeddings": False, "attention_multiplier": 16 ** -0.5,
         "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
         "logits_scaling": 1.0}
    return {"name": f"tiny-kv{kv_heads}", "program_arch": "granite-3-8b",
            "dtype": "bfloat16", "config": c}


def schedule(rng, vocab):
    """(tokens [STEPS, B], positions [STEPS, B], the reference's sequences,
    and for each step and slot the (sequence, position) its logits are):
    slots 0-2 write positions 0..59, crossing every shard boundary; slot 3
    writes 0..39, then rewinds to 10, back across two boundaries, and
    writes a new continuation over 10..29."""
    seqs = [rng.integers(0, vocab, STEPS) for _ in range(3)]
    first = rng.integers(0, vocab, 40)
    again = np.concatenate([first[:10], rng.integers(0, vocab, 20)])
    seqs += [first, again]
    tok = np.zeros((STEPS, B), np.int32)
    pos = np.zeros((STEPS, B), np.int32)
    rows = []
    for t in range(STEPS):
        row = []
        for b in range(3):
            tok[t, b], pos[t, b] = seqs[b][t], t
            row.append((b, t))
        s, p = (3, t) if t < 40 else (4, t - 30)
        tok[t, 3], pos[t, 3] = seqs[s][p], p
        row.append((s, p))
        rows.append(row)
    return tok, pos, seqs, rows


def main(kv_heads: int) -> None:
    assert len(jax.devices()) == 4, jax.devices()
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
    if kv_heads % 4 == 0:
        want = P(None, plan.dp, None, "model", None)
        shard = (L, B, T, kv_heads // 4, hd)
    else:
        want = P(None, plan.dp, "model", None, None)
        shard = (L, B, T // 4, kv_heads, hd)
    assert k.sharding.spec == want, k.sharding.spec
    assert {s.data.shape for s in k.addressable_shards} == {shard}

    # no op of the decode step moves as much as one layer's cache, nor,
    # where the cache splits over heads, does a prompt's write into it (over
    # positions the write of a prompt shorter than max_len gathers the cache)
    layer = int(np.prod(k.shape[1:])) * 2
    tok_sh = jax.sharding.NamedSharding(mesh, P(plan.dp, None))
    pos_sh = jax.sharding.NamedSharding(mesh, P(plan.dp))
    decode = steps["decode"].lower(
        params, jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=tok_sh),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=pos_sh),
        caches).compile()
    write = jax.jit(
        lambda c, new, slots: jax.tree.map(
            lambda x, n: x.at[:, slots, :n.shape[2]].set(n), c, new),
        out_shardings=jax.tree.map(lambda x: x.sharding, caches[0]),
        donate_argnums=(0,))
    new = {n: jax.ShapeDtypeStruct((L, 2, 48, kv_heads, hd), jnp.bfloat16)
           for n in ("k", "v")}
    written = write.lower(caches[0], new, jnp.zeros(2, jnp.int32)).compile()
    checked = [("decode", decode)]
    if kv_heads % 4 == 0:
        checked.append(("prompt write", written))
    for name, exe in checked:
        big = [m.group(0) for m in map(_MOVE.match, exe.as_text().splitlines())
               if m and shape_bytes(m.group(1)) >= layer]
        assert not big, (name, big)

    tok, pos, seqs, rows = schedule(np.random.default_rng(SEED), mc.vocab)
    got = []
    for t in range(STEPS):
        logits, caches = decode(params, jax.device_put(tok[t][:, None], tok_sh),
                                jax.device_put(pos[t], pos_sh), caches)
        got.append(np.asarray(logits[:, 0, :mc.vocab], np.float32))
    flat = [r for row in rows for r in row]
    ref = np.asarray(GRANITE.forward_rows(doc["config"], seed_key(SEED),
                                          seqs, flat))
    err = np.abs(np.concatenate(got) - ref).max(-1).reshape(STEPS, B)
    print(f"kv_heads {kv_heads}: widest logit error {err.max():.5f} "
          f"(tolerance {TOL}); before the rewind {err[:40, 3].max():.5f}, "
          f"after it {err[40:, 3].max():.5f}")
    assert err.max() < TOL, err.max(1)
    print("OK")


if __name__ == "__main__":
    main(int(sys.argv[1]))
