"""Smoke run of the serving path on TPU: solve -> deploy -> prefill -> decode.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the sharded path only

One chip: granite-3-8b at its published widths in bf16, cut to 20 of its 40
layers so that weights, cache and temporaries fit in 16 GB.  The model is
solved on a one-chip v5e package, deployed, and built on the matching
(1, 1) mesh.  Eight seeded 512-token prompts go through one prefill-step
call; the cache is then filled through the decode step, token by token,
and 32 tokens are decoded greedily.

Four chips: the whole 40-layer model, solved on a four-chip package and run
on a (1, 4) mesh; then the 20-layer cut with the same seeded weights on the
four-chip mesh and on the first device alone, whose logits must agree.

Measurements go on earlier lines.  The last line of standard output is one
JSON object, ``{"ok": ..., "device": {"platform", "kind", "count"}}``; the
exit code is 0 only when ``ok`` is true.  Where JAX finds no TPU, or the
``repro`` package is not beside this script, it exits non-zero and prints
no result.  Compiled programs are kept in ``$JAX_COMPILATION_CACHE_DIR``,
or else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from dataclasses import replace
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "granite-3-8b"
ONE_CHIP_LAYERS = 20       # of 40: 4.39 B parameters, 8.8 GB in bf16
BATCH = 8
PROMPT_LEN = 512
MAX_LEN = 1024
GEN_TOKENS = 32
SEED = 0

# Logit agreement, as max|a - b| / max|b| over the batch and vocabulary at
# the last prompt position.  Both sides run in bf16 with float32
# accumulation, and the residual stream is rounded to bf16 (unit roundoff
# 2^-8 = 3.9e-3) after each of the 2 x 20 sublayers, so two orderings of the
# same sums drift apart by about sqrt(40) x 3.9e-3 = 2.5e-2 (a CPU run of a
# narrower 20-layer cut gave 1.7e-2; the same run in float32 gave 2.6e-6).
# 5e-2 is twice the estimate.  A wrong cache slot or position, or rounding
# as coarse as fp8 (2^-4), moves the logits by tens of percent and fails.
LOGIT_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(devices) -> list[int]:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def logit_error(a, b) -> float:
    import numpy as np

    return float(np.abs(a - b).max() / np.abs(b).max())


def serve(cfg, chips: int, label: str) -> dict:
    """solve -> deploy -> build_steps -> prefill, cache fill, greedy decode.

    Returns host copies of the logits at the last prompt position from the
    prefill step (``prefill``) and from the decode step through the cache
    (``decode``), and whether every logit looked at was finite."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import scope
    from repro.core.hw import tpu_v5e
    from repro.runtime.serve import (
        greedy_generate,
        init_sharded_cache,
        init_sharded_params,
    )

    t0 = time.perf_counter()
    sol = scope.solve(scope.problem(
        scope.WorkloadSpec.lm([cfg], PROMPT_LEN), tpu_v5e(chips, (1, chips))))
    dep = sol.deploy(global_batch=BATCH)
    mesh = dep.make_mesh()
    steps = dep.build_steps(mesh, batch=BATCH, max_len=MAX_LEN)[cfg.name]
    plan = steps["plan"]
    log(f"[{label}] solve+deploy {time.perf_counter() - t0:.3f} s; plan "
        f"p1={plan.p1} p2={plan.p2} "
        f"transition_repeat={plan.transition_repeat}; mesh {dict(mesh.shape)}")

    t0 = time.perf_counter()
    params = init_sharded_params(cfg, mesh, steps["param_specs"],
                                 jax.random.PRNGKey(SEED))
    caches = init_sharded_cache(cfg, mesh, steps["cache_specs"], BATCH, MAX_LEN)
    jax.block_until_ready((params, caches))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[{label}] init {time.perf_counter() - t0:.3f} s "
        f"({n_params} parameters, built in their shards)")

    tok_sh = NamedSharding(mesh, P(plan.dp, None))
    pos_sh = NamedSharding(mesh, P(plan.dp))
    prompts = np.random.default_rng(SEED).integers(
        0, cfg.vocab, (BATCH, PROMPT_LEN), dtype=np.int32)
    prompts_d = jax.device_put(prompts, tok_sh)

    t0 = time.perf_counter()
    prefill = steps["prefill"].lower(params, prompts_d).compile()
    t1 = time.perf_counter()
    decode = steps["decode"].lower(
        params, jax.device_put(prompts[:, :1], tok_sh),
        jax.device_put(np.zeros(BATCH, np.int32), pos_sh), caches).compile()
    t2 = time.perf_counter()
    log(f"[{label}] compile_s prefill={t1 - t0:.3f} decode={t2 - t1:.3f}")

    def decode_fn(params, tok, pos, caches):
        return decode(params, jax.device_put(tok, tok_sh),
                      jax.device_put(pos, pos_sh), caches)

    logits = prefill(params, prompts_d)
    finite = bool(jnp.isfinite(logits).all())
    last_prefill = np.asarray(logits[:, -1])
    del logits
    t0 = time.perf_counter()
    jax.block_until_ready(prefill(params, prompts_d))
    prefill_s = time.perf_counter() - t0

    fill_finite = jnp.array(True)
    t0 = time.perf_counter()
    for t in range(PROMPT_LEN):
        logits, caches = decode_fn(params, prompts[:, t:t + 1],
                                   np.full(BATCH, t, np.int32), caches)
        fill_finite &= jnp.isfinite(logits).all()
    first = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(first)
    fill_s = time.perf_counter() - t0
    last_decode = np.asarray(logits[:, -1])

    # one untimed step compiles the loop's small ops (argmax, position add);
    # the timed run then starts over at the same position, rewriting it with
    # the same token
    _, caches = greedy_generate(cfg, params, decode_fn, caches, first,
                                PROMPT_LEN, 1)
    t0 = time.perf_counter()
    out, caches = greedy_generate(cfg, params, decode_fn, caches, first,
                                  PROMPT_LEN, GEN_TOKENS)
    jax.block_until_ready(out)
    decode_s = time.perf_counter() - t0
    logits, caches = decode_fn(params, out[:, -1:],
                               np.full(BATCH, PROMPT_LEN + GEN_TOKENS, np.int32),
                               caches)
    finite = finite and bool(fill_finite) and bool(jnp.isfinite(logits).all())
    log(f"[{label}] prefill {prefill_s:.6f} s for {BATCH}x{PROMPT_LEN} tokens; "
        f"cache fill {fill_s / PROMPT_LEN:.6f} s/step; "
        f"decode {decode_s / GEN_TOKENS:.6f} s/token "
        f"({BATCH}x{GEN_TOKENS} tokens in {decode_s:.6f} s)")
    log(f"[{label}] peak_bytes_in_use per device "
        f"{peak_bytes(mesh.devices.flat)}")
    log(f"[{label}] all logits finite: {finite}")
    return {"prefill": last_prefill, "decode": last_decode, "finite": finite}


def check(label: str, a, b) -> bool:
    err = logit_error(a, b)
    ok = err <= LOGIT_TOL
    log(f"[{label}] max|a-b|/max|b| = {err:.6e} (tolerance {LOGIT_TOL}): "
        f"{'pass' if ok else 'FAIL'}")
    return ok


def one_chip(cfg) -> bool:
    cut = replace(cfg, n_layers=ONE_CHIP_LAYERS)
    log(f"reduced: n_layers {cfg.n_layers}→{cut.n_layers}")
    r = serve(cut, 1, "1 chip")
    ok = check("1 chip: decode through cache vs prefill", r["decode"],
               r["prefill"])
    return ok and r["finite"]


def four_chips(cfg) -> bool:
    label = f"4 chips, {cfg.n_layers} layers"
    full = serve(cfg, 4, label)
    ok = full["finite"] and check(f"{label}: decode through cache vs prefill",
                                  full["decode"], full["prefill"])
    cut = replace(cfg, n_layers=ONE_CHIP_LAYERS)
    log(f"comparison: n_layers {cfg.n_layers}→{cut.n_layers}, same seed, "
        f"4 chips vs the first device alone")
    four = serve(cut, 4, f"4 chips, {cut.n_layers} layers")
    one = serve(cut, 1, f"1 chip, {cut.n_layers} layers")
    ok &= four["finite"] and one["finite"]
    ok &= check(f"{cut.n_layers} layers: 4-chip vs 1-chip prefill logits",
                four["prefill"], one["prefill"])
    ok &= check(f"{cut.n_layers} layers: 4-chip vs 1-chip decode logits",
                four["decode"], one["decode"])
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its comparison")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke.py: no repro package at {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    from repro.launch.compile_cache import use_compile_cache

    cache_dir = use_compile_cache()
    import jax

    from repro.configs import get_config

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke.py: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but {len(devices)} "
              f"devices are visible", file=sys.stderr)
        return 1
    log(f"device {devices[0].device_kind} x{len(devices)}; "
        f"compile cache {cache_dir}")
    cfg = get_config(ARCH)
    ok = one_chip(cfg) if args.chips == 1 else four_chips(cfg)
    print(json.dumps({"ok": bool(ok), "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
