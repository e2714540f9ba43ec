"""Serving launcher: batched prefill + greedy decode.

``python -m repro.launch.serve --arch granite-3-8b --smoke --tokens 32``
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.runtime.planner import plan_for_cell
from repro.runtime.serve import (
    build_decode_step,
    greedy_generate,
    init_sharded_cache,
    init_sharded_params,
)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS), required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--mesh", default="1x1")
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    dims = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dims, ("data", "model"))
    max_len = args.prompt_len + args.tokens
    plan = plan_for_cell(cfg, max_len, args.batch, ("data", "model"),
                         model_axis=dims[1], kind="decode")

    # prefill the prompt token-by-token through the decode path (exercises
    # exactly the serve_step the dry-run lowers)
    dstep, specs = build_decode_step(cfg, mesh, plan, batch=args.batch,
                                     max_len=max_len)
    params = init_sharded_params(cfg, mesh, specs["params"],
                                 jax.random.PRNGKey(0))
    caches = init_sharded_cache(cfg, mesh, specs["caches"], args.batch, max_len,
                                jnp.float32 if args.smoke else jnp.bfloat16)
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len), 0, cfg.vocab)
    for t in range(args.prompt_len):
        pos = jnp.full((args.batch,), t, jnp.int32)
        logits, caches = dstep(params, prompt[:, t:t + 1], pos, caches)
    first = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(first)
    t0 = time.perf_counter()
    out, _ = greedy_generate(cfg, params, dstep, caches,
                             prompt_last_token=first,
                             start_pos=args.prompt_len, steps=args.tokens)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    print(f"generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s) "
          f"on {jax.devices()[0].device_kind}")
    print("sample:", out[0, :16].tolist())


if __name__ == "__main__":
    use_compile_cache()
    main()
