"""Serving runtime: batched prefill + KV-cache decode steps under a plan."""
from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import decode_step, forward, init_kv_cache, init_params
from ..models.config import ModelConfig
from .sharding import (
    ShardPlan,
    cache_pspecs,
    kv_split,
    make_constrain,
    over_slots,
    param_pspecs,
    sanitize_pspecs,
    to_shardings,
)


def _sanitized_param_specs(cfg, plan, mesh):
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return sanitize_pspecs(param_pspecs(cfg, plan, mesh), shapes, mesh)


def init_sharded_params(cfg: ModelConfig, mesh: Mesh, param_specs,
                        key: jax.Array) -> dict:
    """Seeded weights created inside one jitted program, each directly in
    its shard of ``param_specs`` (from ``build_*_step``): no weight is
    ever whole on one device, and no per-layer copy outlives the stack."""
    return jax.jit(init_params, static_argnums=0,
                   out_shardings=to_shardings(mesh, param_specs))(cfg, key)


def init_sharded_cache(cfg: ModelConfig, mesh: Mesh, cache_specs, batch: int,
                       max_len: int, dtype=jnp.bfloat16) -> tuple:
    """Zeroed KV/state cache created in its shards of ``cache_specs``."""
    return jax.jit(init_kv_cache, static_argnums=(0, 1, 2, 3),
                   out_shardings=to_shardings(mesh, cache_specs))(
        cfg, batch, max_len, dtype)


def build_prefill_step(cfg: ModelConfig, mesh: Mesh, plan: ShardPlan):
    """Signature depends on the frontend:
    none        -> prefill(params, tokens)
    audio_stub  -> prefill(params, frontend_embeds)
    vision_stub -> prefill(params, tokens, frontend_embeds)
    """
    c1 = make_constrain(mesh, plan, zone=1)
    c2 = make_constrain(mesh, plan, zone=2)

    def core(params, tokens, frontend_embeds):
        logits, _ = forward(
            params, cfg, tokens, frontend_embeds,
            constrain=c1, constrain2=c2,
            transition_repeat=plan.transition_repeat,
            collect_cache=False,
        )
        return logits

    p_specs = _sanitized_param_specs(cfg, plan, mesh)
    dp = plan.dp
    p_sh = to_shardings(mesh, p_specs)
    tok_sh = NamedSharding(mesh, P(dp, None))
    emb_sh = NamedSharding(mesh, P(dp, None, None))
    out_sh = NamedSharding(mesh, P(dp, None, "model"))

    if cfg.frontend == "audio_stub":
        fn = lambda params, fe: core(params, None, fe)
        in_sh = (p_sh, emb_sh)
    elif cfg.frontend == "vision_stub":
        fn = core
        in_sh = (p_sh, tok_sh, emb_sh)
    else:
        fn = lambda params, tokens: core(params, tokens, None)
        in_sh = (p_sh, tok_sh)
    return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh), p_specs


def build_decode_step(cfg: ModelConfig, mesh: Mesh, plan: ShardPlan,
                      batch: int | None = None, max_len: int | None = None):
    """serve_step: one new token against a resident KV cache (donated).

    ``batch``/``max_len`` (when known) let the cache shardings be checked
    for divisibility against the actual cache shapes; ``batch`` also lets
    K/V split over slots (``kv_split``), where attention then runs on each
    device's own slots (``over_slots``).  The split of each cache entry is
    logged to stderr."""
    c = make_constrain(mesh, plan, zone=2)   # decode is single-token: ISP zone
    dp = plan.dp
    dp_size = math.prod(mesh.shape[a] for a in dp)
    local = batch // dp_size if batch is not None and batch % dp_size == 0 else None
    split = kv_split(cfg.n_kv_heads, mesh.shape["model"], local)
    attend = over_slots(mesh, plan) if split == "slots" else None

    def step(params, token, position, caches):
        return decode_step(params, cfg, token, position, caches, constrain=c,
                           attend=attend)

    p_specs = _sanitized_param_specs(cfg, plan, mesh)
    k_specs = cache_pspecs(cfg, plan, split)
    if batch is not None and max_len is not None:
        cache_shapes = jax.eval_shape(
            lambda: init_kv_cache(cfg, batch, max_len)
        )
        k_specs = sanitize_pspecs(k_specs, cache_shapes, mesh)
    entries = ", ".join(
        f"{i} {kind}: " + (split or "none" if kind in ("attn", "local")
                           else "channels")
        for i, kind in enumerate(cfg.expanded_pattern))
    print(f"decode cache split over model={mesh.shape['model']} "
          f"({cfg.n_kv_heads} KV heads, {batch} slots): {entries}",
          file=sys.stderr, flush=True)
    in_sh = (
        to_shardings(mesh, p_specs),
        NamedSharding(mesh, P(dp, None)),          # token [B,1]
        NamedSharding(mesh, P(dp)),                # position [B]
        to_shardings(mesh, k_specs),
    )
    out_sh = (
        NamedSharding(mesh, P(dp, None, "model")),  # logits
        to_shardings(mesh, k_specs),
    )
    jitted = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=(3,))
    return jitted, {"params": p_specs, "caches": k_specs}


def build_multimodel_steps(
    cfgs,
    mesh: Mesh,
    plans: dict[str, ShardPlan],
    batch: int | None = None,
    max_len: int | None = None,
    with_decode: bool = True,
):
    """Per-model serving steps from a multimodel co-schedule.

    ``plans`` comes from :func:`repro.runtime.planner.plan_for_multimodel`:
    each plan's WSP->ISP transition and ``meta["quota_chips"]`` /
    ``meta["time_share"]`` were chosen jointly by the co-scheduler.  Every
    model gets its own jitted prefill (and decode) step on the *shared*
    mesh, which executes a time-multiplexed co-schedule directly (dispatch
    each model for its ``time_share``).  The request scheduler that drives
    these steps under load -- queueing, batching, quota sub-meshes, slice
    windows -- is :mod:`repro.serving`; its ``measure=True`` path times the
    steps built here to calibrate the simulator's service model
    (:func:`repro.serving.measure_service_models`).

    Returns ``{cfg.name: {"prefill": fn, "param_specs": specs,
    "decode": fn, "cache_specs": specs, "plan": plan}}``.
    """
    fleet = {}
    for cfg in cfgs:
        plan = plans[cfg.name]
        prefill, p_specs = build_prefill_step(cfg, mesh, plan)
        entry = {"prefill": prefill, "param_specs": p_specs, "plan": plan}
        if with_decode:
            decode, specs = build_decode_step(cfg, mesh, plan,
                                              batch=batch, max_len=max_len)
            entry["decode"] = decode
            entry["cache_specs"] = specs["caches"]
        fleet[cfg.name] = entry
    return fleet


def greedy_generate(cfg, params, decode_fn, caches, prompt_last_token, start_pos, steps):
    """Simple batched greedy loop driving the jitted decode step."""
    B = prompt_last_token.shape[0]
    tok = prompt_last_token
    pos = jnp.full((B,), start_pos, jnp.int32)
    out = []
    for _ in range(steps):
        logits, caches = decode_fn(params, tok, pos, caches)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out.append(tok)
        pos = pos + 1
    return jnp.concatenate(out, axis=1), caches
