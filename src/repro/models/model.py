"""Composable decoder model: embed -> scanned blocks -> norm -> logits.

Layers are scanned over pattern *repeats* with stacked params (keeps HLO
small and compile times sane at 48+ layers).  To execute a Scope schedule,
``forward``/``decode_step`` accept:

* ``constrain(x, tag)``   -- sharding-constraint callback (identity default);
  tags: "embed", "resid", "logits", f"blk{i}:attn" etc.
* ``transition_repeat``   -- the paper's WSP->ISP transition point mapped to
  the repeat axis: repeats [0, t) run under ``constrain``, repeats [t, R)
  under ``constrain2``.  Implemented as two scan segments over sliced
  stacked params -- per-layer heterogeneous sharding with scanned layers is
  exactly what the single-transition-point structure makes possible.

The parts of a step run under fixed ``jax.named_scope`` names, which reach
the compiled program only as ``op_name`` metadata: ``embed``, ``layers``
(the scan over the block stack), per block the sequence mixer ``attn`` /
``mamba`` / ``rwkv`` (``attn/kv_write`` around the decode cache write) and
the channel mixer ``ffn`` / ``moe``, then ``head``.  The names are part of
the interface: device time is split by them.

In ``decode_step`` the stacked caches ride in the scan's carry: attention
writes its new row into the stack under ``attn/kv_write`` and reads its
layer straight from the stack, so ``layers`` slices only the stacked
weights (and the small per-layer mamba / rwkv states) and stacks nothing.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .attention import attention_decode, attention_prefill, init_attn
from .config import ModelConfig
from .layers import dense, embed, ffn, init_ffn, rmsnorm, softcap
from .moe import init_moe, moe_ffn
from .rwkv import init_rwkv, rwkv_channel_mix, rwkv_time_mix
from .ssm import init_mamba, mamba_decode, mamba_prefill


def _identity_constrain(x, tag):
    return x


# --------------------------------------------------------------------- init

def _init_block(key, cfg: ModelConfig, kind: str, layer_idx: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    p = {"ln1": jnp.zeros((cfg.d_model,), jnp.float32),
         "ln2": jnp.zeros((cfg.d_model,), jnp.float32)}
    if kind in ("attn", "local"):
        p["attn"] = init_attn(ks[0], cfg, dtype)
    elif kind == "mamba":
        p["mamba"] = init_mamba(ks[0], cfg, dtype)
    elif kind == "rwkv":
        p["rwkv"] = init_rwkv(ks[0], cfg, dtype)
    else:
        raise ValueError(kind)
    if kind == "rwkv":
        pass                                  # channel mix lives in p["rwkv"]
    elif cfg.is_moe_block(layer_idx):
        p["moe"] = init_moe(ks[1], cfg, dtype)
    else:
        p["ffn"] = init_ffn(ks[1], cfg.d_model, cfg.d_ff, cfg.ffn_gated, dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> dict:
    """Seeded random weights.  Layer ``r * P + pi`` draws from
    ``keys[r * P + pi]``; each pattern position is vmapped over its repeats,
    so the stack is built in one piece (pattern positions share MoE
    placement across repeats, see ``expanded_pattern``).  Run it under
    ``jax.jit(..., out_shardings=...)`` (``runtime.serve.init_sharded_params``)
    to create the weights directly in their shards."""
    dtype = jnp.dtype(cfg.param_dtype)
    R = cfg.pattern_repeats
    P = len(cfg.expanded_pattern)
    keys = jax.random.split(key, R * P + 2)
    blocks = tuple(
        jax.vmap(partial(_init_block, cfg=cfg, kind=kind, layer_idx=pi,
                         dtype=dtype))(keys[pi:R * P:P])
        for pi, kind in enumerate(cfg.expanded_pattern)
    )
    params = {
        "embed": (jax.random.normal(keys[-2], (cfg.padded_vocab, cfg.d_model))
                  * cfg.d_model ** -0.5).astype(dtype),
        "blocks": blocks,
        "final_ln": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = (
            jax.random.normal(keys[-1], (cfg.d_model, cfg.padded_vocab)) * cfg.d_model ** -0.5
        ).astype(dtype)
    return params


def param_count(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ------------------------------------------------------------------ forward

def _mixer_scope(kind: str) -> str:
    """Named scope (and constrain tag) of a block kind's sequence mixer."""
    if kind in ("attn", "local"):
        return "attn"
    if kind in ("mamba", "rwkv"):
        return kind
    raise ValueError(kind)


def _block_prefill(cfg, kind, layer_idx_in_pattern, bp, x, positions, constrain):
    tag = f"blk{layer_idx_in_pattern}"
    mixer = _mixer_scope(kind)
    with jax.named_scope(mixer):
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        if mixer == "attn":
            window = cfg.window if kind == "local" else 0
            a, kv = attention_prefill(bp["attn"], h, cfg, positions, window)
            cache = {"k": kv[0], "v": kv[1]}
        elif mixer == "mamba":
            a, cache = mamba_prefill(bp["mamba"], h, cfg)
        else:
            a, cache = rwkv_time_mix(bp["rwkv"], h, cfg)
        x = constrain(x + a, f"{tag}:{mixer}")
    with jax.named_scope("moe" if "moe" in bp else "ffn"):
        h2 = rmsnorm(x, bp["ln2"], cfg.norm_eps)
        if mixer == "rwkv":
            f, st2 = rwkv_channel_mix(bp["rwkv"], h2)
            cache = {**cache, **st2}
        else:
            f = (moe_ffn(bp["moe"], h2, cfg, constrain) if "moe" in bp
                 else ffn(bp["ffn"], h2, cfg.ffn_gated))
        x = constrain(x + f, f"{tag}:ffn")
    return x, cache


def _scan_blocks(cfg, blocks, x, positions, constrain, collect_cache=False):
    """One lax.scan over repeats; pattern positions applied inside the body."""

    def body(carry, bps):
        h = carry
        caches = []
        for pi, kind in enumerate(cfg.expanded_pattern):
            h, c = _block_prefill(cfg, kind, pi, bps[pi], h, positions, constrain)
            caches.append(c)
        return h, tuple(caches) if collect_cache else None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    n = jax.tree.leaves(blocks)[0].shape[0]
    x, caches = jax.lax.scan(
        body_fn, x, blocks, unroll=max(1, min(cfg.scan_unroll, n))
    )
    return x, caches


def forward(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array | None,
    frontend_embeds: jax.Array | None = None,
    constrain=_identity_constrain,
    constrain2=None,
    transition_repeat: int | None = None,
    collect_cache: bool = False,
    positions: jax.Array | None = None,
):
    """Returns (logits [B,S,V], caches or None)."""
    with jax.named_scope("embed"):
        if cfg.frontend == "audio_stub":
            x = frontend_embeds.astype(jnp.dtype(cfg.param_dtype))
            B, S = x.shape[:2]
        elif cfg.frontend == "vision_stub":
            t_emb = embed(tokens, params["embed"])
            x = jnp.concatenate(
                [frontend_embeds.astype(t_emb.dtype), t_emb], axis=1
            )
            B, S = x.shape[:2]
        else:
            x = embed(tokens, params["embed"])
            B, S = tokens.shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x = constrain(x, "embed")

    with jax.named_scope("layers"):
        if transition_repeat is None or constrain2 is None:
            blocks = params["blocks"]
            x, caches = _scan_blocks(cfg, blocks, x, positions, constrain,
                                     collect_cache)
        else:
            t = transition_repeat
            zone1 = jax.tree.map(lambda a: a[:t], params["blocks"])
            zone2 = jax.tree.map(lambda a: a[t:], params["blocks"])
            caches = []
            if t > 0:
                x, c1 = _scan_blocks(cfg, zone1, x, positions, constrain,
                                     collect_cache)
                caches.append(c1)
            if t < cfg.pattern_repeats:
                x = constrain2(x, "transition")
                x, c2 = _scan_blocks(cfg, zone2, x, positions, constrain2,
                                     collect_cache)
                caches.append(c2)
            caches = tuple(caches) if collect_cache else None

    return _head(params, cfg, x, constrain), caches


def _head(params, cfg, x, constrain):
    """Final norm, lm head, float32 logits, softcap."""
    with jax.named_scope("head"):
        x = rmsnorm(x, params["final_ln"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = dense(x, head)
        logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
        return constrain(logits, "logits")


# ----------------------------------------------------------------- KV cache

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    R = cfg.pattern_repeats
    caches = []
    for kind in cfg.expanded_pattern:
        if kind in ("attn", "local"):
            kv, hd = cfg.n_kv_heads, cfg.head_dim
            caches.append({
                "k": jnp.zeros((R, batch, max_len, kv, hd), dtype),
                "v": jnp.zeros((R, batch, max_len, kv, hd), dtype),
            })
        elif kind == "mamba":
            di = cfg.mamba_expand * cfg.d_model
            caches.append({
                "h": jnp.zeros((R, batch, di, cfg.mamba_d_state), jnp.float32),
                "conv": jnp.zeros((R, batch, cfg.mamba_d_conv - 1, di), dtype),
            })
        elif kind == "rwkv":
            H = cfg.d_model // cfg.rwkv_head_dim
            caches.append({
                "S": jnp.zeros((R, batch, H, cfg.rwkv_head_dim, cfg.rwkv_head_dim), jnp.float32),
                "shift": jnp.zeros((R, batch, 1, cfg.d_model), dtype),
                "shift_ffn": jnp.zeros((R, batch, 1, cfg.d_model), dtype),
            })
    return tuple(caches)


def _block_decode(cfg, kind, pi, bp, x, position, cache, constrain, layer=None,
                  attend=None):
    """One block's decode step.  ``cache`` is this layer's own cache, or,
    with ``layer``, the stack of every repeat's cache at this pattern
    position: attention then writes its new row into the stack in place,
    and a state layer reads its state out and writes it back whole.
    ``attend`` is ``attention_decode``'s placement hook."""
    tag = f"blk{pi}"
    mixer = _mixer_scope(kind)
    stacked = layer is not None
    if stacked and mixer != "attn":
        stack, cache = cache, jax.tree.map(
            lambda a: jax.lax.dynamic_index_in_dim(a, layer, keepdims=False),
            cache)
    with jax.named_scope(mixer):
        h = rmsnorm(x, bp["ln1"], cfg.norm_eps)
        if mixer == "attn":
            window = cfg.window if kind == "local" else 0
            a, (ck, cv) = attention_decode(
                bp["attn"], h, cfg, cache["k"], cache["v"], position, window,
                layer, attend,
            )
            new_cache = {"k": ck, "v": cv}
        elif mixer == "mamba":
            a, new_cache = mamba_decode(bp["mamba"], h, cfg, cache)
        else:
            a, new_cache = rwkv_time_mix(bp["rwkv"], h, cfg, state=cache)
        x = constrain(x + a, f"{tag}:{mixer}")
    with jax.named_scope("moe" if "moe" in bp else "ffn"):
        h2 = rmsnorm(x, bp["ln2"], cfg.norm_eps)
        if mixer == "rwkv":
            f, st2 = rwkv_channel_mix(bp["rwkv"], h2, state=cache)
            new_cache = {**new_cache, **st2}
        else:
            f = (moe_ffn(bp["moe"], h2, cfg, constrain) if "moe" in bp
                 else ffn(bp["ffn"], h2, cfg.ffn_gated))
        x = constrain(x + f, f"{tag}:ffn")
    if stacked and mixer != "attn":
        new_cache = {
            name: jax.lax.dynamic_update_index_in_dim(
                stack[name], new_cache[name].astype(stack[name].dtype),
                layer, 0)
            for name in stack
        }
    return x, new_cache


def decode_step(
    params: dict,
    cfg: ModelConfig,
    token: jax.Array,            # [B, 1] int32
    position: jax.Array,         # [B] write index
    caches: tuple,
    constrain=_identity_constrain,
    attend=None,
):
    """One autoregressive step.  Returns (logits [B,1,V], new caches).

    The stacked caches ride in the scan's carry, not in its ``xs``/``ys``:
    each layer writes its new row into the stack in place, so nothing
    slices a layer's cache out or stacks the caches again, and the donated
    input buffers are the output's.  ``attend`` places attention's
    per-slot part on the devices (``attention_decode``), as the runtime
    chooses for its cache split."""
    with jax.named_scope("embed"):
        x = embed(token, params["embed"])
        x = constrain(x, "embed")

    def body(carry, scanned):
        h, stacks = carry
        bps, layer = scanned
        new_stacks = []
        for pi, kind in enumerate(cfg.expanded_pattern):
            h, c = _block_decode(cfg, kind, pi, bps[pi], h, position,
                                 stacks[pi], constrain, layer, attend)
            new_stacks.append(c)
        return (h, tuple(new_stacks)), None

    R = cfg.pattern_repeats
    with jax.named_scope("layers"):
        (x, new_caches), _ = jax.lax.scan(
            body, (x, tuple(caches)),
            (params["blocks"], jnp.arange(R, dtype=jnp.int32)),
            unroll=max(1, min(cfg.scan_unroll, R)),
        )
    return _head(params, cfg, x, constrain), new_caches


# -------------------------------------------------------------------- loss

def loss_fn(
    params: dict,
    cfg: ModelConfig,
    tokens: jax.Array,
    labels: jax.Array,
    frontend_embeds: jax.Array | None = None,
    constrain=_identity_constrain,
    constrain2=None,
    transition_repeat: int | None = None,
) -> jax.Array:
    logits, _ = forward(
        params, cfg, tokens, frontend_embeds,
        constrain=constrain, constrain2=constrain2,
        transition_repeat=transition_repeat,
    )
    # labels cover the final S_label positions of the sequence (frontend
    # stub positions are unlabeled)
    S_lab = labels.shape[1]
    logits = logits[:, -S_lab:]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return nll.mean()
