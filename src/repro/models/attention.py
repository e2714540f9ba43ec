"""GQA attention: prefill (full/sliding-window causal) and KV-cache decode.

The jnp path here is the reference implementation used for dry-run lowering
and CPU tests; on real TPUs the Pallas flash kernel
(``repro.kernels.flash_attention``) substitutes for the prefill einsum path
(``impl="pallas"``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import apply_rope, dense, rope_freqs, softcap

NEG_INF = -2.0e38


def init_attn(key, cfg: ModelConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    s = d ** -0.5
    return {
        "wq": (jax.random.normal(ks[0], (d, h * hd)) * s).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d, kv * hd)) * s).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d, kv * hd)) * s).astype(dtype),
        "wo": (jax.random.normal(ks[3], (h * hd, d)) * (h * hd) ** -0.5).astype(dtype),
    }


def _split_heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


def attention_scores(q, k, scale, cap):
    """q [B,S,H,hd], k [B,T,KV,hd] -> scores [B,H,S,T] with GQA broadcast."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    qg = q.reshape(B, S, KV, g, hd)
    scores = jnp.einsum("bskgh,btkh->bkgst", qg.astype(jnp.float32), k.astype(jnp.float32))
    scores = softcap(scores * scale, cap)
    return scores.reshape(B, KV * g, S, k.shape[1])


def attention_prefill(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    positions: jax.Array,
    window: int = 0,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Full-sequence causal attention.  Returns (out, (k, v)) for caching."""
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = _split_heads(dense(x, params["wq"]), H, hd)
    k = _split_heads(dense(x, params["wk"]), KV, hd)
    v = _split_heads(dense(x, params["wv"]), KV, hd)
    cos, sin = rope_freqs(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    scores = attention_scores(q, k, hd ** -0.5, cfg.attn_softcap)  # [B,H,S,S]
    i = positions[:, :, None]          # query positions [B,S,1]
    j = positions[:, None, :]          # key positions   [B,1,S]
    mask = j <= i
    if window > 0:
        mask &= j > i - window
    scores = jnp.where(mask[:, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)

    g = H // KV
    pg = probs.reshape(B, KV, g, S, S)
    out = jnp.einsum("bkgst,btkh->bskgh", probs.reshape(B, KV, g, S, S), v.astype(jnp.float32))
    out = out.reshape(B, S, H * hd).astype(x.dtype)
    return dense(out, params["wo"]), (k, v)


def attention_decode(
    params: dict,
    x: jax.Array,
    cfg: ModelConfig,
    cache_k: jax.Array,        # [B, T, KV, hd], or [R, B, T, KV, hd] with layer
    cache_v: jax.Array,
    position: jax.Array,       # [B] current write index
    window: int = 0,
    layer: jax.Array | None = None,
    attend=None,
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """One-token decode against a KV cache, in-place cache update.

    With ``layer`` the caches are the stack of every repeat's cache: only
    row ``[layer, b, position[b]]`` is written, attention reads layer
    ``layer`` of the stack, and the stacks are returned.  ``attend``, where
    given, places the per-slot part (``attend_cached``) on the devices:
    ``attend(core, q, k, v, position, cache_k, cache_v, layer)``."""
    B, S1, _ = x.shape
    assert S1 == 1
    q = dense(x, params["wq"])
    k = dense(x, params["wk"])
    v = dense(x, params["wv"])
    core = partial(attend_cached, cfg, window)
    if attend is None:
        out, cache_k, cache_v = core(q, k, v, position, cache_k, cache_v, layer)
    else:
        out, cache_k, cache_v = attend(core, q, k, v, position, cache_k,
                                       cache_v, layer)
    return dense(out, params["wo"]), (cache_k, cache_v)


def attend_cached(cfg: ModelConfig, window: int, q, k, v, position, cache_k,
                  cache_v, layer):
    """Decode attention of the slots given: q [B, 1, H*hd] and the new k, v
    [B, 1, KV*hd] -> (out [B, 1, H*hd], cache_k, cache_v): RoPE, the new
    rows written into the caches, scores, softmax and P·V."""
    B = q.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    T = cache_k.shape[-3]
    q = _split_heads(q, H, hd)
    k = _split_heads(k, KV, hd)
    v = _split_heads(v, KV, hd)
    cos, sin = rope_freqs(position[:, None], hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    with jax.named_scope("kv_write"):
        row = (jnp.arange(B), position)
        if layer is not None:
            row = (layer, *row)
        cache_k = cache_k.at[row].set(k[:, 0])
        cache_v = cache_v.at[row].set(v[:, 0])
    if layer is None:
        layer_k, layer_v = cache_k, cache_v
    else:
        layer_k = jax.lax.dynamic_index_in_dim(cache_k, layer, keepdims=False)
        layer_v = jax.lax.dynamic_index_in_dim(cache_v, layer, keepdims=False)

    scores = attention_scores(q, layer_k, hd ** -0.5, cfg.attn_softcap)  # [B,H,1,T]
    j = jnp.arange(T)[None, :]
    valid = j <= position[:, None]
    if window > 0:
        valid &= j > position[:, None] - window
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    g = H // KV
    out = jnp.einsum(
        "bkgst,btkh->bskgh", probs.reshape(B, KV, g, 1, T), layer_v.astype(jnp.float32)
    )
    return out.reshape(B, 1, H * hd).astype(q.dtype), cache_k, cache_v
