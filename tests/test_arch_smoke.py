"""Per-architecture smoke tests: reduced configs, one forward/train/decode
step on CPU, asserting output shapes and finiteness (assignment SSf)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_smoke_config
from repro.models import decode_step, forward, init_kv_cache, init_params, loss_fn
from repro.models.layers import embed
from repro.models.model import _block_decode, _head, _identity_constrain

B, S = 2, 16


def _inputs(cfg):
    key = jax.random.PRNGKey(0)
    if cfg.frontend == "audio_stub":
        emb = jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
        return None, emb, S
    if cfg.frontend == "vision_stub":
        ft = cfg.frontend_tokens
        toks = jax.random.randint(key, (B, S - ft), 0, cfg.vocab)
        emb = jax.random.normal(key, (B, ft, cfg.d_model), jnp.float32)
        return toks, emb, S
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    return toks, None, S


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_and_finite(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens, emb, S_total = _inputs(cfg)
    logits, _ = forward(params, cfg, tokens, emb)
    assert logits.shape == (B, S_total, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_finite_loss_and_grads(arch):
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(2))
    tokens, emb, S_total = _inputs(cfg)
    labels = jax.random.randint(jax.random.PRNGKey(3), (B, S_total), 0, cfg.vocab)

    loss, grads = jax.value_and_grad(
        lambda p: loss_fn(p, cfg, tokens, labels, emb)
    )(params)
    assert np.isfinite(float(loss))
    gnorm = jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(grads))
    )
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_step_matches_cache_semantics(arch):
    cfg = get_smoke_config(arch)
    if cfg.frontend != "none":
        pytest.skip("frontend stubs decode from token path only after prefill")
    params = init_params(cfg, jax.random.PRNGKey(4))
    caches = init_kv_cache(cfg, B, max_len=S, dtype=jnp.float32)
    tok = jax.random.randint(jax.random.PRNGKey(5), (B, 1), 0, cfg.vocab)
    pos = jnp.zeros((B,), jnp.int32)
    logits, new_caches = decode_step(params, cfg, tok, pos, caches)
    assert logits.shape == (B, 1, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    # cache structure preserved
    assert jax.tree.structure(caches) == jax.tree.structure(new_caches)


@pytest.mark.parametrize("arch", ["granite-3-8b", "rwkv6-3b", "jamba-v0.1-52b"])
def test_prefill_then_decode_consistency(arch):
    """Decoding token-by-token must reproduce the prefill logits."""
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(6))
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, 6), 0, cfg.vocab)
    full_logits, _ = forward(params, cfg, toks)

    caches = init_kv_cache(cfg, B, max_len=8, dtype=jnp.float32)
    outs = []
    for t in range(6):
        pos = jnp.full((B,), t, jnp.int32)
        lg, caches = decode_step(params, cfg, toks[:, t : t + 1], pos, caches)
        outs.append(lg[:, 0])
    dec_logits = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full_logits, np.float32),
        np.asarray(dec_logits, np.float32),
        rtol=2e-3, atol=2e-3,
    )


def _layer_by_layer_decode(params, cfg, token, position, caches):
    """Plain reference: each layer's cache sliced out of the stack, one
    ``_block_decode`` per layer, the new caches stacked again.  Jit it:
    op-by-op execution rounds differently from any compiled program."""
    x = embed(token, params["embed"])
    new = [[] for _ in cfg.expanded_pattern]
    for r in range(cfg.pattern_repeats):
        for pi, kind in enumerate(cfg.expanded_pattern):
            bp = jax.tree.map(lambda a: a[r], params["blocks"][pi])
            cache = jax.tree.map(lambda a: a[r], caches[pi])
            x, c = _block_decode(cfg, kind, pi, bp, x, position, cache,
                                 _identity_constrain)
            new[pi].append(c)
    stacked = tuple(jax.tree.map(lambda *a: jnp.stack(a), *c) for c in new)
    return _head(params, cfg, x, _identity_constrain), stacked


@pytest.mark.parametrize("arch", ["granite-3-8b", "jamba-v0.1-52b", "rwkv6-3b"])
def test_decode_step_matches_layer_by_layer(arch):
    """The scan that carries the stacked caches computes what a per-layer
    loop over sliced caches computes, and writes an attention cache only
    at each slot's own position."""
    cfg = get_smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(8))
    shapes = init_kv_cache(cfg, B, max_len=S, dtype=jnp.float32)
    leaves, tree = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(9), len(leaves))
    caches = jax.tree.unflatten(tree, [
        jax.random.normal(k, a.shape, a.dtype) for k, a in zip(keys, leaves)])
    tok = jax.random.randint(jax.random.PRNGKey(10), (B, 1), 0, cfg.vocab)
    pos = jnp.array([3, 11], jnp.int32)

    logits, new = decode_step(params, cfg, tok, pos, caches)
    ref_logits, ref = jax.jit(partial(_layer_by_layer_decode, cfg=cfg))(
        params, token=tok, position=pos, caches=caches)

    np.testing.assert_allclose(logits, ref_logits, rtol=1e-6, atol=1e-6)
    assert jax.tree.structure(new) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    for kind, old, c in zip(cfg.expanded_pattern, caches, new):
        if kind not in ("attn", "local"):
            continue
        for name in ("k", "v"):
            changed = np.asarray(old[name] != c[name]).any(axis=(3, 4))
            rows = np.zeros(changed.shape, bool)
            rows[:, np.arange(B), np.asarray(pos)] = True
            assert (changed == rows).all(), name
