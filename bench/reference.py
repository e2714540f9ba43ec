"""Plain reference of a dense GQA decoder with a gated (SwiGLU) FFN.

Written from the Granite/Llama layer equations and the configuration file
alone; it imports nothing of the program and takes nothing it made.  The
weights are drawn again from ``--seed`` by the recipe the configuration
file states under ``assumed.weights`` (threefry, one key per layer, the
same split order), so both sides hold the same numbers without sharing an
array.

It runs layer by layer once the program's state is freed: the weights of
one layer are drawn, applied to every sequence of the sample, and dropped.
Activations, norms, softmax and every matmul are float32 at HIGHEST
precision (a TPU otherwise rounds float32 matmul inputs to bfloat16).

``quant`` gives the control: the same model computed in ``"int8"`` (or
``"fp8"``, e4m3), with symmetric per-output-channel weight scales and
per-token activation scales on every linear layer and the output head
(attention stays float32), the step below bfloat16 that would tempt a
later change.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def padded_vocab(cfg: dict) -> int:
    return (cfg["vocab_size"] + 255) // 256 * 256


# ------------------------------------------------------------------ weights

def _normal(key, shape, fan_in):
    return (jax.random.normal(key, shape) * fan_in ** -0.5).astype(jnp.bfloat16)


@partial(jax.jit, static_argnums=(1,))
def _draw_block(key, dims):
    d, q, kv, ff = dims
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 4)
    f = jax.random.split(ks[1], 3)
    return {
        "wq": _normal(a[0], (d, q), d), "wk": _normal(a[1], (d, kv), d),
        "wv": _normal(a[2], (d, kv), d), "wo": _normal(a[3], (q, d), q),
        "w1": _normal(f[0], (d, ff), d), "w2": _normal(f[1], (ff, d), ff),
        "w3": _normal(f[2], (d, ff), d),
    }


@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw_table(key, rows, cols, fan_in):
    return _normal(key, (rows, cols), fan_in)


class Weights:
    """Draws layer ``i``'s matrices, the embedding and the head from the seed
    key, as ``init_params`` of a one-kind, one-pattern model does: keys =
    split(key, L + 2), layer i from keys[i], embedding from keys[-2], head
    from keys[-1]; norm scales are zero (gain 1 + 0)."""

    def __init__(self, cfg: dict, key: np.ndarray):
        self.cfg = cfg
        d = cfg["hidden_size"]
        h = cfg["num_attention_heads"]
        hd = cfg.get("head_dim") or d // h
        self.dims = (d, h * hd, cfg["num_key_value_heads"] * hd,
                     cfg["intermediate_size"])
        self.keys = jax.random.split(jnp.asarray(key), cfg["num_hidden_layers"] + 2)

    def block(self, i: int) -> dict:
        return _draw_block(self.keys[i], self.dims)

    def embed(self):
        d, V = self.dims[0], self.cfg["vocab_size"]
        return _draw_table(self.keys[-2], padded_vocab(self.cfg), d, d)[:V]

    def head(self):
        d, V = self.dims[0], self.cfg["vocab_size"]
        if self.cfg["tie_word_embeddings"]:
            return self.embed().T
        return _draw_table(self.keys[-1], d, padded_vocab(self.cfg), d)[:, :V]


# ------------------------------------------------------------------ layers

def _q8(x, axis):
    """Symmetric int8 along ``axis`` -> (int8 values, float32 scales)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _f8(x, axis):
    """float8 e4m3 along ``axis``, scaled so the largest magnitude is 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), s


def _mm(x, w, quant):
    """x [..., k] float32 @ w [k, n] (bf16 weights)."""
    if quant == "fp8":
        xq, xs = _f8(x, -1)
        wq, ws = _f8(w.astype(jnp.float32), 0)
        y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return y * xs * ws
    if quant == "int8":
        xq, xs = _q8(x, -1)
        wq, ws = _q8(w.astype(jnp.float32), 0)
        y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * xs * ws
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope(x, theta):
    """Rotate-half RoPE over positions 0..S-1; x [n, S, heads, hd]."""
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnums=(2, 3), donate_argnums=(1,))
def _layer(w, x, hp, quant):
    """One block over x [n, S, d] float32 (causal, every sequence from 0)."""
    H, KV, eps, theta, amul, rmul = hp
    n, S, d = x.shape
    hd = w["wq"].shape[1] // H
    h = _rms(x, eps)
    q = _rope(_mm(h, w["wq"], quant).reshape(n, S, H, hd), theta)
    k = _rope(_mm(h, w["wk"], quant).reshape(n, S, KV, hd), theta)
    v = _mm(h, w["wv"], quant).reshape(n, S, KV, hd)
    q = q.reshape(n, S, KV, H // KV, hd)
    s = jnp.einsum("nskgh,ntkh->nkgst", q, k, precision=HI) * amul
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgst,ntkh->nskgh", p, v, precision=HI).reshape(n, S, H * hd)
    x = x + rmul * _mm(o, w["wo"], quant)
    h = _rms(x, eps)
    f = jax.nn.silu(_mm(h, w["w1"], quant)) * _mm(h, w["w3"], quant)
    return x + rmul * _mm(f, w["w2"], quant)


@partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, emul):
    return table[tokens].astype(jnp.float32) * emul


@partial(jax.jit, static_argnums=(4, 5))
def _logits(x, rows, head, lscale, eps, quant):
    h = _rms(x[rows[:, 0], rows[:, 1]], eps)
    return _mm(h, head, quant) / lscale


def forward_rows(cfg: dict, key: np.ndarray, seqs, rows, quant=None,
                 group_tokens: int = 4096):
    """Logits [len(rows), vocab] (float32, on the device) at the positions
    ``rows``: a list of (sequence index, position) pairs into ``seqs``, a
    list of int token arrays.  Sequences are padded at the end to a
    multiple of 512, which causal attention keeps from the real positions,
    and run in groups of one padded length."""
    W = Weights(cfg, key)
    hp = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
          cfg["rms_norm_eps"], float(cfg["rope_theta"]),
          float(cfg["attention_multiplier"]), float(cfg["residual_multiplier"]))
    groups: dict[int, list[int]] = {}
    for i, s in enumerate(seqs):
        groups.setdefault(-(-len(s) // 512) * 512, []).append(i)
    batches = []                      # (padded length, [seq indices])
    for S, idx in sorted(groups.items()):
        per = max(1, group_tokens // S)
        batches += [(S, idx[j:j + per]) for j in range(0, len(idx), per)]
    table = W.embed()
    xs = []
    for S, idx in batches:
        toks = np.zeros((max(1, group_tokens // S), S), np.int32)
        for r, i in enumerate(idx):
            toks[r, :len(seqs[i])] = seqs[i]
        xs.append(_embed(table, jnp.asarray(toks),
                         float(cfg["embedding_multiplier"])))
    del table
    for layer in range(cfg["num_hidden_layers"]):
        w = W.block(layer)
        xs = [_layer(w, x, hp, quant) for x in xs]
        del w
    where = {i: (b, r) for b, (_, idx) in enumerate(batches)
             for r, i in enumerate(idx)}
    head = W.head()
    out = []
    for b, x in enumerate(xs):
        sel = [(n, where[i][1], p) for n, (i, p) in enumerate(rows)
               if where[i][0] == b]
        if not sel:
            continue
        r = jnp.asarray(np.array([[q, p] for _, q, p in sel], np.int32))
        out.append((np.array([n for n, _, _ in sel]),
                    _logits(x, r, head, float(cfg["logits_scaling"]),
                            cfg["rms_norm_eps"], quant)))
    order = np.concatenate([n for n, _ in out])
    logits = jnp.concatenate([l for _, l in out])
    return logits[jnp.asarray(np.argsort(order))]


@jax.jit
def served_gaps(ref_logits, served):
    """How far below the reference's best logit each served token lies."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
    return best - got


@jax.jit
def control_gaps(ref_logits, low_logits):
    """The same gap for the token that the low-precision run puts first."""
    return served_gaps(ref_logits, jnp.argmax(low_logits, -1).astype(jnp.int32))
