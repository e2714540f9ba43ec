"""Granite (``model_type: granite``): a dense GQA decoder with RoPE and a
gated (SwiGLU) FFN.

The three things the benchmark needs of an architecture, each from the
configuration file's ``config`` (Hugging Face key names):

* ``program_config`` -- the program's ``ModelConfig`` at the file's sizes,
  refused where the program would compute another model;
* ``forward_rows``   -- the plain reference, written from the Granite/Llama
  layer equations and the configuration file alone: it imports nothing of
  the program and takes nothing it made;
* ``work``           -- the FLOPs and bytes each step of a window needs,
  from shapes.
"""
from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from refops import HI, draw_table, mm, normal, padded_vocab, rms

NEG = -1e30
BF16 = 2
F32 = 4


# --------------------------------------------------------------- the program

def program_config(config: dict):
    """The program's ModelConfig with the sizes of a configuration file;
    refused where the program would compute another model than the file
    states (it has no keys for Granite's multipliers)."""
    from repro.configs import get_config

    c = config["config"]
    mc = replace(
        get_config(config["program_arch"]), n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], ffn_gated=c["hidden_act"] == "silu",
        tie_embeddings=c["tie_word_embeddings"], param_dtype=config["dtype"])
    fixed = {"attention_multiplier": mc.head_dim ** -0.5,
             "embedding_multiplier": 1.0, "residual_multiplier": 1.0,
             "logits_scaling": 1.0}
    bad = {k: (v, c[k]) for k, v in fixed.items()
           if abs(c[k] - v) > 1e-12 * max(1.0, abs(v))}
    if bad or mc.block_pattern != ("attn",) or mc.moe is not None \
            or mc.logit_softcap or mc.attn_softcap or mc.d_head \
            or jnp.dtype(mc.param_dtype) != jnp.bfloat16:
        raise SystemExit(f"{config['name']}: the program computes another "
                         f"model than the file states (program, file): {bad}")
    return mc


# ------------------------------------------------------------ the reference
#
# The weights are drawn again from ``--seed`` by the recipe the
# configuration file states under ``assumed.weights`` (threefry, one key per
# layer, the same split order), so both sides hold the same numbers without
# sharing an array.  The reference runs layer by layer once the program's
# state is freed: the weights of one layer are drawn, applied to every
# sequence of the sample, and dropped.  ``quant`` computes every linear
# layer and the output head in int8 or fp8 (attention stays float32).

@partial(jax.jit, static_argnums=(1,))
def _draw_block(key, dims):
    d, q, kv, ff = dims
    ks = jax.random.split(key, 4)
    a = jax.random.split(ks[0], 4)
    f = jax.random.split(ks[1], 3)
    return {
        "wq": normal(a[0], (d, q), d), "wk": normal(a[1], (d, kv), d),
        "wv": normal(a[2], (d, kv), d), "wo": normal(a[3], (q, d), q),
        "w1": normal(f[0], (d, ff), d), "w2": normal(f[1], (ff, d), ff),
        "w3": normal(f[2], (d, ff), d),
    }


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
        return draw_table(self.keys[-2], padded_vocab(self.cfg), d, d)[:V]

    def head(self):
        d, V = self.dims[0], self.cfg["vocab_size"]
        if self.cfg["tie_word_embeddings"]:
            return self.embed().T
        return draw_table(self.keys[-1], d, padded_vocab(self.cfg), d)[:, :V]


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
    h = rms(x, eps)
    q = _rope(mm(h, w["wq"], quant).reshape(n, S, H, hd), theta)
    k = _rope(mm(h, w["wk"], quant).reshape(n, S, KV, hd), theta)
    v = mm(h, w["wv"], quant).reshape(n, S, KV, hd)
    q = q.reshape(n, S, KV, H // KV, hd)
    s = jnp.einsum("nskgh,ntkh->nkgst", q, k, precision=HI) * amul
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgst,ntkh->nskgh", p, v, precision=HI).reshape(n, S, H * hd)
    x = x + rmul * mm(o, w["wo"], quant)
    h = rms(x, eps)
    f = jax.nn.silu(mm(h, w["w1"], quant)) * mm(h, w["w3"], quant)
    return x + rmul * mm(f, w["w2"], quant)


@partial(jax.jit, static_argnums=(2,))
def _embed(table, tokens, emul):
    return table[tokens].astype(jnp.float32) * emul


@partial(jax.jit, static_argnums=(4, 5))
def _logits(x, rows, head, lscale, eps, quant):
    h = rms(x[rows[:, 0], rows[:, 1]], eps)
    return mm(h, head, quant) / lscale


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


# ------------------------------------------------------------------- counts
#
# The work the model asks for, not what the program happens to do: weights
# are read once per step, attention covers the live (causal) keys only, and
# the output head runs at the positions whose logits are used.  Padding,
# recomputation and temporaries do not count, so a program that wastes work
# shows a lower share of the roofline, never a higher one.

@dataclass(frozen=True)
class Shapes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    gated: bool = True

    @classmethod
    def of(cls, cfg: dict) -> "Shapes":
        d = cfg["hidden_size"]
        heads = cfg["num_attention_heads"]
        return cls(
            layers=cfg["num_hidden_layers"], d=d, heads=heads,
            kv_heads=cfg["num_key_value_heads"],
            head_dim=cfg.get("head_dim") or d // heads,
            ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            gated=cfg.get("hidden_act", "silu") == "silu")

    @property
    def attn_params(self) -> int:
        q = self.heads * self.head_dim
        kv = self.kv_heads * self.head_dim
        return self.d * q + 2 * self.d * kv + q * self.d

    @property
    def ffn_params(self) -> int:
        return (3 if self.gated else 2) * self.d * self.ff

    @property
    def block_params(self) -> int:
        """Matrix parameters of all blocks (norm scales are not matmuls)."""
        return self.layers * (self.attn_params + self.ffn_params)

    @property
    def head_params(self) -> int:
        return self.d * self.vocab

    @property
    def kv_bytes_per_position(self) -> int:
        """K and V of one position over all layers, in bf16."""
        return self.layers * 2 * self.kv_heads * self.head_dim * BF16

    def attn_flops(self, keys: int) -> int:
        """QK^T and PV of one query against ``keys`` keys, all layers."""
        return self.layers * 4 * self.heads * self.head_dim * keys

    @property
    def weight_bytes(self) -> int:
        """Block and output-head matrices in bf16, plus float32 norm scales."""
        norms = (2 * self.layers + 1) * self.d * F32
        return (self.block_params + self.head_params) * BF16 + norms


def decode_step(s: Shapes, positions) -> tuple[float, float]:
    """(FLOPs, bytes) of one decode step; slot ``b`` writes position
    ``positions[b]`` and attends to the ``positions[b] + 1`` keys up to it.

    Bytes: the weights once, the embedding row of each slot, the K/V of the
    live keys read, the new K/V written, and float32 logits of the one
    position written out."""
    positions = [int(p) for p in positions]
    b = len(positions)
    keys = sum(p + 1 for p in positions)
    flops = b * 2 * (s.block_params + s.head_params) + s.attn_flops(keys)
    nbytes = (s.weight_bytes + b * s.d * BF16
              + keys * s.kv_bytes_per_position
              + b * s.kv_bytes_per_position
              + b * s.vocab * F32)
    return float(flops), float(nbytes)


def prefill_call(s: Shapes, lengths) -> tuple[float, float]:
    """(FLOPs, bytes) of one prefill call over prompts of ``lengths`` real
    tokens: causal attention over each prompt, the output head at its last
    position only.  Bytes: the weights once, the embedding rows, the K/V a
    prefill instance hands on, and float32 logits of one position a prompt."""
    lengths = [int(n) for n in lengths]
    tokens = sum(lengths)
    causal_keys = sum(n * (n + 1) // 2 for n in lengths)
    flops = (tokens * 2 * s.block_params + s.attn_flops(causal_keys)
             + len(lengths) * 2 * s.head_params)
    nbytes = (s.weight_bytes + tokens * s.d * BF16
              + tokens * s.kv_bytes_per_position
              + len(lengths) * s.vocab * F32)
    return float(flops), float(nbytes)


def work(cfg: dict, window: dict) -> list[tuple[float, float]]:
    """(FLOPs, bytes) the model needs for each step or call of the window:
    decode, one per step at its slots' positions (``step_pos``); prefill,
    one per call over its prompts' lengths (``calls``)."""
    s = Shapes.of(cfg)
    if window["kind"] == "decode":
        return [decode_step(s, pos) for pos in window["step_pos"]]
    return [prefill_call(s, lengths) for _, lengths in window["calls"]]
