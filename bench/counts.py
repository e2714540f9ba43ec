"""Operations and bytes that a step of a dense GQA decoder needs, from shapes.

These are the work the model asks for, not what the program happens to do:
weights are read once per step, attention covers the live (causal) keys
only, and the output head runs at the positions whose logits are used.
Padding, recomputation and temporaries do not count, so a program that
wastes work shows a lower share of the roofline, never a higher one.

``cfg`` is the ``config`` object of a configuration file under
``bench/configs`` (Hugging Face key names).
"""
from __future__ import annotations

from dataclasses import dataclass

BF16 = 2
F32 = 4


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


def least_time(flops: float, nbytes: float, peak_flops: float,
               peak_bw: float) -> tuple[float, str]:
    """The roofline: the larger of compute time and memory time, and which."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
