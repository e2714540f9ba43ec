"""Pallas TPU flash attention (forward): causal/windowed, GQA, logit softcap.

TPU mapping (DESIGN.md SS3 -- MXU/VMEM adaptation, not a CUDA port):
* grid = (batch, q_heads, Sq/block_q, Skv/block_k); the last axis is
  ``arbitrary`` (sequential) so the online-softmax state lives in VMEM
  scratch across kv steps.
* BlockSpecs stage [block_q, head_dim] / [block_k, head_dim] tiles in VMEM;
  head_dim and block sizes are 128-multiples to fill the MXU's 128x128
  systolic tiles.
* Causal/window masking prunes whole kv blocks via ``pl.when`` (no wasted
  MXU work on fully-masked tiles).
* fp32 running max / sum / accumulator scratch (online softmax).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1.0e30


def _fa_kernel(
    q_ref, k_ref, v_ref,            # VMEM tiles
    o_ref,                          # output tile
    m_scr, l_scr, acc_scr,          # scratch: running max/denominator/acc
    *,
    scale: float,
    causal: bool,
    window: int,
    softcap: float,
    block_q: int,
    block_k: int,
    kv_steps: int,
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_lo = qi * block_q
    k_lo = kj * block_k

    # Whole-block visibility test: skip fully masked kv tiles.
    run = True
    if causal:
        run = k_lo <= q_lo + block_q - 1
    if window > 0:
        run = jnp.logical_and(run, k_lo + block_k - 1 > q_lo - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)          # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                     # [bq, bk]
        if softcap > 0.0:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(kj == kv_steps - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(
    q: jax.Array,      # [B, H, Sq, hd]
    k: jax.Array,      # [B, KV, Skv, hd]
    v: jax.Array,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, H, Sq, hd = q.shape
    KV, Skv = k.shape[1], k.shape[2]
    assert H % KV == 0
    g = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Skv)
    assert Sq % block_q == 0 and Skv % block_k == 0
    kv_steps = Skv // block_k
    scale = 1.0 / math.sqrt(hd)

    grid = (B, H, Sq // block_q, kv_steps)
    kernel = functools.partial(
        _fa_kernel,
        scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, kv_steps=kv_steps,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda b, h, i, j, g=g: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
