"""Pallas TPU chunked WKV scan for RWKV-6 (data-dependent decay).

Recurrence per head (state S [hd, hd], fp32):
    out_t = r_t . (S_{t-1} + (u * k_t) v_t^T)
    S_t   = diag(w_t) S_{t-1} + k_t v_t^T

TPU mapping: the sequence is processed in chunks of T tokens; the state S
lives in VMEM scratch across the (sequential) chunk grid axis, so HBM traffic
is one read of r/k/v/logw and one write of out per token -- the recurrence
itself never touches HBM.  Within a chunk the scan is refactored into three
MXU matmuls (chunk form):

    lw      = cumsum(log w)                       # [T, hd] per-channel decays
    rt      = r * exp(lw - logw)  (exclusive)     # decayed receptance
    kt      = k * exp(-lw)                        # inverse-decayed keys
    intra   = tril_strict(rt @ kt^T) @ v + ((r*u*k) @ 1) v_t   (diagonal term)
    cross   = rt @ S
    S_new   = diag(exp(lw_T)) S + (k * exp(lw_T - lw))^T @ v

Numerics: per-channel cumulative decays are re-based inside each chunk, so
the exp() magnitudes are bounded by the *chunk* decay range; chunk=32..64
keeps fp32 well in range for w >= ~0.6 (production RWKV clamps decay).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_out_ref, s_scr, *,
                chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)       # [T, hd]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)     # log decay, <= 0
    u = u_ref[0].astype(jnp.float32)          # [1, hd]

    # inclusive per-channel cum-decay, as a lower-triangular matmul (the TPU
    # lowering has no cumsum); HIGHEST keeps the log-decays in full fp32
    ti = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    tj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    clw = jax.lax.dot_general(
        jnp.where(tj <= ti, 1.0, 0.0), lw, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    clw_excl = clw - lw                       # exclusive
    rt = r * jnp.exp(clw_excl)                # decayed receptance
    kt = k * jnp.exp(-clw)                    # inverse-decayed keys

    # intra-chunk attention-like term (strictly causal) + u-bonus diagonal
    a = jax.lax.dot_general(rt, kt, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [T, T]
    a = jnp.where(tj < ti, a, 0.0)
    intra = jax.lax.dot_general(a, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True) * v

    cross = jax.lax.dot_general(rt, s_scr[...], (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    o_ref[0, 0] = (cross + intra + diag).astype(o_ref.dtype)

    # state update
    total = clw[chunk - 1:chunk]               # [1, hd]
    kdec = k * jnp.exp(total - clw)            # keys decayed to chunk end
    # the same chunk decay as a column [hd, 1], to scale the state's rows
    total_col = jax.lax.dot_general(
        lw, jnp.ones((chunk, 1), jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    s_new = jnp.exp(total_col) * s_scr[...] + jax.lax.dot_general(
        kdec, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    s_scr[...] = s_new

    @pl.when(c == n_chunks - 1)
    def _final():
        s_out_ref[0, 0] = s_new


def wkv6_kernel(
    r: jax.Array,       # [B, H, S, hd]
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,    # [B, H, S, hd], log of decay in (0,1)
    u: jax.Array,       # [H, hd]
    chunk: int = 32,
    interpret: bool = False,
):
    """Returns (out [B,H,S,hd] fp32, S_last [B,H,hd,hd] fp32)."""
    B, H, S, hd = r.shape
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_chunks = S // chunk
    grid = (B, H, n_chunks)
    kernel = functools.partial(_wkv_kernel, chunk=chunk, n_chunks=n_chunks)
    tile = pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0))
    return pl.pallas_call(
        kernel,
        grid=grid,
        # u as [H, 1, hd]: its block is whole in the last two dims, as the
        # TPU tiling requires (a (1, hd) block of [H, hd] is not)
        in_specs=[tile, tile, tile, tile,
                  pl.BlockSpec((1, 1, hd), lambda b, h, c: (h, 0, 0))],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u.reshape(H, 1, hd))
