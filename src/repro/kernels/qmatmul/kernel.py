"""Pallas TPU int8 x int8 -> int32 blocked matmul with row/col dequant.

The paper deploys 8-bit weights/activations with 24-bit accumulation
(Table III); the TPU analogue is int8 MXU issue with int32 accumulation.
Grid = (M/bm, N/bn, K/bk), K sequential with an int32 VMEM accumulator;
dequantization (row scale x col scale) happens once at the last K step.
Blocks are 128-aligned for the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _qmm_kernel(x_ref, w_ref, xs_ref, ws_ref, o_ref, acc_scr, *, k_steps: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[...]            # [bm, bk] int8
    w = w_ref[...]            # [bk, bn] int8
    acc_scr[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )

    @pl.when(kk == k_steps - 1)
    def _final():
        xs = xs_ref[...].astype(jnp.float32)       # [bm, 1]
        ws = ws_ref[...].astype(jnp.float32)       # [1, bn]
        o_ref[...] = (acc_scr[...].astype(jnp.float32) * xs * ws).astype(o_ref.dtype)


def qmatmul_kernel(
    x: jax.Array,        # [M, K] int8
    w: jax.Array,        # [K, N] int8
    x_scale: jax.Array,  # [M] f32 (per-row)
    w_scale: jax.Array,  # [N] f32 (per-col)
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    out_dtype=jnp.float32,
    interpret: bool = False,
) -> jax.Array:
    M, K = x.shape
    N = w.shape[1]
    block_m, block_n, block_k = min(block_m, M), min(block_n, N), min(block_k, K)
    assert M % block_m == 0 and N % block_n == 0 and K % block_k == 0
    k_steps = K // block_k
    kernel = functools.partial(_qmm_kernel, k_steps=k_steps)
    return pl.pallas_call(
        kernel,
        grid=(M // block_m, N // block_n, k_steps),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
            # scales as [M, 1] / [1, N]: 2-D blocks follow the TPU tiling,
            # where a 1-D block's layout differs from XLA's
            pl.BlockSpec((block_m, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, w, x_scale.reshape(M, 1), w_scale.reshape(1, N))
