"""Pallas TPU chunked selective scan (Mamba-1 SSM core).

Recurrence per channel block (state h [N, bd], fp32):
    h_t = exp(dt_t * A) h_{t-1} + (dt_t * x_t) B_t
    y_t = C_t . h_t + D x_t

TPU mapping: grid = (batch, d_inner/bd, S/chunk) with the chunk axis
sequential; h persists in VMEM scratch, so the state never round-trips HBM.
The state is held channel-minor, [N, bd], so every per-step operand is a
row: dt/x rows [1, bd] are read from their [chunk, bd] tiles at a dynamic
sublane offset (``pl.ds(t, 1)`` on the ref), and the y row is written the
same way.  B/C arrive time-minor, [N, chunk] tiles, and step t's column is
picked with a lane mask and a lane reduction -- the TPU lowering has no
dynamic slice of a value and no dynamic lane offset.  The per-step update
is VPU elementwise work over [N, bd]: the kernel's value is state residency
+ fused discretization (exp(dt*A)) rather than MXU throughput.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _mamba_kernel(dt_ref, x_ref, At_ref, Bt_ref, Ct_ref, D_ref, y_ref,
                  h_out_ref, h_scr, *, chunk: int, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    At = At_ref[...].astype(jnp.float32)    # [N, bd]
    Bt = Bt_ref[0].astype(jnp.float32)      # [N, T]
    Ct = Ct_ref[0].astype(jnp.float32)      # [N, T]
    D = D_ref[...].astype(jnp.float32)      # [1, bd]
    lane_t = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)

    def step(t, h):
        dt = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # [1, bd]
        x = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)     # [1, bd]
        at_t = lane_t == t
        b = jnp.sum(jnp.where(at_t, Bt, 0.0), axis=1, keepdims=True)  # [N, 1]
        cc = jnp.sum(jnp.where(at_t, Ct, 0.0), axis=1, keepdims=True)
        h = jnp.exp(dt * At) * h + (dt * x) * b                      # [N, bd]
        y = jnp.sum(h * cc, axis=0, keepdims=True) + D * x          # [1, bd]
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        return h

    h_last = jax.lax.fori_loop(0, chunk, step, h_scr[...])
    h_scr[...] = h_last

    @pl.when(c == n_chunks - 1)
    def _final():
        h_out_ref[0] = h_last


def mamba_scan_kernel(
    dt: jax.Array,     # [B, S, di] fp32 (post-softplus)
    x: jax.Array,      # [B, S, di]
    A: jax.Array,      # [di, N]  (negative)
    Bc: jax.Array,     # [B, S, N]
    Cc: jax.Array,     # [B, S, N]
    D: jax.Array,      # [di]
    block_d: int = 128,
    chunk: int = 128,
    interpret: bool = False,
):
    """Returns (y [B,S,di] fp32, h_last [B,di,N] fp32).

    On the TPU ``chunk`` is the lane width of the B/C tiles, so it must be a
    multiple of 128 or the whole sequence."""
    B, S, di = x.shape
    N = A.shape[1]
    block_d = min(block_d, di)
    chunk = min(chunk, S)
    assert di % block_d == 0 and S % chunk == 0
    n_chunks = S // chunk
    grid = (B, di // block_d, n_chunks)
    kernel = functools.partial(_mamba_kernel, chunk=chunk, n_chunks=n_chunks)
    sd = pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d))
    nt = pl.BlockSpec((1, N, chunk), lambda b, d, c: (b, 0, c))
    y, h_t = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            sd,                                                     # dt
            sd,                                                     # x
            pl.BlockSpec((N, block_d), lambda b, d, c: (0, d)),     # A^T
            nt,                                                     # B^T
            nt,                                                     # C^T
            pl.BlockSpec((1, block_d), lambda b, d, c: (0, d)),     # D
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b, d, c: (b, c, d)),
            pl.BlockSpec((1, N, block_d), lambda b, d, c: (b, 0, d)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, N, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(dt, x, A.T, jnp.swapaxes(Bc, 1, 2), jnp.swapaxes(Cc, 1, 2),
      D.reshape(1, di))
    return y, jnp.swapaxes(h_t, 1, 2)
