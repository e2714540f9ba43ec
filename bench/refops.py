"""Arithmetic that every plain reference of ``bench/archs`` shares.

Weights are drawn on the device from the seed as the program's
``init_params`` draws them: normal(0, 1/sqrt(fan_in)) in bfloat16, the
embedding and head over the vocabulary padded to a multiple of 256.
Activations, norms and every matmul are float32 at HIGHEST precision (a
TPU otherwise rounds float32 matmul inputs to bfloat16).

``quant`` gives the control: a matmul computed in ``"int8"`` (or
``"fp8"``, e4m3), with symmetric per-output-channel weight scales and
per-token activation scales, the step below bfloat16 that would tempt a
later change.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def padded_vocab(cfg: dict) -> int:
    return (cfg["vocab_size"] + 255) // 256 * 256


def normal(key, shape, fan_in):
    return (jax.random.normal(key, shape) * fan_in ** -0.5).astype(jnp.bfloat16)


@partial(jax.jit, static_argnums=(1, 2, 3))
def draw_table(key, rows, cols, fan_in):
    return normal(key, (rows, cols), fan_in)


def q8(x, axis):
    """Symmetric int8 along ``axis`` -> (int8 values, float32 scales)."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def f8(x, axis):
    """float8 e4m3 along ``axis``, scaled so the largest magnitude is 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.bfloat16), s


def mm(x, w, quant):
    """x [..., k] float32 @ w [k, n] (bf16 weights)."""
    if quant == "fp8":
        xq, xs = f8(x, -1)
        wq, ws = f8(w.astype(jnp.float32), 0)
        y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return y * xs * ws
    if quant == "int8":
        xq, xs = q8(x, -1)
        wq, ws = q8(w.astype(jnp.float32), 0)
        y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                                preferred_element_type=jnp.int32)
        return y.astype(jnp.float32) * xs * ws
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
