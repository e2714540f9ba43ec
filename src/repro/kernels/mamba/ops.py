"""Jit'd public wrapper for the chunked selective scan."""
from __future__ import annotations

from functools import partial

import jax

from .kernel import mamba_scan_kernel
from .ref import mamba_scan_ref


@partial(jax.jit, static_argnames=("block_d", "chunk", "impl", "interpret"))
def mamba_scan(dt, x, A, Bc, Cc, D, block_d: int = 128, chunk: int = 128,
               impl: str = "pallas", interpret: bool = False):
    if impl == "ref":
        return mamba_scan_ref(dt, x, A, Bc, Cc, D)
    return mamba_scan_kernel(dt, x, A, Bc, Cc, D, block_d=block_d,
                             chunk=chunk, interpret=interpret)
