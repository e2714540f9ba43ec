"""Ahead-of-time compiles for a described TPU v5e chip, at real widths.

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present.  These tests catch what
interpret mode cannot -- block shapes off the (8, 128) tiling, primitives
the Pallas TPU lowering lacks, programs that do not fit the chip's HBM.
Nothing runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and pytest-xdist workers all import this
file.
"""
import os
from dataclasses import replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro import scope
from repro.configs import get_config
from repro.core.hw import tpu_v5e
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.mamba.ops import mamba_scan
from repro.kernels.qmatmul.ops import qmatmul
from repro.kernels.rwkv6.ops import wkv6
from repro.launch.hlo_analysis import (PASS_THROUGH, shape_bytes,
                                      unfused_instructions)
from repro.models import init_kv_cache, init_params
from repro.runtime.sharding import to_shardings

HBM_BYTES = 16 * 10**9      # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------------------------ kernels

def test_flash_attention_compiles(one_chip):
    """granite-3-8b attention: 32 heads, 8 KV heads, hd 128, S 2048."""
    q = _spec(one_chip, (1, 32, 2048, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 8, 2048, 128), jnp.bfloat16)
    _assert_kernel(flash_attention.lower(q, kv, kv).compile())


def test_wkv6_compiles(one_chip):
    """rwkv6-3b widths: d_model / head_dim = 40 heads of 64."""
    cfg = get_config("rwkv6-3b")
    H, hd = cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x = _spec(one_chip, (1, H, 512, hd))
    _assert_kernel(wkv6.lower(x, x, x, x, _spec(one_chip, (H, hd))).compile())


def test_mamba_scan_compiles(one_chip):
    """jamba widths: d_inner = 2 x 4096 = 8192, d_state 16."""
    cfg = get_config("jamba-v0.1-52b")
    di, N = cfg.mamba_expand * cfg.d_model, cfg.mamba_d_state
    sd = _spec(one_chip, (1, 512, di))
    sn = _spec(one_chip, (1, 512, N))
    compiled = mamba_scan.lower(sd, sd, _spec(one_chip, (di, N)), sn, sn,
                                _spec(one_chip, (di,))).compile()
    _assert_kernel(compiled)


def test_qmatmul_compiles(one_chip):
    """granite-3-8b FFN up-projection in int8: 512x4096 @ 4096x12800."""
    x = _spec(one_chip, (512, 4096), jnp.int8)
    w = _spec(one_chip, (4096, 12800), jnp.int8)
    compiled = qmatmul.lower(x, w, _spec(one_chip, (512,)),
                             _spec(one_chip, (12800,))).compile()
    _assert_kernel(compiled)


# ---------------------------------------------------------- main-path steps

def _granite_steps(devices, package, batch, max_len, n_layers=20):
    """granite-3-8b cut to ``n_layers`` of 40 layers, solved on ``package``
    and built through deploy on a (1, chips) mesh of the described chips."""
    cfg = replace(get_config("granite-3-8b"), n_layers=n_layers)
    sol = scope.solve(scope.problem(scope.WorkloadSpec.lm([cfg], 512),
                                    package))
    dep = sol.deploy(global_batch=batch)
    mesh = Mesh(np.array(devices).reshape(1, len(devices)),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    steps = dep.build_steps(mesh, batch=batch, max_len=max_len)[cfg.name]
    params = _shaped(mesh, steps["param_specs"],
                     lambda: init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, mesh, steps, params


def _decode_compiled(devices, package, batch, max_len, n_layers=20):
    """(stacked K cache shape, compiled decode step) of ``_granite_steps``."""
    cfg, mesh, steps, params = _granite_steps(devices, package, batch,
                                              max_len, n_layers)
    caches = _shaped(mesh, steps["cache_specs"],
                     lambda: init_kv_cache(cfg, batch, max_len))
    dp = steps["plan"].dp
    tok = _spec(NamedSharding(mesh, P(dp, None)), (batch, 1), jnp.int32)
    pos = _spec(NamedSharding(mesh, P(dp)), (batch,), jnp.int32)
    compiled = steps["decode"].lower(params, tok, pos, caches).compile()
    return caches[0]["k"].shape, compiled


def _shaped(mesh, specs, make):
    """Shapes of ``make()``'s pytree, each with its sharding from ``specs``."""
    return jax.tree.map(
        lambda s, x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
        to_shardings(mesh, specs), jax.eval_shape(make))


def _hbm_bytes(compiled):
    m = compiled.memory_analysis()
    return m.argument_size_in_bytes + m.temp_size_in_bytes


@pytest.fixture(scope="module")
def cell_decode(one_chip):
    """The decode step of the one-chip decode cell: granite-3-8b cut to 20
    layers, 16 slots of 2048 positions."""
    return _decode_compiled(list(one_chip.device_set), tpu_v5e(1, (1, 1)),
                            16, 2048)


def test_decode_step_fits_one_chip(cell_decode):
    _, compiled = cell_decode
    assert 8 * 10**9 < _hbm_bytes(compiled) < HBM_BYTES


def test_decode_writes_the_stacked_cache_in_place(cell_decode):
    """Decode writes each new K/V row into the donated stacked cache: no op
    copies or updates the stack or a whole layer of it, save the scatter of
    the new rows under ``attn/kv_write`` and attention's read of its layer,
    and the step needs next to no scratch memory (2.82 GB when the scan
    sliced and restacked the caches)."""
    stack, compiled = cell_decode
    layer = stack[1:]
    big = [i for i in unfused_instructions(compiled.as_text())
           if i.dims in (stack, layer, (1, *layer))
           and i.opcode not in PASS_THROUGH]
    assert any(i.dims == stack and i.op_name.endswith("/attn/kv_write/scatter")
               for i in big)
    for i in big:
        assert i.opcode != "copy" and "dynamic-update-slice" not in i.name, i
        assert "/attn/" in i.op_name, i
        if i.dims == stack:
            assert "/attn/kv_write/" in i.op_name, i
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 10**9


def test_sharded_decode_gathers_no_cache(topo):
    """granite-3-8b (40 layers) decoding on the described 2x2 package under
    the plan ``scope.solve`` gives it: the carried caches keep their
    shards, so no all-gather or all-to-all moves a layer of cache, and
    nothing gathers a weight.  With 2 of the 8 KV heads a chip the cache
    splits over slots, and each chip reads its layer of K and V as one
    chip does: whole slots with all 8 heads, tiled (8, 128), not (2, 128)."""
    stack, compiled = _decode_compiled(topo.devices, tpu_v5e(4, (2, 2)), 16,
                                       2048, n_layers=40)
    n = len(topo.devices)
    layer_shard = np.prod(stack[1:]) * 2 // n   # bf16
    ops = unfused_instructions(compiled.as_text())
    moves = [i for i in ops
             if i.opcode.removesuffix("-start") in ("all-gather", "all-to-all")]
    assert any(i.opcode.startswith("all-") for i in ops)
    assert not [i for i in moves if i.opcode.startswith("all-gather")]
    for i in moves:
        assert shape_bytes(i.type) < layer_shard, i
    local = (stack[1] // n, *stack[2:])
    reads = [i for i in ops if i.dims in (local, (1, *local))]
    assert reads and all("T(8,128)(2,1)" in i.type for i in reads), reads


def test_prefill_step_fits_one_chip(one_chip):
    batch, seq = 8, 512
    _, mesh, steps, params = _granite_steps(list(one_chip.device_set),
                                            tpu_v5e(1, (1, 1)), batch, 1024)
    toks = _spec(NamedSharding(mesh, P(steps["plan"].dp, None)), (batch, seq),
                 jnp.int32)
    compiled = steps["prefill"].lower(params, toks).compile()
    assert 8 * 10**9 < _hbm_bytes(compiled) < HBM_BYTES
