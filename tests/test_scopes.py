"""The named scopes of the model's parts reach the compiled serving steps
as ``op_name`` metadata, and nothing else of the program changes."""
import contextlib
import re
from dataclasses import replace

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke_config
from repro.launch.hlo_analysis import PASS_THROUGH, unfused_instructions
from repro.launch.mesh import single_device_mesh
from repro.models import attention, init_kv_cache, init_params, model
from repro.runtime.serve import build_decode_step, build_prefill_step
from repro.runtime.sharding import ShardPlan

B, S = 2, 16
INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\b([a-z][\w\-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')


def _compiled(kind: str) -> str:
    """Optimized HLO of the decode or prefill step of a 2-layer model of
    Granite's layout (GQA attention, gated FFN)."""
    cfg = replace(get_smoke_config("granite-3-8b"), n_layers=2)
    mesh = single_device_mesh()
    plan = ShardPlan(mesh_axes=("data", "model"))
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    if kind == "decode":
        step, _ = build_decode_step(cfg, mesh, plan, batch=B, max_len=S)
        caches = jax.eval_shape(lambda: init_kv_cache(cfg, B, S))
        low = step.lower(params, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                         jax.ShapeDtypeStruct((B,), jnp.int32), caches)
    else:
        step, _ = build_prefill_step(cfg, mesh, plan)
        low = step.lower(params, jax.ShapeDtypeStruct((B, S), jnp.int32))
    return low.compile().as_text()


def _ops(text: str) -> list[tuple[str, str, str]]:
    """(name, opcode, op_name) of every instruction, fused ones included."""
    out = []
    for line in text.splitlines():
        m = INSTR.match(line)
        if m:
            on = OP_NAME.search(line)
            out.append((m.group(1), m.group(2), on.group(1) if on else ""))
    return out


def _strip(text: str) -> str:
    """The HLO without metadata and without its table of source frames."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    keep, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif skip and not line.startswith(("%", "ENTRY")) and \
                not re.match(r"^[\w.\-]+ \(", line):
            continue
        else:
            skip = False
            keep.append(line)
    return "\n".join(keep)


@pytest.fixture(scope="module")
def hlo():
    return {k: _compiled(k) for k in ("decode", "prefill")}


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_every_matmul_is_in_a_block_or_the_head(hlo, kind):
    """Each dot the program wrote carries ``attn``, ``ffn`` or ``head``;
    the CPU compiler also splits batched dots into dots of its own, which
    carry no op_name at all."""
    dots = [on for _, op, on in _ops(hlo[kind])
            if op in ("dot", "convolution") and on]
    assert dots
    for on in dots:
        assert {"attn", "ffn", "head"} & set(on.split("/")), on


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_scan_slicing_is_under_layers(hlo, kind):
    """The scan's own slicing of the stacked weights runs under ``layers``,
    outside any block.  In decode the stacked cache rides in the scan's
    carry: nothing updates a slice of it, and the only ops that write an
    array of its shape are the cache write's under ``attn/kv_write``."""
    scan = [on for _, _, on in _ops(hlo[kind])
            if re.search(r"/while/body/dynamic_(update_)?slice$", on)]
    assert scan
    for on in scan:
        assert "/layers/while/body/" in on, on
    if kind == "decode":
        assert not [(name, on) for name, op, on in _ops(hlo[kind])
                    if op == "dynamic-update-slice"
                    or on.endswith("dynamic_update_slice")]
        cfg = replace(get_smoke_config("granite-3-8b"), n_layers=2)
        stack = jax.eval_shape(lambda: init_kv_cache(cfg, B, S))[0]["k"].shape
        writers = [i for i in unfused_instructions(hlo[kind])
                   if i.dims == stack and i.opcode not in PASS_THROUGH]
        assert any(i.op_name.endswith("/attn/kv_write/scatter")
                   for i in writers)
        for i in writers:
            assert "/attn/kv_write/" in i.op_name, i


def test_decode_cache_write_is_under_attn_kv_write(hlo):
    scatters = [on for _, op, on in _ops(hlo["decode"])
                if op == "scatter" and on]
    assert scatters
    for on in scatters:
        assert "/attn/kv_write/" in on, on


@pytest.mark.parametrize("kind", ["decode", "prefill"])
def test_scopes_change_nothing_but_metadata(hlo, kind, monkeypatch):
    """Built again with every named scope turned into a no-op, the step's
    optimized HLO differs in its metadata alone."""
    def no_scope(name):
        return contextlib.nullcontext()

    monkeypatch.setattr(model.jax, "named_scope", no_scope)
    assert attention.jax is model.jax
    bare = _compiled(kind)
    assert "/attn/" not in bare and "/attn/" in hlo[kind]
    assert _strip(bare) == _strip(hlo[kind])
