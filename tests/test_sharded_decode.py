"""Granite decoding on a four-chip v5e package, the path of the four-chip
benchmark cell at toy widths, on 4 fake CPU devices in a subprocess (the
main pytest process keeps seeing 1 device); and the rule that picks the
axis the K/V caches split over."""
import os
import subprocess
import sys

import pytest

from repro.runtime.sharding import kv_split

SCRIPT = os.path.join(os.path.dirname(__file__), "md_scripts",
                      "check_sharded_decode.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("split", ["heads", "slots", "positions"])
def test_sharded_decode_matches_the_reference(split):
    """solve -> deploy -> build_steps on tpu_v5e(4, (1, 4)): the cache
    splits over the KV heads where each device holds 8 of them, over the
    slots where 4 divides them, else over positions; no op of the step
    gathers a layer of cache or a weight; the fill of two prompts gathers
    nothing larger than their own K/V (over positions, than their slots'
    rows); and decode logits across every shard boundary, and after a
    rewind, match the benchmark's plain float32 reference."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, SCRIPT, split],
                         capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK" in out.stdout


@pytest.mark.parametrize("kv_heads, model, slots, split", [
    (8, 1, 16, None),           # one device: nothing split
    (32, 4, 64, "heads"),       # 8 KV heads a device
    (16, 2, 3, "heads"),
    (8, 4, 64, "slots"),        # granite-3-8b on four chips: 16 slots a chip
    (8, 16, 32, "slots"),
    (4, 4, 4, "slots"),
    (8, 4, 6, "heads"),         # 2 heads a device, but 4 does not divide 6
    (8, 4, None, "heads"),      # slots not known
    (2, 4, 2, "positions"),
    (3, 4, 6, "positions"),
])
def test_kv_split_rule(kv_heads, model, slots, split):
    assert kv_split(kv_heads, model, slots) == split
