"""Granite decoding on a four-chip v5e package, the path of the four-chip
benchmark cell at toy widths, on 4 fake CPU devices in a subprocess (the
main pytest process keeps seeing 1 device)."""
import os
import subprocess
import sys

import pytest

SCRIPT = os.path.join(os.path.dirname(__file__), "md_scripts",
                      "check_sharded_decode.py")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["heads", "positions"])
def test_sharded_decode_matches_the_reference(kv_heads):
    """solve -> deploy -> build_steps on tpu_v5e(4, (1, 4)): the cache
    splits over the KV heads where 4 divides them, else over positions;
    no op of the step gathers a layer of cache (nor of a prompt's write
    into a head-split cache); and decode logits across every shard
    boundary, and after a rewind back across two, match the benchmark's
    plain float32 reference."""
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, SCRIPT, str(kv_heads)],
                         capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "OK" in out.stdout
