"""A whole run of a tiny cell on the CPU, past the look for a chip, with the
timed path broken underneath it: ``correct`` has to come out false for
every fault the cell can have, and true with none."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import tiny

LIMIT = 0.1     # the tiny model's sound runs read 0 to 0.05 here


def _copy_tree(tree):
    import jax
    import jax.numpy as jnp

    return jax.tree.map(jnp.copy, tree)


def state_unchanged(name, fn):
    """The decode step hands back the cache it was given."""
    if name != "decode":
        return fn

    def step(params, tok, pos, caches):
        kept = _copy_tree(caches)
        logits, _ = fn(params, tok, pos, caches)
        return logits, kept
    return step


def half_batch(name, fn):
    """Half of the batch is left out: its logits never computed (zeros)."""
    if name not in ("decode", "prefill"):
        return fn

    def step(*args):
        out = fn(*args)
        logits, rest = (out[0], out[1:]) if isinstance(out, tuple) else (out, None)
        logits = logits.at[logits.shape[0] // 2:].set(0.0)
        return (logits, *rest) if rest is not None else logits
    return step


def token_altered(name, fn):
    """The first slot's token is changed where it is produced."""
    if name not in ("greedy", "first"):
        return fn

    def pick(*args):
        tok = fn(*args)
        return tok.at[0].set((tok[0] + 1) % 500)
    return pick


def test_sound_runs_are_correct():
    for kind in ("decode", "prefill"):
        out = tiny.run(tiny.cell(kind, limit=LIMIT, seconds=0.5))
        assert out["correct"], (kind, out["checks"])
        assert list(out)[-1] == "checks"


@pytest.mark.parametrize("kind,fault", [
    ("decode", state_unchanged), ("decode", half_batch),
    ("decode", token_altered), ("prefill", half_batch),
    ("prefill", token_altered)])
def test_fault_is_caught(kind, fault):
    out = tiny.run(tiny.cell(kind, limit=LIMIT, seconds=0.5), fault=fault)
    assert not out["correct"], out["checks"]
    assert out["failed"] > 0


EXCHANGE = r'''
import sys
sys.path[:0] = [sys.argv[1]]
import jax, jax.numpy as jnp, numpy as np
import tiny

def left_out(name, fn):
    """Each chip keeps its own part: the weight shards of chips 1..3 never
    reach the sum (zeroed), as if the exchange between chips were left out."""
    if name != "decode":
        return fn
    def mask(x):
        dev0 = jax.devices()[0]
        shards = [jnp.ones(s.data.shape, x.dtype) if s.device == dev0
                  else jnp.zeros(s.data.shape, x.dtype)
                  for s in x.addressable_shards]
        shards = [jax.device_put(a, s.device)
                  for a, s in zip(shards, x.addressable_shards)]
        return jax.make_array_from_single_device_arrays(x.shape, x.sharding, shards)
    def step(params, tok, pos, caches):
        if "masked" not in step.__dict__:
            step.masked = jax.tree.map(lambda p: p * mask(p), params)
        return fn(step.masked, tok, pos, caches)
    return step

sound = tiny.run(tiny.cell("decode", chips=4, limit=float(sys.argv[2]), seconds=0.5))
broken = tiny.run(tiny.cell("decode", chips=4, limit=float(sys.argv[2]), seconds=0.5),
                  fault=left_out)
print("RESULT", sound["correct"], broken["correct"])
'''


def test_exchange_left_out_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    here = str(Path(__file__).resolve().parent)
    p = subprocess.run([sys.executable, "-c", EXCHANGE, here, str(LIMIT)],
                       env=env, capture_output=True, text=True, timeout=600)
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")]
    assert line, p.stderr[-3000:]
    assert line[0].split()[1:] == ["True", "False"]


def test_no_finished_request_is_not_correct():
    import check

    checks, failed = check.compare(
        tiny.cell("decode", limit=LIMIT),
        {"seqs": [], "rows": [], "targets": [], "n_requests": 0})
    assert checks["served_gap"]["value"] == float("inf") and failed == 0
