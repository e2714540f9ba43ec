"""A cell at a size the CPU holds, for the tests: Granite's layout at toy
widths, and both mixes shrunk to match."""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
CHECKOUT = BENCH.parent
for p in (str(BENCH), str(CHECKOUT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402


def config(layers: int = 2, chips: int = 1) -> dict:
    doc = harness.load_json(BENCH / "configs" / "granite-3-8b-l20.json")
    doc = copy.deepcopy(doc)
    doc["name"] = "tiny"
    doc["chips"] = chips
    doc["package"] = {"preset": "tpu_v5e", "chips": chips, "mesh": [1, chips]}
    doc["decode_slots"] = 4 * chips
    doc["config"].update(hidden_size=128, num_attention_heads=4,
                         num_key_value_heads=2, intermediate_size=256,
                         vocab_size=500, num_hidden_layers=layers,
                         attention_multiplier=32 ** -0.5)
    return doc


def traffic(kind: str) -> dict:
    doc = harness.load_json(BENCH / "traffic" / f"{kind}.json")
    if kind == "decode":
        doc.update(max_len=64, prompt_len={"dist": "uniform", "lo": 8, "hi": 24},
                   output_len={"dist": "uniform", "lo": 4, "hi": 12}, pool=8)
    else:
        doc.update(prompt_len={"dist": "lognormal", "median": 16, "sigma": 0.7,
                               "lo": 4, "hi": 64},
                   buckets=[[16, 4], [32, 2], [64, 1]], pool=16,
                   plan_seq_len=16, plan_batch=4, sample_requests=8)
    return doc


def cell(kind: str, seed: int = 2**31 + 17, seconds: float = 1.0,
         chips: int = 1, limit: float = 0.05, trace: bool = False):
    return harness.Cell(
        workload={"name": f"tiny.{kind}", "config": "tiny", "traffic": kind,
                  "chips": chips},
        config=config(chips=chips), traffic=traffic(kind),
        limits={"served_gap": {"limit": limit}}, seed=seed, seconds=seconds,
        trace=trace, t_start=time.perf_counter())


def bench() -> dict:
    return json.loads((CHECKOUT / "BENCHMARK.json").read_text())


def run(c, fault=None) -> dict:
    import jax

    c.workload["name"] = f"granite-3-8b-l20.{c.workload['traffic']}"
    return harness.run(c, bench(), jax.devices(), fault=fault)
