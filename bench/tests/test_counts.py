"""FLOP and byte counts against values worked by hand from the shapes,
through the counts of the configuration's architecture (``work`` of
``bench/archs/granite.py``, found by ``harness.arch``)."""
import json
from pathlib import Path

import pytest

import harness
from readers import least_time

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def granite():
    cell = harness.Cell.find(json.loads((harness.CHECKOUT / "BENCHMARK.json")
                                        .read_text()),
                             "granite-3-8b-l20.decode", seed=0, seconds=1.0,
                             trace=False, t_start=0.0)
    return harness.arch(cell), cell.cfg


def shapes(name):
    G, _ = granite()
    return G.Shapes.of(json.loads((CONFIGS / f"{name}.json").read_text())["config"])


def decode(positions):
    G, cfg = granite()
    (fb,) = G.work(cfg, {"kind": "decode", "step_pos": [positions]})
    return fb


def prefill(lengths):
    G, cfg = granite()
    (fb,) = G.work(cfg, {"kind": "prefill", "calls": [(0, lengths)]})
    return fb


def test_l20_parameters():
    s = shapes("granite-3-8b-l20")
    # attention 4096*4096 + 2*4096*1024 + 4096*4096 = 41,943,040
    # gated FFN 3*4096*12800 = 157,286,400
    assert s.attn_params == 41_943_040
    assert s.ffn_params == 157_286_400
    assert s.block_params == 20 * 199_229_440 == 3_984_588_800
    assert s.head_params == 4096 * 49155 == 201_338_880
    assert s.kv_bytes_per_position == 20 * 2 * 8 * 128 * 2 == 81_920
    # bf16 matrices + float32 norm scales (2 per layer + final)
    assert s.weight_bytes == (3_984_588_800 + 201_338_880) * 2 + 41 * 4096 * 4


def test_published_depth_parameters():
    G, _ = granite()
    cfg = json.loads((CONFIGS / "granite-3-8b-l20.json").read_text())["published"]
    s = G.Shapes.of(cfg)
    assert s.block_params == 40 * 199_229_440 == 7_969_177_600
    assert 2 * s.block_params == pytest.approx(2 * 7.97e9, rel=1e-3)


def test_decode_step_one_key():
    f, b = decode([0])
    # 2 * (blocks + head) + attention 20 layers * 4 * 32 * 128 * 1 key
    assert f == 2 * (3_984_588_800 + 201_338_880) + 327_680 == 8_372_183_040
    # weights + embedding row + K/V read (1 key) + K/V written + f32 logits
    assert b == 8_372_527_104 + 8_192 + 81_920 + 81_920 + 49155 * 4


def test_decode_step_scales_with_live_keys():
    f1, b1 = decode([99, 199])      # 100 + 200 keys
    f0, b0 = decode([0, 0])         # 1 + 1 keys
    assert f1 - f0 == 327_680 * 298
    assert b1 - b0 == 81_920 * 298


def test_prefill_causal_and_last_position_head():
    f, b = prefill([3])
    # 3 tokens through the blocks, 1 + 2 + 3 = 6 causal keys, head once
    assert f == 3 * 7_969_177_600 + 327_680 * 6 + 2 * 201_338_880 == 24_312_176_640
    assert b == 8_372_527_104 + 3 * 8_192 + 3 * 81_920 + 49155 * 4


def test_roofline_bound():
    t, which = least_time(197e12, 819e9 / 2, 197e12, 819e9)
    assert (t, which) == (1.0, "compute")
    t, which = least_time(1.0, 819e9, 197e12, 819e9)
    assert (t, which) == (1.0, "memory")
