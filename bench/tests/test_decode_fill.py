"""The decode driver's cache fill and rewind on a model that carries
recurrent state: a tiny attention + Mamba ``ModelConfig`` of the program's
own, whose d_inner equals max_len, so that no cache entry can be told from
another by its shape."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import tiny
from repro.models import forward
from repro.models.config import ModelConfig
from repro.runtime.serve import init_sharded_cache

HYBRID = ModelConfig(name="tiny-hybrid", n_layers=4, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=500,
                     block_pattern=("attn", "mamba"), mamba_d_state=8)
MAX_LEN = HYBRID.mamba_expand * HYBRID.d_model        # == d_inner, 128


def _driver():
    return harness.load_module(harness.BENCH / "drivers" / "decode_closed.py")


@pytest.fixture
def hybrid(monkeypatch):
    """The decode driver, handed the hybrid model for the tiny cell."""
    d = _driver()
    monkeypatch.setattr(d, "arch", lambda cell: types.SimpleNamespace(
        program_config=lambda config: HYBRID))
    return d


def hybrid_cell(seed=2**31 + 5, seconds=0.5):
    c = tiny.cell("decode", seed=seed, seconds=seconds)
    c.traffic.update(max_len=MAX_LEN, prompt_len={
        "dist": "uniform", "lo": 8, "hi": 24})
    return c


def _lone(params, toks):
    """The caches of a lone forward over one sequence at its own length."""
    _, caches = jax.jit(lambda p, t: forward(p, HYBRID, t, collect_cache=True))(
        params, jnp.asarray(np.asarray(toks)[None], jnp.int32))
    return caches


def _assert_slot(kind, entry, lone, b, n):
    """Slot ``b`` of a cache entry holds what a lone forward over ``n``
    tokens left: K/V at its first ``n`` positions (nothing beyond), a state
    whole."""
    for name in entry:
        got = np.asarray(entry[name][:, b].astype(jnp.float32))
        want = np.asarray(lone[name][:, 0].astype(entry[name].dtype)
                          .astype(jnp.float32))
        if kind == "attn":
            np.testing.assert_array_equal(got[:, :n], want)
            assert not got[:, n:].any()
        else:
            np.testing.assert_array_equal(got, want)


def test_fill_writes_states_whole_and_kv_by_position(hybrid):
    c = hybrid_cell()
    B = c.config["decode_slots"]
    mesh, steps = harness.solve_and_build(c, HYBRID, "decode", MAX_LEN, B,
                                          MAX_LEN, True)
    params = harness.init_weights(c, HYBRID, mesh, steps)
    caches = init_sharded_cache(HYBRID, mesh, steps["cache_specs"], B, MAX_LEN)
    r = np.random.default_rng(7)
    prompts = {b: r.integers(0, HYBRID.vocab, n).astype(np.int32)
               for b, n in {0: 7, 1: 15, 2: 7, 3: 23}.items()}
    # one call per length; a short call repeats its slot, as set-up does
    for n, slots in [(7, [2, 0]), (15, [1, 1]), (23, [3, 3])]:
        fill = hybrid._fill_fn(HYBRID, mesh, steps["plan"], steps, n, 2)
        caches = fill(params, np.stack([prompts[b] for b in slots]),
                      np.array(slots, np.int32), caches)
    for b, p in prompts.items():
        for kind, entry, lone in zip(HYBRID.expanded_pattern, caches,
                                     _lone(params, p)):
            _assert_slot(kind, entry, lone, b, len(p))


def test_rewind_restores_states_and_repeats_the_first_step(hybrid):
    state = hybrid.setup(hybrid_cell())
    prompts, B = state["prompts"], len(state["prompts"])
    assert state["states"] == [1]
    assert len({len(p) for p in prompts}) > 1

    def states():
        return [np.asarray(x) for x in jax.tree.leaves(state["caches"][1])]

    snap = [np.asarray(x) for x in jax.tree.leaves(state["snap"])]
    # the snapshot is the fill's: a forward over all but the last token
    for b, p in enumerate(prompts):
        lone = _lone(state["params"], p[:-1])
        _assert_slot("mamba", state["snap"][0], lone[1], b, len(p) - 1)
    # set-up's warm steps fed the last token twice; it undid them
    for a, s in zip(states(), snap):
        np.testing.assert_array_equal(a, s)

    def step(tok, pos):
        logits, state["caches"] = state["decode"](
            state["params"], jax.device_put(tok[:, None], state["tok_sh"]),
            jax.device_put(pos, state["pos_sh"]), state["caches"])
        return np.asarray(logits)

    tok0, pos0 = hybrid._first_inputs(prompts)
    first = step(tok0, pos0)
    tok, pos = first[:, -1, :HYBRID.vocab].argmax(-1).astype(np.int32), pos0 + 1
    for _ in range(3):
        nxt = step(tok, pos)[:, -1, :HYBRID.vocab].argmax(-1).astype(np.int32)
        tok, pos = nxt, pos + 1
    mask = np.arange(B) % 2 == 0
    hybrid._rewind(state, np.flatnonzero(mask))
    for a, s in zip(states(), snap):
        np.testing.assert_array_equal(a[:, mask], s[:, mask])
        assert (a[:, ~mask] != s[:, ~mask]).any()
    again = step(np.where(mask, tok0, tok), np.where(mask, pos0, pos))
    np.testing.assert_array_equal(again[mask], first[mask])



def test_restore_writes_one_slot_in_place(hybrid):
    """A rewind costs the rows of the slot it restores: the restore
    program updates one slot of each state leaf in place and selects
    nothing across the batch."""
    c = hybrid_cell()
    B = c.config["decode_slots"]
    mesh, steps = harness.solve_and_build(c, HYBRID, "decode", MAX_LEN, B,
                                          MAX_LEN, True)
    caches = init_sharded_cache(HYBRID, mesh, steps["cache_specs"], B, MAX_LEN)
    entries = (caches[1],)
    text = hybrid._restore_fn(mesh, steps, [1]).lower(
        entries, entries, jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    leaves = jax.tree.leaves(entries)
    assert text.count("stablehlo.dynamic_update_slice") == len(leaves)
    assert [line for line in text.splitlines() if "stablehlo.select" in line
            and "tensor<i32>" not in line] == []

@pytest.mark.parametrize("model", ["granite", "hybrid"])
def test_requests_over_one_prompt_serve_one_sequence(model, monkeypatch):
    """Every request of a slot starts over the same prompt, so greedy
    decoding serves the same tokens again: requests after a rewind agree
    with the slot's first on every token both served."""
    d = _driver()
    if model == "hybrid":
        monkeypatch.setattr(d, "arch", lambda cell: types.SimpleNamespace(
            program_config=lambda config: HYBRID))
        c = hybrid_cell(seconds=1.0)
    else:
        c = tiny.cell("decode", seconds=1.0)
    state = d.setup(c)
    win = d.window(c, state)
    first, rewound = {}, 0
    for b, out, after in win["finished"]:
        ref = first.setdefault(b, out)
        n = min(len(ref), len(out))
        assert out[:n] == ref[:n], (b, after)
        rewound += after
    assert rewound > 0


def test_a_cache_of_kv_alone_keeps_its_one_fill():
    """Granite's cache has no state entries: whole prompts padded to one
    length, no snapshot and no restore program."""
    d = _driver()
    prompts = [np.zeros(n, np.int32) for n in (9, 20, 9, 13)]
    assert d._fill_calls(prompts, 2, 128) == [(128, [0, 1]), (128, [2, 3])]
    assert d._fill_calls(prompts, 2, None) == [(8, [0, 2]), (12, [3]),
                                               (19, [1])]
    state = d.setup(tiny.cell("decode"))
    assert state["states"] == [] and state["snap"] == ()
    assert state["restore"] is None

