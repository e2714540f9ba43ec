"""The comparison that decides ``correct``.

A served token is right to the extent that the plain reference of its
architecture (``forward_rows`` of ``bench/archs/<model_type>.py``), run
over the request's prompt and the tokens served before it, ranks it
first.  The number compared is the widest gap, over a sample of the
window's finished requests, by which a served token's reference logit
lies below the reference's best logit at that position (0 where the two
agree).  Its limit is in ``bench/limits/<workload>.json``, with the
readings it was set from: the largest over sound runs of the program
(``lower``) and the smallest of the fp8 control (``upper``, see
``control.py``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from gen import seed_key
from harness import arch

GAP = "served_gap"


@jax.jit
def served_gaps(ref_logits, served):
    """How far below the reference's best logit each served token lies."""
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
    return best - got


@jax.jit
def control_gaps(ref_logits, low_logits):
    """The same gap for the token that the low-precision run puts first."""
    return served_gaps(ref_logits, jnp.argmax(low_logits, -1).astype(jnp.int32))


def reference_gaps(cell, sample, controls=()) -> dict:
    """{"served": gaps of the served tokens} and, for each precision in
    ``controls``, the gaps of the tokens that the reference computed in it
    ranks first at the same positions."""
    if not sample["rows"]:
        return {"served": np.array([])} | {q: np.array([]) for q in controls}
    forward_rows = arch(cell).forward_rows
    key = seed_key(cell.seed)
    ref = forward_rows(cell.cfg, key, sample["seqs"], sample["rows"])
    targets = np.asarray(sample["targets"], np.int64)
    vocab = cell.cfg["vocab_size"]
    ok = (targets >= 0) & (targets < vocab)
    served = np.asarray(served_gaps(
        ref, jnp.asarray(np.where(ok, targets, 0), jnp.int32)))
    served = np.where(ok, served, np.inf)
    out = {"served": served}
    for q in controls:
        low = forward_rows(cell.cfg, key, sample["seqs"], sample["rows"],
                           quant=q)
        out[q] = np.asarray(control_gaps(ref, low))
        del low
    return out


def widest(gaps) -> float:
    g = float(np.max(gaps)) if len(gaps) else math.inf
    return math.inf if math.isnan(g) else g


def compare(cell, sample) -> tuple[dict, int]:
    """({number: {"value", "limit"}}, requests of the sample that fail)."""
    gaps = reference_gaps(cell, sample)["served"]
    limit = cell.limits[GAP]["limit"]
    bad = {r for (r, _), g in zip(sample["rows"], gaps) if not g <= limit}
    return {GAP: {"value": widest(gaps), "limit": limit}}, len(bad)
