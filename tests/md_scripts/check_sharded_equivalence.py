import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
# Multi-device CPU test worker: numeric equivalence of sharded vs single-
# device execution, and collective-pattern assertions (Table II analogue).
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))

from repro.configs import get_smoke_config           # noqa: E402
from repro.launch.mesh import make_mesh              # noqa: E402
from repro.launch.hlo_analysis import collective_stats  # noqa: E402
from repro.models import init_params, loss_fn        # noqa: E402
from repro.runtime.sharding import ShardPlan, make_constrain  # noqa: E402


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = get_smoke_config("granite-3-8b")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 4, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)

    # single-device reference
    ref = float(loss_fn(params, cfg, toks, labels))

    results = {}
    for name, plan in {
        "isp": ShardPlan(("data", "model"), p1="ISP", p2="ISP"),
        "wsp": ShardPlan(("data", "model"), p1="WSP", p2="WSP"),
        "mixed": ShardPlan(("data", "model"), p1="WSP", p2="ISP", transition_repeat=1),
    }.items():
        c1 = make_constrain(mesh, plan, 1)
        c2 = make_constrain(mesh, plan, 2)
        fn = jax.jit(lambda p, t, l: loss_fn(
            p, cfg, t, l, constrain=c1, constrain2=c2,
            transition_repeat=plan.transition_repeat,
        ))
        with jax.set_mesh(mesh):
            loss = float(fn(params, toks, labels))
            hlo = fn.lower(params, toks, labels).compile().as_text()
        stats = collective_stats(hlo)
        results[name] = (loss, stats.total_bytes, dict(stats.count_by_kind))
        assert abs(loss - ref) < 5e-3, (name, loss, ref)

    # WSP (sequence-sharded) must communicate differently than ISP
    assert results["isp"][1] > 0, "ISP plan produced no collectives"
    assert results["wsp"][1] > 0, "WSP plan produced no collectives"
    print("OK", ref, {k: (round(v[0], 4), v[1]) for k, v in results.items()})


if __name__ == "__main__":
    main()
