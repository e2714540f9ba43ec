"""Solver-facade tests: the one front door (repro.scope).

* facade-vs-legacy bit-identical parity: ``solve()`` against direct
  ``search`` / ``search_mixed`` / ``co_schedule`` calls on the
  resnet18/resnet50 x mcm16/mcm64_hetero matrix, both RegionModes
  (facade and legacy share one engine memo -- memoization is exact, so
  sharing changes nothing but wall time);
* strategy auto-selection by problem shape + registry behavior;
* Deployment round-trip: solve -> deploy == plan_for_multimodel, without
  a second search;
* the ``python -m repro solve`` CLI (JSON payload parity).
"""
import json

import pytest

from repro import scope
from repro.core.costmodel import INF
from repro.core.fastcost import FastCostModel
from repro.core.hw import get_hw, mcm_table_iii
from repro.core.regions import RegionMode
from repro.core.search import search, search_mixed
from repro.core.workloads import get_cnn
from repro.multimodel import co_schedule, parse_mix


def _shared(hw, m_samples=16):
    return FastCostModel(hw, m_samples=m_samples)


def _facade(net, hw, cost, mode, **opts):
    return scope.solve(scope.problem(
        net, hw, mode=mode, cost=cost, **opts
    ))


def _assert_same_schedule(sol, legacy):
    assert legacy is not None and sol.feasible
    assert sol.latency == legacy.latency          # bit-identical
    assert len(sol.schedule.segments) == len(legacy.segments)
    for a, b in zip(sol.schedule.segments, legacy.segments):
        assert a.clusters == b.clusters
        assert a.cluster_times == b.cluster_times


# ---------------------------------------------------------------- parity

PARITY_FAST = [
    ("resnet18", "mcm16", "free"),
    ("resnet18", "mcm16", "uniform"),
    ("resnet50", "mcm16", "free"),
    ("resnet50", "mcm16", "uniform"),
    ("resnet18", "mcm64_hetero", "free"),
    ("resnet18", "mcm64_hetero", "uniform"),
    ("resnet50", "mcm64_hetero", "uniform"),
]
PARITY_SLOW = [
    ("resnet50", "mcm64_hetero", "free"),
]


def _check_parity(net, hw_name, mode):
    hw = get_hw(hw_name)
    cost = _shared(hw)
    g = get_cnn(net)
    sol = _facade(net, hw, cost, mode)
    if hw.region_types:
        assert sol.strategy == "scope-mixed"
        legacy = search_mixed(g, cost, mode=RegionMode(mode))
    else:
        assert sol.strategy == "scope"
        legacy = search(g, cost, hw.chips, mode=RegionMode(mode))
    _assert_same_schedule(sol, legacy)


@pytest.mark.parametrize("net,hw_name,mode", PARITY_FAST)
def test_solve_matches_legacy(net, hw_name, mode):
    _check_parity(net, hw_name, mode)


@pytest.mark.slow
@pytest.mark.parametrize("net,hw_name,mode", PARITY_SLOW)
def test_solve_matches_legacy_slow(net, hw_name, mode):
    _check_parity(net, hw_name, mode)


def test_solve_matches_co_schedule_homogeneous():
    hw = get_hw("mcm16")
    cost = _shared(hw)
    specs = parse_mix("resnet18:1,resnet50:1")
    sol = scope.solve(scope.problem("resnet18:1,resnet50:1", hw, cost=cost))
    legacy = co_schedule(specs, hw, cost=cost)
    assert sol.strategy == "coschedule"
    assert sol.multi.mode == legacy.mode
    assert sol.multi.mix_rate == legacy.mix_rate
    assert sol.weighted_throughput == legacy.weighted_throughput
    assert [a.chips for a in sol.multi.assignments] == [
        a.chips for a in legacy.assignments
    ]


@pytest.mark.slow
def test_solve_matches_co_schedule_hetero():
    hw = get_hw("mcm64_hetero")
    cost = _shared(hw)
    specs = parse_mix("resnet18:1,resnet50:1")
    opts = dict(step=4, mixed_step=16)
    sol = scope.solve(scope.problem(
        "resnet18:1,resnet50:1", hw, cost=cost, **opts
    ))
    legacy = co_schedule(specs, hw, cost=cost, **opts)
    assert sol.multi.mode == legacy.mode
    assert sol.weighted_throughput == legacy.weighted_throughput
    assert [(a.chips, a.chip_type, a.chip_quota)
            for a in sol.multi.assignments] == [
        (a.chips, a.chip_type, a.chip_quota) for a in legacy.assignments
    ]


def test_exhaustive_and_random_strategies():
    from repro.core.graph import chain
    from repro.core.search import exhaustive_search, random_search

    g = chain("alexnet[:4]", get_cnn("alexnet").layers[:4])
    hw = mcm_table_iii(16).with_chips(6)
    cost = _shared(hw)
    best = scope.solve(scope.problem(
        scope.WorkloadSpec.graphs([g]), hw,
        options=scope.SearchOptions(strategy="exhaustive", cost=cost),
    ))
    lat, _, _, _ = next(exhaustive_search(cost, g, 6))
    assert best.latency == lat
    rand = scope.solve(scope.problem(
        scope.WorkloadSpec.graphs([g]), hw,
        options=scope.SearchOptions(strategy="random", cost=cost,
                                    samples=200, seed=3),
    ))
    legacy_pop = random_search(cost, g, 6, samples=200, seed=3)
    assert rand.diagnostics["population"] == legacy_pop
    # the exhaustive optimum lower-bounds everything sampled, and
    # Algorithm 1 lands near it (paper Fig. 8 narrative)
    assert best.latency <= min(legacy_pop) + 1e-15
    alg1 = scope.solve(scope.problem(
        scope.WorkloadSpec.graphs([g]), hw,
        options=scope.SearchOptions(strategy="scope", cost=cost),
    ))
    assert best.latency <= alg1.latency <= 1.25 * best.latency


def test_baseline_strategies_match_legacy():
    from repro.core.baselines import ALL_METHODS

    hw = get_hw("mcm16")
    cost = _shared(hw)
    for method in ("sequential", "segmented", "scope"):
        sol = _facade("alexnet", hw, cost, "free", strategy=method)
        legacy = ALL_METHODS[method](get_cnn("alexnet"), cost, 16)
        assert sol.latency == legacy.latency, method


# ------------------------------------------------------- auto-selection

class TestAutoSelection:
    def test_single_model_single_flavor(self):
        sol = scope.solve(workload="alexnet", package="mcm16")
        assert sol.strategy == "scope"

    def test_single_model_many_flavors(self):
        sol = scope.solve(workload="alexnet", package="mcm16_hetero")
        assert sol.strategy == "scope-mixed"

    def test_single_model_many_flavors_mixed_off(self):
        sol = scope.solve(workload="alexnet", package="mcm16_hetero",
                          mixed=False)
        assert sol.strategy == "scope"
        assert set(sol.diagnostics["per_flavor"]) == {"big", "little"}

    def test_multi_model(self):
        sol = scope.solve(workload="alexnet:1,resnet18:1", package="mcm16")
        assert sol.strategy == "coschedule"

    def test_explicit_strategy_wins(self):
        sol = scope.solve(workload="alexnet:1,resnet18:1", package="mcm16",
                          strategy="time-mux")
        assert sol.strategy == "time-mux"
        assert sol.multi.mode == "time_mux"

    def test_unknown_strategy_lists_available(self):
        with pytest.raises(KeyError, match="coschedule"):
            scope.solve(workload="alexnet", package="mcm16",
                        strategy="nonesuch")

    def test_register_strategy(self):
        from repro.api import _STRATEGIES

        @scope.register_strategy("everything-is-42")
        def _fake(prob, hw, cost):
            return scope.Solution(problem=prob, strategy="everything-is-42",
                                  hw=hw, diagnostics={"answer": 42})

        try:
            sol = scope.solve(workload="alexnet", package="mcm16",
                              strategy="everything-is-42")
            assert sol.diagnostics["answer"] == 42
        finally:
            _STRATEGIES.pop("everything-is-42")


# ----------------------------------------------------- problem plumbing

class TestProblemModel:
    def test_flavor_caps_restrict_budgets(self):
        hw = get_hw("mcm16_hetero")
        cost = _shared(hw)
        prob = scope.Problem(
            workload=scope.WorkloadSpec.cnn("alexnet"),
            package=scope.PackageSpec(hw=hw,
                                      flavor_caps=(("big", 4), ("little", 4))),
            options=scope.SearchOptions(cost=cost),
        )
        sol = scope.solve(prob)
        legacy = search_mixed(get_cnn("alexnet"), cost,
                              flavor_budgets=[("big", 4), ("little", 4)])
        _assert_same_schedule(sol, legacy)

    def test_seam_override_changes_result_model(self):
        base = scope.PackageSpec.of("mcm16_hetero").resolve()
        derated = scope.PackageSpec(
            preset="mcm16_hetero", seam_bw_scale=0.25
        ).resolve()
        assert derated.seam_link_bw("big", "little") == (
            0.25 * base.seam_link_bw("big", "little")
        )

    def test_workload_coercions(self):
        assert scope.WorkloadSpec.of("alexnet").n_models == 1
        assert scope.WorkloadSpec.of("alexnet:2,resnet18:1").n_models == 2
        g = get_cnn("alexnet")
        assert scope.WorkloadSpec.of(g).graph is g
        assert scope.WorkloadSpec.of([(g, 2.0)]).models[0].weight == 2.0
        with pytest.raises(ValueError):
            scope.problem("alexnet", "mcm16", options=scope.SearchOptions(),
                          step=2)

    def test_m_samples_flows_to_throughput(self):
        sol = scope.solve(workload="alexnet", package="mcm16", m_samples=32)
        assert sol.throughput == 32 / sol.latency

    def test_shared_cost_on_wrong_hardware_rejected(self):
        cost = _shared(mcm_table_iii(16))
        with pytest.raises(ValueError, match="wrong hardware"):
            scope.solve(workload="alexnet", package="mcm64", cost=cost)


# ------------------------------------------------------------ deployment

class TestDeployment:
    @pytest.fixture(scope="class")
    def lm_setup(self):
        from dataclasses import replace

        from repro.configs import get_smoke_config
        from repro.core.hw import ChipType, tpu_v5e

        cfgs = (get_smoke_config("granite-3-8b"),
                get_smoke_config("granite-20b"))
        hw = replace(
            tpu_v5e(8, (1, 8)),
            name="tpu_v5e_8_hetero",
            region_types=(
                ChipType("big", 4),
                ChipType("little", 4, flops_scale=0.5, nop_bw_scale=0.75),
            ),
        )
        return cfgs, hw

    def test_roundtrip_matches_planner(self, lm_setup):
        from repro.runtime.planner import plan_for_multimodel

        cfgs, hw = lm_setup
        wl = scope.WorkloadSpec.lm(cfgs, seq_len=64, weights=[2.0, 1.0])
        sol = scope.solve(scope.problem(
            wl, hw, m_samples=8, include_merged=False,
        ))
        assert sol.strategy == "coschedule" and sol.feasible
        dep = sol.deploy(global_batch=8, mesh_axes=("data", "model"))
        # deploy reuses the already-solved co-schedule: no second search
        assert dep.multi is sol.multi
        mm, plans = plan_for_multimodel(
            list(cfgs), 64, 8, ("data", "model"), model_axis=8,
            weights=[2.0, 1.0], hw=hw,
        )
        assert set(dep.plans) == set(plans)
        for name, direct in plans.items():
            p = dep.plans[name]
            assert (p.p1, p.p2, p.transition_repeat) == (
                direct.p1, direct.p2, direct.transition_repeat
            )
            assert p.stage_chip_types == direct.stage_chip_types
            assert p.meta["quota_chips"] == direct.meta["quota_chips"]
            assert p.meta["co_mode"] == direct.meta["co_mode"]

    def test_merged_mode_not_reused_for_plans(self, lm_setup):
        """A merged-mode co-schedule spans the concatenated graph and has
        no per-model execution path: deploy must re-plan (merged excluded)
        instead of deriving per-model ShardPlans from it."""
        from dataclasses import replace

        cfgs, hw = lm_setup
        wl = scope.WorkloadSpec.lm(cfgs, seq_len=64, weights=[2.0, 1.0])
        sol = scope.solve(scope.problem(
            wl, hw, m_samples=8, include_merged=False,
        ))
        sol.multi = replace(sol.multi, mode="merged")
        dep = sol.deploy(global_batch=8)
        assert dep.multi is not sol.multi
        assert dep.multi.mode != "merged"
        assert set(dep.plans) == {c.name for c in cfgs}

    def test_single_cfg_uses_plan_for_cell(self, lm_setup):
        cfgs, _ = lm_setup
        from repro.core.hw import tpu_v5e

        wl = scope.WorkloadSpec.lm(cfgs[:1], seq_len=64)
        sol = scope.solve(scope.problem(wl, tpu_v5e(8, (1, 8)), m_samples=8))
        dep = sol.deploy(global_batch=8)
        plan = dep.plans[cfgs[0].name]
        assert plan.meta["kind"] == "train" and plan.meta["dse"] is True

    def test_deploy_without_cfgs_raises(self):
        sol = scope.solve(workload="alexnet", package="mcm16")
        with pytest.raises(ValueError, match="ModelConfigs"):
            sol.deploy(global_batch=8)

    def test_mesh_must_match_solved_package(self, lm_setup):
        """Plans are derived for the solved package's chip count: a mesh
        of any other size is refused, and so is a package larger than the
        visible devices."""
        from repro.core.hw import tpu_v5e
        from repro.launch.mesh import single_device_mesh

        cfgs, _ = lm_setup
        wl = scope.WorkloadSpec.lm(cfgs[:1], seq_len=64)
        dep = scope.solve(scope.problem(wl, tpu_v5e(8, (1, 8)),
                                        m_samples=8)).deploy(global_batch=8)
        assert dep.chips == 8
        with pytest.raises(ValueError, match="solved for 8 chips"):
            dep.build_steps(single_device_mesh())
        with pytest.raises(ValueError, match="solved for 8 chips"):
            dep.make_mesh()

    def test_sharded_init_serves_through_deploy(self, lm_setup):
        """solve -> deploy -> make_mesh -> build_steps on one device, with
        weights and cache created in their shards by one jitted program each;
        the jitted weights are the eager ``init_params`` ones (to rounding
        of the init scale constants)."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from repro.core.hw import tpu_v5e
        from repro.models import init_params
        from repro.runtime.serve import init_sharded_cache, init_sharded_params
        from repro.runtime.sharding import to_shardings

        cfg = lm_setup[0][0]
        dep = scope.solve(scope.problem(scope.WorkloadSpec.lm([cfg], seq_len=16),
                                        tpu_v5e(1, (1, 1)),
                                        m_samples=8)).deploy(global_batch=2)
        mesh = dep.make_mesh()
        assert dict(mesh.shape) == {"data": 1, "model": 1}
        steps = dep.build_steps(mesh, batch=2, max_len=24)[cfg.name]
        key = jax.random.PRNGKey(0)
        params = init_sharded_params(cfg, mesh, steps["param_specs"], key)
        want = to_shardings(mesh, steps["param_specs"])
        for leaf, sh in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
            assert leaf.sharding.is_equivalent_to(sh, leaf.ndim)
        for a, b in zip(jax.tree.leaves(params),
                        jax.tree.leaves(init_params(cfg, key))):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
        caches = init_sharded_cache(cfg, mesh, steps["cache_specs"], 2, 24,
                                    jnp.float32)
        toks = jnp.ones((2, 16), jnp.int32)
        logits = steps["prefill"](params, toks)
        step_logits, _ = steps["decode"](params, toks[:, :1],
                                         jnp.zeros((2,), jnp.int32), caches)
        np.testing.assert_allclose(np.asarray(step_logits[:, 0]),
                                   np.asarray(logits[:, 0]),
                                   rtol=1e-4, atol=1e-4)


class TestCompileCache:
    """Entry points keep JAX's compile cache at one fixed place."""

    def test_environment_variable_is_left_to_jax(self, monkeypatch, tmp_path):
        import jax

        from repro.launch.compile_cache import use_compile_cache

        before = (jax.config.jax_compilation_cache_dir,
                  jax.config.jax_persistent_cache_min_compile_time_secs)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert (jax.config.jax_compilation_cache_dir,
                jax.config.jax_persistent_cache_min_compile_time_secs) == before

    def test_default_is_checkout_dot_jax_cache(self, monkeypatch):
        from pathlib import Path

        import jax

        import repro
        from repro.launch.compile_cache import use_compile_cache

        before = (jax.config.jax_compilation_cache_dir,
                  jax.config.jax_persistent_cache_min_compile_time_secs)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            path = use_compile_cache()
            assert jax.config.jax_compilation_cache_dir == path
            assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        finally:
            jax.config.update("jax_compilation_cache_dir", before[0])
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              before[1])
        checkout = Path(repro.__file__).resolve().parents[2]
        assert path == str(checkout / ".jax_cache")


# ------------------------------------------------------------------- CLI

class TestCLI:
    def test_solve_json_parity(self, capsys):
        from repro.__main__ import main

        main(["solve", "--mix", "alexnet", "--hw", "mcm16", "--json"])
        out = json.loads(capsys.readouterr().out)
        assert out["strategy"] == "scope" and out["feasible"]
        legacy = search(get_cnn("alexnet"),
                        _shared(mcm_table_iii(16)), 16)
        assert out["latency_s"] == legacy.latency
        assert out["seam_crossings"] == 0

    def test_solve_multimodel_text(self, capsys):
        from repro.__main__ import main

        main(["solve", "--mix", "alexnet:1,resnet18:1", "--hw", "mcm16",
              "--baselines"])
        out = capsys.readouterr().out
        assert "2 models" in out and "equal-split" in out

    def test_legacy_cli_shim(self, capsys):
        from repro.multimodel.cli import main

        main(["--mix", "alexnet:1,resnet18:1", "--hw", "mcm16"])
        assert "2 models" in capsys.readouterr().out

    def test_strategies_command(self, capsys):
        from repro.__main__ import main

        main(["strategies"])
        out = capsys.readouterr().out.split()
        assert "scope" in out and "coschedule" in out


# ------------------------------------------------------------ validation

class TestSolutionValidation:
    def test_seam_crossings_reported(self):
        sol = scope.solve(workload="resnet18", package="mcm16_hetero")
        assert sol.strategy == "scope-mixed"
        assert "seam_crossings" in sol.diagnostics
        crossings = sol.diagnostics["seam_crossings"]
        flavors = {cl.chip_type for seg in sol.schedule.segments
                   for cl in seg.clusters}
        if len(flavors) == 1:
            assert crossings == 0
        else:
            assert crossings >= 1

    def test_verify_reference_parity(self):
        sol = scope.solve(workload="alexnet", package="mcm16_hetero")
        ref = sol.verify_reference()
        assert ref == pytest.approx(sol.latency, rel=1e-9)

    def test_infeasible_solution(self):
        # full_pipeline is invalid when L > chips
        sol = scope.solve(workload="resnet50", package="mcm16",
                          strategy="full_pipeline")
        assert not sol.feasible
        assert sol.throughput == 0.0


class TestWarmStart:
    """options.warm_start: interactive re-solves seeded by an incumbent."""

    def test_coschedule_drift_refinement(self):
        prob = scope.problem("alexnet:1,resnet18:1", "mcm16", m_samples=16)
        sol = scope.solve(prob)
        drifted = scope.problem("alexnet:3,resnet18:1", "mcm16", m_samples=16)
        cold = scope.solve(drifted)
        warm = scope.solve(drifted.with_options(warm_start=sol))
        assert warm.feasible
        assert warm.multi.meta.get("warm_start") is True
        assert cold.multi.meta.get("warm_start") is False
        # a local refinement, not a cold-quality regression
        assert warm.weighted_throughput >= 0.9 * cold.weighted_throughput

    def test_single_model_warm_matches_cold(self):
        prob = scope.problem("resnet18", "mcm16", m_samples=16)
        cold = scope.solve(prob)
        warm = scope.solve(prob.with_options(warm_start=cold))
        # the window contains the incumbent's segment count, and the sweep
        # is deterministic: the warm solve lands on the same schedule
        assert warm.schedule.latency == cold.schedule.latency
        assert warm.schedule.segments == cold.schedule.segments

    def test_warm_rejected_when_incumbent_does_not_fit(self):
        big = scope.solve(scope.problem(
            "alexnet:1,resnet18:1", "mcm64", m_samples=16))
        small = scope.problem("alexnet:1,resnet18:1", "mcm16", m_samples=16)
        warm = scope.solve(small.with_options(warm_start=big))
        # the 64-chip incumbent cannot anchor a 16-chip package: the solve
        # must fall back to the full (cold) search
        assert warm.feasible
        assert warm.multi.meta.get("warm_start") is False
        cold = scope.solve(small)
        assert warm.weighted_throughput == cold.weighted_throughput

    def test_warm_start_excluded_from_fingerprint(self):
        prob = scope.problem("alexnet:1,resnet18:1", "mcm16", m_samples=16)
        sol = scope.solve(prob)
        fp_cold = scope.problem_fingerprint(prob)
        fp_warm = scope.problem_fingerprint(prob.with_options(warm_start=sol))
        assert fp_cold == fp_warm
