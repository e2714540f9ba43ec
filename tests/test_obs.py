"""Scope Observatory (repro.obs): tracer, metrics, export, and determinism.

Covers the tentpole contracts:

* the disabled path is near-zero overhead (micro-benched bound on the
  no-op singletons),
* wall-clock spans nest by construction and export valid Chrome trace
  JSON (property-tested against :func:`validate_chrome_trace`),
* executor traces on the simulated clock are bytewise identical across
  two same-seed runs (faults included),
* the metrics registry's time-weighted series reproduce the serving
  report's queue statistics, and
* both evaluation engines report one counter schema.
"""
from __future__ import annotations

import json
import time

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import scope
from repro.api import problem_fingerprint
from repro.obs import (
    NULL_METRICS,
    NULL_TRACER,
    Histogram,
    MetricsRegistry,
    TimeSeries,
    Tracer,
    current_tracer,
    use_tracer,
    validate_chrome_trace,
)

M = 16          # m_samples everywhere: small and fast


# ---------------------------------------------------------------------------
# Metrics primitives
# ---------------------------------------------------------------------------

class TestTimeSeries:
    def test_mean_is_time_weighted(self):
        ts = TimeSeries()
        ts.extend([(1.0, 2), (3.0, 4)])
        # [0,1): 0, [1,3): 2, [3,5): 4 over t_end=5 -> (0+4+8)/5
        assert ts.mean(5.0) == pytest.approx((0 * 1 + 2 * 2 + 4 * 2) / 5.0)

    def test_implicit_zero_before_first_point(self):
        ts = TimeSeries()
        ts.record(4.0, 10)
        assert ts.mean(5.0) == pytest.approx(10 * 1.0 / 5.0)
        assert ts.percentile(50, 5.0) == 0.0        # zero holds 80% of time

    def test_percentile_bounds_and_max(self):
        ts = TimeSeries()
        ts.extend([(0.0, 1), (1.0, 5), (1.5, 2)])
        t_end = 2.0
        p95 = ts.percentile(95, t_end)
        assert 0 <= ts.percentile(5, t_end) <= p95 <= ts.max == 5

    def test_same_timestamp_dedups_to_last_value(self):
        ts = TimeSeries()
        ts.record(1.0, 3)
        ts.record(1.0, 7)
        assert ts.points == [(1.0, 7)]

    def test_empty_series(self):
        ts = TimeSeries()
        assert ts.mean(10.0) == 0.0
        assert ts.percentile(95, 10.0) == 0.0
        assert ts.max == 0

    @given(st.lists(st.tuples(st.floats(min_value=0.0, max_value=9.0),
                              st.integers(min_value=0, max_value=50)),
                    min_size=1, max_size=20))
    @settings(max_examples=50, deadline=None)
    def test_mean_never_exceeds_peak(self, pairs):
        pairs = sorted(pairs)
        ts = TimeSeries()
        ts.extend(pairs)
        t_end = 10.0
        assert 0.0 <= ts.mean(t_end) <= ts.max + 1e-12
        assert 0 <= ts.percentile(95, t_end) <= ts.max

    def test_queue_stats_parity_with_serving_report(self):
        """report.metrics time-weighted queue series == ModelMetrics scalars."""
        sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M))
        rep = sol.serve(n_requests=600, seed=0)
        for m, mm in rep.per_model.items():
            series = rep.metrics.series[f"queue_depth/{m}"]
            assert mm.queue_mean == pytest.approx(series.mean(rep.makespan_s))
            assert mm.queue_max == series.max
            assert mm.queue_p95 == series.percentile(95, rep.makespan_s)
            assert 0 <= mm.queue_p95 <= mm.queue_max


class TestRegistry:
    def test_instruments_create_on_first_use(self):
        reg = MetricsRegistry()
        reg.counter("a").inc(3)
        reg.counter("a").inc()
        reg.gauge("g").set(2.5)
        reg.histogram("h").observe(1.0)
        reg.timeseries("s").record(0.0, 1)
        snap = reg.snapshot(t_end=2.0)
        assert snap["counters"] == {"a": 4}
        assert snap["gauges"] == {"g": 2.5}
        assert snap["histograms"]["h"]["count"] == 1
        assert snap["series"]["s"]["mean"] == pytest.approx(1.0)

    def test_update_counters_snapshots_numeric_values(self):
        reg = MetricsRegistry()
        reg.update_counters({"x": 3, "y": 1.5, "skip": "str"}, prefix="e.")
        assert reg.snapshot()["counters"] == {"e.x": 3, "e.y": 1.5}

    def test_histogram_nearest_rank(self):
        h = Histogram()
        for v in range(1, 101):
            h.observe(float(v))
        assert h.percentile(50) == 50.0
        assert h.percentile(95) == 95.0
        assert h.snapshot()["p99"] == 99.0


# ---------------------------------------------------------------------------
# Disabled-path overhead (the zero-overhead contract, micro-benched)
# ---------------------------------------------------------------------------

class TestNullOverhead:
    N = 100_000
    BUDGET_S_PER_CALL = 5e-6        # 5us: ~100x a no-op call, CI-safe

    def test_null_tracer_span_overhead(self):
        tr = NULL_TRACER
        t0 = time.perf_counter()
        for _ in range(self.N):
            with tr.span("x"):
                pass
        dt = time.perf_counter() - t0
        assert not tr.events
        assert dt / self.N < self.BUDGET_S_PER_CALL, (
            f"disabled span costs {dt / self.N * 1e6:.2f}us/call")

    def test_null_metrics_overhead(self):
        reg = NULL_METRICS
        t0 = time.perf_counter()
        for _ in range(self.N):
            reg.counter("x").inc()
        dt = time.perf_counter() - t0
        assert reg.snapshot() == {}
        assert dt / self.N < self.BUDGET_S_PER_CALL

    def test_ambient_default_is_null_and_falsy(self):
        tr = current_tracer()
        assert tr is NULL_TRACER and not tr
        tr.instant("nothing")
        tr.counter("c", 0.0, 1)
        tr.complete("x", 0.0, 1.0)
        assert tr.summary() == "(tracing disabled)"

    def test_use_tracer_stacks_and_restores(self):
        assert current_tracer() is NULL_TRACER
        tr = Tracer()
        with use_tracer(tr):
            assert current_tracer() is tr
            with use_tracer(None):
                assert current_tracer() is NULL_TRACER
            assert current_tracer() is tr
        assert current_tracer() is NULL_TRACER


# ---------------------------------------------------------------------------
# Tracer spans + Chrome export
# ---------------------------------------------------------------------------

def _counting_clock():
    """Deterministic clock: advances 1s per call."""
    state = {"t": 0.0}

    def clock():
        state["t"] += 1.0
        return state["t"]

    return clock


class TestTracer:
    def test_spans_nest_and_export_valid_chrome(self):
        tr = Tracer(clock=_counting_clock())
        with tr.span("outer", alpha=1):
            with tr.span("inner"):
                pass
            tr.instant("mark")
        tr.counter("depth", 0.5, 3, group="serving")
        payload = tr.to_chrome()
        assert validate_chrome_trace(payload, expect_groups=["dse", "serving"]) == []
        phases = sorted(ev["ph"] for ev in payload["traceEvents"])
        assert "C" in phases and "X" in phases and "i" in phases and "M" in phases

    def test_span_records_error_arg_on_exception(self):
        tr = Tracer(clock=_counting_clock())
        with pytest.raises(ValueError):
            with tr.span("bad"):
                raise ValueError("boom")
        (ev,) = tr.events
        assert ev[6]["error"] == "ValueError"

    def test_sim_complete_events_ignore_wall_clock(self):
        tr = Tracer()
        tr.complete("batch", 1.0, 2.0, group="serving", lane="alexnet", n=4)
        tr.instant("fault:fail", t=1.5, group="serving", lane="faults")
        (x, i) = tr.to_chrome()["traceEvents"][-2:]
        assert (x["ts"], x["dur"]) == (1_000_000, 1_000_000)
        assert i["ts"] == 1_500_000 and i["s"] == "t"

    def test_jsonl_export_one_event_per_line(self, tmp_path):
        tr = Tracer(clock=_counting_clock())
        with tr.span("s"):
            pass
        path = tr.write(str(tmp_path / "t.jsonl"))
        lines = [json.loads(l) for l in open(path)]
        assert [e["ph"] for e in lines] == ["M", "M", "X"]

    def test_summary_reports_self_time_and_metrics(self):
        tr = Tracer(clock=_counting_clock())
        with tr.span("outer"):          # clock ticks 1s per now() call:
            with tr.span("inner"):      # outer [1,4], inner [2,3]
                pass
        tr.metrics.counter("hits").inc(7)
        s = tr.summary()
        assert "dse/inner" in s and "dse/outer" in s and "hits" in s
        inner = next(l for l in s.splitlines() if "dse/inner" in l)
        outer = next(l for l in s.splitlines() if "dse/outer" in l)
        assert float(inner.split()[0]) == pytest.approx(1.0)
        assert float(outer.split()[0]) == pytest.approx(2.0)   # child removed

    @given(st.lists(st.booleans(), min_size=1, max_size=40))
    @settings(max_examples=50, deadline=None)
    def test_random_span_trees_always_validate(self, ops):
        """Spans produced by the context-manager API nest by construction:
        any open/close sequence exports with zero nesting violations."""
        tr = Tracer(clock=_counting_clock())
        with tr.span("root"):           # never empty, whatever ops drew
            pass
        open_spans = []
        for op in ops:
            if op and len(open_spans) < 5:
                sp = tr.span(f"s{len(open_spans)}")
                sp.__enter__()
                open_spans.append(sp)
            elif open_spans:
                open_spans.pop().__exit__(None, None, None)
        while open_spans:
            open_spans.pop().__exit__(None, None, None)
        assert validate_chrome_trace(tr.to_chrome()) == []

    def test_validator_flags_overlap_and_bad_counter(self):
        tr = Tracer()
        # two overlapping (non-nested) spans on one lane
        tr.complete("a", 0.0, 2.0, group="serving", lane="m")
        tr.complete("b", 1.0, 3.0, group="serving", lane="m")
        # counter going back in time
        tr.counter("q", 2.0, 1, group="serving")
        tr.counter("q", 1.0, 2, group="serving")
        problems = validate_chrome_trace(tr.to_chrome())
        assert any("overlaps" in p for p in problems)
        assert any("non-monotone" in p for p in problems)

    def test_validator_rejects_malformed_payloads(self):
        assert validate_chrome_trace([]) != []
        assert validate_chrome_trace({"traceEvents": [{"ph": "X"}]}) != []
        probs = validate_chrome_trace(
            {"traceEvents": [{"ph": "M", "name": "process_name", "pid": 1,
                              "tid": 0, "ts": 0, "args": {"name": "dse"}}]},
            expect_fault_events=True, expect_groups=["serving"])
        assert any("fault" in p for p in probs)
        assert any("serving" in p for p in probs)


# ---------------------------------------------------------------------------
# Engine counter schema (satellite: one stats schema for both engines)
# ---------------------------------------------------------------------------

class TestEngineStatsSchema:
    def test_reference_and_fast_share_one_schema(self):
        opts = scope.SearchOptions(m_samples=M)
        hw = scope.PackageSpec.of("mcm16").resolve()
        fast = opts.make_cost(hw)
        ref = scope.SearchOptions(m_samples=M, engine="reference").make_cost(hw)
        f_sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M,
                                          cost=fast))
        r_sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M,
                                          cost=ref))
        assert f_sol.latency == pytest.approx(r_sol.latency, rel=1e-9)
        fs, rs = fast.stats, ref.stats
        assert set(fs) == set(rs)
        # reference: no memo, every probe is a compute
        assert rs["memo_hits"] == 0 and rs["memo_cells"] == 0
        assert rs["cluster_probes"] == rs["cluster_computes"] > 0
        # fast: memo answers the probes it doesn't compute
        assert fs["memo_hits"] == fs["cluster_probes"] - fs["cluster_computes"]
        assert fs["memo_hits"] > 0
        # both runs routed their stats into solve()'s diagnostics
        assert f_sol.diagnostics["engine_stats"] == fs


# ---------------------------------------------------------------------------
# Front doors: solve(trace=...) / serve(tracer=...)
# ---------------------------------------------------------------------------

class TestFrontDoors:
    def test_trace_option_is_not_part_of_problem_identity(self):
        plain = scope.problem("alexnet", "mcm16", m_samples=M)
        traced_p = plain.with_options(trace="somewhere.json")
        assert problem_fingerprint(plain) == problem_fingerprint(traced_p)

    def test_solve_trace_true_attaches_tracer(self):
        sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M,
                                        trace=True))
        tr = sol.diagnostics["trace"]
        assert isinstance(tr, Tracer)
        names = {e[1] for e in tr.events}
        assert "solve:scope" in names and "search" in names
        assert "segment" in names
        snap = tr.metrics.snapshot()["counters"]
        assert snap["solve.calls"] == 1
        assert snap["engine.segment_evals"] > 0
        assert validate_chrome_trace(tr.to_chrome(),
                                     expect_groups=["dse"]) == []

    def test_solve_trace_path_writes_file(self, tmp_path):
        path = str(tmp_path / "solve.json")
        sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M,
                                        trace=path))
        assert sol.feasible
        payload = json.load(open(path))
        assert validate_chrome_trace(payload, expect_groups=["dse"]) == []

    def test_solve_without_trace_records_nothing(self):
        sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M))
        assert "trace" not in sol.diagnostics
        assert "engine_stats" in sol.diagnostics       # stats stay regardless

    def test_serve_tracer_builds_gantt(self, tmp_path):
        path = str(tmp_path / "serve.json")
        sol = scope.solve(scope.problem("alexnet:1:500,resnet18:1:500",
                                        "mcm16_hetero", m_samples=M))
        rep = sol.serve(n_requests=1500, rate_scale=0.75, seed=0,
                        faults="zone:little@35%:65%", tracer=path)
        assert rep.conserved
        tr = rep.tracer
        assert rep.meta["trace_path"] == path
        payload = json.load(open(path))
        assert validate_chrome_trace(payload, expect_fault_events=True,
                                     expect_groups=["serving"]) == []
        names = {e[1] for e in tr.events}
        assert "batch" in names and "fault:fail" in names
        assert "fault:re-solve" in names and "recovered" in names
        assert "redeploy" in names
        assert any(e[0] == "C" and e[1].startswith("queue:")
                   for e in tr.events)
        # mid-run degraded re-solves land on the same timeline (dse group)
        groups = {e[2] for e in tr.events}
        assert "serving" in groups and "dse" in groups
        counters = tr.metrics.snapshot()["counters"]
        assert counters["serving.faults"] >= 1
        assert counters["serving.batches"] > 0


# ---------------------------------------------------------------------------
# Determinism: sim-clock traces are bytewise stable across same-seed runs
# ---------------------------------------------------------------------------

class TestTraceDeterminism:
    def test_same_seed_serving_trace_is_bytewise_identical(self, tmp_path):
        # fault_recovery=False keeps the run free of wall-clock solver
        # spans: every event is on the simulated clock.
        sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M))

        def run(path):
            rep = sol.serve(n_requests=1200, seed=7,
                            faults="chip:0,0@30%:60%",
                            fault_recovery=False, tracer=str(path))
            assert rep.conserved
            return path.read_bytes()

        a = run(tmp_path / "a.json")
        b = run(tmp_path / "b.json")
        assert a == b
        payload = json.loads(a)
        assert validate_chrome_trace(payload, expect_fault_events=True,
                                     expect_groups=["serving"]) == []

    def test_different_seed_changes_the_trace(self, tmp_path):
        sol = scope.solve(scope.problem("alexnet", "mcm16", m_samples=M))
        reps = [sol.serve(n_requests=400, seed=s, tracer=True)
                for s in (0, 1)]
        streams = [r.tracer.to_chrome()["traceEvents"] for r in reps]
        assert streams[0] != streams[1]
