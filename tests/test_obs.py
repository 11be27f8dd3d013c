"""Observability layer tests: metrics registry exactness, trace
nesting, engine/autotune instrumentation wiring, timing dispersion."""

import json
import math
import warnings

import numpy as np
import pytest

from repro import obs
from repro.autotune import (DecisionCache, TimingSample, calibrate,
                            clear_memo, select, time_kernel)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.sparse.formats import CSR
from repro.sparse.random_graphs import erdos_renyi


def _small(seed: int = 2) -> CSR:
    a = erdos_renyi(220, 5, np.random.default_rng(seed))
    return CSR(a.indptr, a.indices, a.values.astype(np.float32), a.shape)


class TestHistogram:
    @pytest.mark.parametrize("samples", [
        [1.0], [3.0, 1.0, 2.0], list(range(100)),
        list(np.random.default_rng(0).standard_normal(512)),
        list(np.random.default_rng(1).lognormal(size=333)),
    ])
    def test_quantiles_match_numpy_while_bounded(self, samples):
        h = Histogram("t")
        for s in samples:
            h.observe(s)
        for q in (0.0, 0.25, 0.5, 0.95, 0.99, 1.0):
            assert h.quantile(q) == pytest.approx(
                float(np.percentile(np.asarray(samples, float), 100 * q,
                                    method="linear")), rel=0, abs=0)

    def test_reservoir_bounded_with_exact_aggregates(self):
        h = Histogram("t", capacity=8)
        xs = np.random.default_rng(3).uniform(0, 10, size=200)
        for x in xs:
            h.observe(x)
        # Reservoir stays bounded; count/total/min/max stay exact.
        assert len(h._samples) == 8
        assert h.count == 200
        assert h.total == pytest.approx(xs.sum())
        assert h.min == xs.min() and h.max == xs.max()
        # Quantiles remain sane (within observed range) after overflow.
        assert xs.min() <= h.quantile(0.5) <= xs.max()

    def test_reservoir_deterministic_across_runs(self):
        def fill():
            h = Histogram("same-name", capacity=16)
            for i in range(500):
                h.observe(float(i))
            return sorted(h._samples)
        assert fill() == fill()

    def test_empty_histogram(self):
        h = Histogram("t")
        assert math.isnan(h.quantile(0.5))
        assert math.isnan(h.mean)
        assert h.snapshot()["count"] == 0

    def test_bad_args(self):
        with pytest.raises(ValueError):
            Histogram("t", capacity=0)
        with pytest.raises(ValueError):
            Histogram("t").quantile(1.5)


class TestRegistry:
    def test_get_or_create_identity(self):
        r = MetricsRegistry()
        assert r.counter("a") is r.counter("a")
        assert r.gauge("g") is r.gauge("g")
        assert r.histogram("h") is r.histogram("h")

    def test_snapshot_is_detached_copy(self):
        r = MetricsRegistry()
        r.counter("c").add(2)
        r.gauge("g").set(7.5)
        r.histogram("h").observe(1.0)
        snap = r.snapshot()
        r.counter("c").add(100)
        r.gauge("g").set(0.0)
        r.histogram("h").observe(99.0)
        # The snapshot keeps the values from snapshot time...
        assert snap["counters"]["c"] == 2
        assert snap["gauges"]["g"] == 7.5
        assert snap["histograms"]["h"]["count"] == 1
        # ...is JSON-serializable, and mutating it leaves the registry
        # untouched.
        json.dumps(snap)
        snap["counters"]["c"] = -1
        assert r.counter("c").value == 102

    def test_null_registry_noops(self):
        obs.NULL.counter("x").add(5)
        obs.NULL.gauge("x").set(5)
        obs.NULL.histogram("x").observe(5)
        assert obs.NULL.counter("x").value == 0
        assert obs.NULL.histogram("x").count == 0
        assert obs.NULL.snapshot() == {"counters": {}, "gauges": {},
                                       "histograms": {}}

    def test_isolated_registries_dont_share(self):
        a, b = MetricsRegistry(), MetricsRegistry()
        a.counter("c").add(1)
        assert b.counter("c").value == 0


class TestTrace:
    def test_span_nesting_in_jsonl(self, tmp_path):
        p = tmp_path / "trace.jsonl"
        obs.configure_trace(p)
        try:
            assert obs.trace_active()
            assert obs.trace_path() == str(p)
            with obs.span("outer", k="v") as outer_id:
                obs.event("mark", x=1)
                with obs.span("inner") as inner_id:
                    assert inner_id != outer_id
        finally:
            obs.configure_trace(None)
        recs = [json.loads(line) for line in p.read_text().splitlines()]
        by_name = {r["name"]: r for r in recs}
        assert len(recs) == 3
        # Children close (and serialize) before parents; parent ids
        # stitch the tree back together.
        assert [r["name"] for r in recs] == ["mark", "inner", "outer"]
        assert by_name["outer"]["parent"] is None
        assert by_name["inner"]["parent"] == by_name["outer"]["id"]
        assert by_name["mark"]["parent"] == by_name["outer"]["id"]
        assert by_name["outer"]["k"] == "v"
        assert by_name["mark"]["type"] == "event"
        assert by_name["inner"]["dur_s"] >= 0.0

    def test_span_records_error_and_propagates(self, tmp_path):
        p = tmp_path / "trace.jsonl"
        obs.configure_trace(p)
        try:
            with pytest.raises(RuntimeError):
                with obs.span("boom"):
                    raise RuntimeError("x")
        finally:
            obs.configure_trace(None)
        (rec,) = [json.loads(line) for line in p.read_text().splitlines()]
        assert rec["error"] == "RuntimeError"

    def test_disabled_path_yields_none(self):
        obs.configure_trace(None)
        assert not obs.trace_active()
        with obs.span("off") as sid:
            assert sid is None
        obs.event("off")      # must not raise

    def test_sink_is_written_only_when_closed(self, tmp_path):
        p = tmp_path / "trace.jsonl"
        obs.configure_trace(p)
        try:
            for i in range(50):
                with obs.span("outer", i=i):
                    with obs.span("inner"):
                        obs.event("mark")
            with pytest.raises(ValueError):
                with obs.span("crash"):
                    raise ValueError
            assert p.stat().st_size == 0      # nothing on the hot path
        finally:
            obs.configure_trace(None)
        recs = [json.loads(line) for line in p.read_text().splitlines()]
        names = [r["name"] for r in recs]
        assert names == ["mark", "inner", "outer"] * 50 + ["crash"]
        assert [r["i"] for r in recs if r["name"] == "outer"] \
            == list(range(50))
        assert recs[-1]["error"] == "ValueError"

    def test_spans_enter_a_profiler_annotation_only_when_tracing(
            self, tmp_path, monkeypatch):
        from repro.obs import trace as trace_mod
        entered = []

        class Annotation:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                return False
        monkeypatch.setattr(trace_mod, "_annotation", Annotation)
        obs.configure_trace(None)
        with obs.span("off"):
            pass
        assert entered == []
        obs.configure_trace(tmp_path / "t.jsonl")
        try:
            with obs.span("outer", k=1):
                with obs.span("inner"):
                    pass
        finally:
            obs.configure_trace(None)
        assert entered == ["outer", "inner"]

    def test_tracing_off_needs_no_jax(self, tmp_path, monkeypatch):
        """The module imports, and spans run, with JAX unimportable; only
        turning tracing on imports it."""
        import importlib.util
        import sys

        from repro.obs import trace as trace_mod
        monkeypatch.setitem(sys.modules, "jax", None)
        monkeypatch.setitem(sys.modules, "jax.profiler", None)
        spec = importlib.util.spec_from_file_location(
            "repro_obs_trace_without_jax", trace_mod.__file__)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.configure_trace(None)
        with mod.span("off") as sid:
            assert sid is None
        with pytest.raises(ImportError):
            mod.configure_trace(tmp_path / "t.jsonl")
        assert mod.trace_path() is None


class TestEngineMetrics:
    @pytest.fixture(scope="class")
    def drained(self):
        import jax

        from repro.configs import get_smoke
        from repro.models import api
        from repro.serving.engine import Engine
        cfg = get_smoke("smollm-135m").with_(vocab=32)
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        reg = MetricsRegistry()
        eng = Engine(cfg, params, slots=2, max_seq=32, metrics=reg)
        rng = np.random.default_rng(0)
        reqs = [eng.submit(rng.integers(0, 32, size=3), 3)
                for _ in range(3)]
        done = eng.run_until_drained()
        return reg, eng, reqs, done

    def test_step_metrics_after_drain(self, drained):
        reg, eng, reqs, _ = drained
        snap = reg.snapshot()
        c, h = snap["counters"], snap["histograms"]
        assert c["engine.requests_submitted"] == 3
        assert c["engine.requests_completed"] == 3
        assert c["engine.tokens_total"] == sum(len(r.out) for r in reqs)
        assert c["engine.steps_total"] == h["engine.step_s"]["count"] > 0
        for name in ("engine.step_s", "engine.decode_s",
                     "engine.refill_s", "engine.prefill_s"):
            assert h[name]["min"] >= 0.0
        # step wall time bounds its decode component
        assert h["engine.step_s"]["p50"] >= h["engine.decode_s"]["min"]
        occ = h["engine.occupancy"]
        assert 0.0 < occ["min"] and occ["max"] <= 1.0
        assert snap["gauges"]["engine.queue_depth"] == 0

    def test_latency_timestamps_and_histograms(self, drained):
        reg, _, reqs, _ = drained
        h = reg.snapshot()["histograms"]
        for r in reqs:
            assert r.t_submit is not None
            assert r.t_first is not None and r.t_first >= r.t_submit
            assert r.t_done is not None and r.t_done >= r.t_first
        assert h["engine.ttft_s"]["count"] == 3
        assert h["engine.e2e_s"]["count"] == 3
        assert h["engine.e2e_s"]["max"] >= h["engine.ttft_s"]["min"]


class TestDrainTruncation:
    @pytest.fixture(scope="class")
    def engine_factory(self):
        import jax

        from repro.configs import get_smoke
        from repro.models import api
        from repro.serving.engine import Engine
        cfg = get_smoke("smollm-135m").with_(vocab=32)
        params = api.init_params(cfg, jax.random.PRNGKey(1))

        def make():
            return Engine(cfg, params, slots=2, max_seq=32,
                          metrics=MetricsRegistry())
        return make

    def test_truncation_raises_by_default(self, engine_factory):
        eng = engine_factory()
        eng.submit(np.array([1, 2]), 8)
        with pytest.raises(RuntimeError, match="max_steps=1"):
            eng.run_until_drained(max_steps=1)

    def test_truncation_warn_sets_flag_and_counts(self, engine_factory):
        eng = engine_factory()
        eng.submit(np.array([1, 2]), 8)
        with pytest.warns(UserWarning, match="truncated"):
            eng.run_until_drained(max_steps=1, on_truncate="warn")
        assert eng.truncated
        assert eng.metrics.counter("engine.drain_truncations").value == 1
        # A later full drain completes and clears the flag.
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            done = eng.run_until_drained()
        assert not eng.truncated
        assert len(done) == 1 and done[0].done

    def test_clean_drain_does_not_warn_or_flag(self, engine_factory):
        eng = engine_factory()
        eng.submit(np.array([1, 2]), 2)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            eng.run_until_drained()
        assert not eng.truncated

    def test_invalid_on_truncate_rejected(self, engine_factory):
        with pytest.raises(ValueError, match="on_truncate"):
            engine_factory().run_until_drained(on_truncate="ignore")


class TestDecisionCacheCounters:
    def _counts(self):
        c = obs.default_registry().snapshot()["counters"]
        return (c.get("autotune.decision_cache.hits", 0),
                c.get("autotune.decision_cache.misses", 0))

    def test_cold_then_warm_select(self):
        a = _small(21)
        cache = DecisionCache(path=None)
        clear_memo()
        h0, m0 = self._counts()
        d1 = select(a, warm=True, cache=cache)
        h1, m1 = self._counts()
        assert m1 > m0                      # cold lookup missed
        assert h1 == h0
        clear_memo()                        # force the persistent cache
        d2 = select(a, warm=True, cache=cache)
        h2, m2 = self._counts()
        assert h2 > h1                      # warm lookup hit
        assert m2 == m1
        assert d2.config_name == d1.config_name

    def test_memo_hit_skips_cache_lookup(self):
        a = _small(22)
        cache = DecisionCache(path=None)
        clear_memo()
        select(a, warm=True, cache=cache)
        h1, m1 = self._counts()
        select(a, warm=True, cache=cache)   # in-process memo hit
        assert self._counts() == (h1, m1)


class TestTimingSample:
    def test_structure_and_float_compat(self):
        import jax.numpy as jnp
        t = time_kernel(lambda: jnp.zeros(()), warmup=1, repeats=5)
        assert isinstance(t, TimingSample)
        assert isinstance(t, float)
        assert t.n == 5
        assert t.iqr >= 0.0
        assert 0.0 < t.min <= t.median == float(t)
        assert json.dumps(t) == json.dumps(float(t))

    def test_from_samples(self):
        t = TimingSample.from_samples([3.0, 1.0, 2.0])
        assert float(t) == 2.0
        assert t.min == 1.0 and t.n == 3
        assert t.iqr == pytest.approx(1.0)
        assert not t.noisy
        noisy = TimingSample(1.0, iqr=0.9, min=0.5, n=3)
        assert noisy.noisy and noisy.rel_iqr == pytest.approx(0.9)

    def test_calibrate_carries_dispersion_and_weights(self):
        res = calibrate({"er": _small(23)}, warmup=0, repeats=1)
        assert all(p.measured_iqr >= 0.0 for p in res.points)
        assert all(0.0 < p.weight <= 1.0 for p in res.points)
        # to_dict keeps its documented top-level shape.
        assert set(res.to_dict()) == {"model", "err_before",
                                      "err_after", "points"}
