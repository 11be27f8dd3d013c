"""The FormatSpec registry seam: config-string round-trips for every
registered format, registry-derived groupings, and — the acceptance
bar — a toy format registered HERE (not in any dispatch site) showing
up in the conformance path discovery, the candidate sweep, the
exhaustive oracle and `select()` without a single edit to
search/oracle/measure/serving code."""

import numpy as np
import pytest

from repro.autotune import (DecisionCache, candidates, clear_memo,
                            fingerprint, format_names, get_format,
                            iter_formats, oracle_times, parse_config,
                            select)
from repro.autotune.measure import measure_named, spmv_runner
from repro.sparse.formats import CSR
from repro.sparse.random_graphs import erdos_renyi, stencil_2d
from repro.sparse.registry import CostTerms, FormatSpec, register, unregister


def _f32(a: CSR) -> CSR:
    return CSR(a.indptr, a.indices, a.values.astype(np.float32), a.shape)


class TestRegistryBasics:
    def test_builtin_formats_registered(self):
        names = format_names()
        assert len(names) >= 8
        for want in ("dense", "csr", "coo", "sell", "rgcsr", "dtans",
                     "rgcsr_dtans", "bcsr", "bcsr_dtans"):
            assert want in names

    def test_dense_not_selectable(self):
        assert "dense" not in format_names(selectable=True)
        assert not get_format("dense").selectable

    def test_decode_formats(self):
        assert set(format_names(decodes=True)) == {
            "dtans", "rgcsr_dtans", "bcsr_dtans"}

    def test_unknown_format_raises(self):
        with pytest.raises(ValueError, match="unknown format"):
            get_format("alphasparse")

    def test_config_name_roundtrip_every_format(self):
        """encode_knobs / decode_knobs invert each other over every
        registered format's full knob grid."""
        for spec in iter_formats():
            for knobs in spec.knob_grid():
                name = spec.encode_knobs(knobs)
                spec2, parsed = parse_config(name)
                assert spec2 is spec
                assert spec.normalize_knobs(parsed) == knobs

    def test_duplicate_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register(get_format("csr"))

    def test_unknown_knob_rejected(self):
        with pytest.raises(ValueError, match="unknown knobs"):
            get_format("csr").normalize_knobs({"group_size": 4})


class ToyDiagSpec(FormatSpec):
    """Minimal example format: stores only the main diagonal's nonzero
    pattern positions (lossy for anything off-diagonal — fine for the
    seam test, which runs it on a diagonal-only corpus)."""

    name = "toy_diag"
    knob_domains = {"stride": (1, 2)}
    named_knobs = ()

    def nbytes_exact(self, fp, *, stride=1):
        return fp.nnz * fp.value_bytes + 8 * stride

    def nbytes_constructed(self, a, *, params=None, artifacts=None,
                           stride=1):
        return a.nnz * a.values.dtype.itemsize + 8 * stride

    def cost_terms(self, fp, *, stride=1):
        return CostTerms(lockstep=float(fp.nnz))

    def pack(self, a, *, params=None, artifacts=None, stride=1):
        d = a.to_dense()
        return np.diagonal(d).copy(), d.shape

    def runner(self, packed, x):
        diag, shape = packed
        m, n = shape
        k = min(m, n)
        out = np.zeros(m, dtype=diag.dtype)

        def run():
            out[:k] = diag * np.asarray(x, dtype=diag.dtype)[:k]
            return out

        return run


@pytest.fixture
def toy_spec():
    spec = ToyDiagSpec()
    register(spec)
    try:
        yield spec
    finally:
        unregister("toy_diag")
        clear_memo()


class TestToyFormatJoinsEverything:
    """A format registered in a test — zero edits anywhere else — must
    surface in every registry consumer."""

    def test_joins_conformance_path_discovery(self, toy_spec):
        from test_spmv_conformance import registry_spmv_paths
        paths = registry_spmv_paths()
        assert "registry:toy_diag" in paths
        d = np.diag(np.arange(1.0, 7.0))
        a = CSR.from_dense(d)
        x = np.arange(6.0)
        got = np.asarray(paths["registry:toy_diag"](a, x))
        np.testing.assert_allclose(got, d @ x)

    def test_joins_spmm_path_discovery(self, toy_spec):
        """A spec with only the single-vector contract still joins the
        batched sweep: `FormatSpec.spmm_runner`'s generic per-column
        fallback drives it (no spmm_fn override anywhere)."""
        from test_spmv_conformance import registry_spmm_paths
        paths = registry_spmm_paths()
        assert "registry:toy_diag" in paths
        d = np.diag(np.arange(1.0, 7.0))
        a = CSR.from_dense(d)
        X = np.arange(18.0).reshape(6, 3)
        got = np.asarray(paths["registry:toy_diag"](a, X))
        np.testing.assert_allclose(got, d @ X)

    def test_joins_batched_timing_harness(self, toy_spec):
        a = CSR.from_dense(np.diag(np.arange(1.0, 9.0)))
        X = np.arange(16.0).reshape(8, 2)
        fn = spmv_runner(a, "toy_diag", x=X, batch=2)
        np.testing.assert_allclose(np.asarray(fn()), a.to_dense() @ X)
        assert measure_named(a, "toy_diag", batch=2, warmup=0,
                             repeats=1) >= 0.0

    def test_joins_candidate_sweep_and_select(self, toy_spec):
        a = _f32(stencil_2d(12))
        fp = fingerprint(a)
        cands = candidates(fp)                 # default = full registry
        toy = [c for c in cands if c.fmt == "toy_diag"]
        assert len(toy) == 2                   # stride sweep
        assert {c.config_name for c in toy} == {"toy_diag",
                                                "toy_diag[stride=2]"}
        dec = select(a, formats=("toy_diag",),
                     cache=DecisionCache(path=None))
        assert dec.fmt == "toy_diag"
        assert dec.exact_size

    def test_joins_oracle(self, toy_spec):
        a = _f32(stencil_2d(10))
        times = oracle_times(a)
        assert "toy_diag" in times
        assert "toy_diag[stride=2]" in times

    def test_joins_timing_harness(self, toy_spec):
        a = CSR.from_dense(np.diag(np.arange(1.0, 9.0)))
        x = np.arange(8.0)
        fn = spmv_runner(a, "toy_diag", x=x)
        np.testing.assert_allclose(np.asarray(fn()), a.to_dense() @ x)
        assert measure_named(a, "toy_diag[stride=2]", warmup=0,
                             repeats=1) >= 0.0


class ToyGroupedSpec(ToyDiagSpec):
    """Toy spec REUSING a built-in override knob name (group_size) with
    its own domain — select() must sweep the spec's domain, not clobber
    it with the built-in RGCSR sweep."""

    name = "toy_grouped"
    knob_domains = {"group_size": (64, 128)}
    named_knobs = ("group_size",)

    def nbytes_exact(self, fp, *, group_size=64):
        return fp.nnz * fp.value_bytes + group_size

    def nbytes_constructed(self, a, *, params=None, artifacts=None,
                           group_size=64):
        return a.nnz * a.values.dtype.itemsize + group_size

    def cost_terms(self, fp, *, group_size=64):
        return CostTerms(lockstep=float(fp.nnz))

    def pack(self, a, *, params=None, artifacts=None, group_size=64):
        return super().pack(a)


def test_select_sweeps_third_party_knob_domain():
    """select()'s built-in sweep defaults must not override a
    third-party format's own domain for a same-named knob."""
    register(ToyGroupedSpec())
    try:
        a = _f32(stencil_2d(10))
        clear_memo()
        dec = select(a, formats=("toy_grouped",),
                     cache=DecisionCache(path=None))
        names = {row[0] for row in dec.leaderboard}
        assert names == {"toy_grouped[G=64]", "toy_grouped[G=128]"}
        assert oracle_times(a, formats=("toy_grouped",)).keys() == names
    finally:
        unregister("toy_grouped")
        clear_memo()


class TestKnobOverrides:
    """The generic `knob_overrides=` parameter (ROADMAP open item):
    narrows ANY spec's knob domain by name — third-party knobs without
    a dedicated keyword included — on both select() and the oracle."""

    def test_narrows_third_party_knob(self, toy_spec):
        a = _f32(stencil_2d(10))
        clear_memo()
        dec = select(a, formats=("toy_diag",),
                     knob_overrides={"stride": (2,)},
                     cache=DecisionCache(path=None))
        assert [row[0] for row in dec.leaderboard] == ["toy_diag[stride=2]"]
        times = oracle_times(a, formats=("toy_diag",),
                             knob_overrides={"stride": (2,)})
        assert set(times) == {"toy_diag[stride=2]"}

    def test_matches_legacy_sugar(self):
        """knob_overrides={'group_size': ...} and the deprecated
        group_sizes= sugar must produce identical sweeps."""
        a = _f32(stencil_2d(12))
        clear_memo()
        d1 = select(a, formats=("rgcsr",), group_sizes=(8, 16),
                    cache=DecisionCache(path=None))
        clear_memo()
        d2 = select(a, formats=("rgcsr",),
                    knob_overrides={"group_size": (8, 16)},
                    cache=DecisionCache(path=None))
        assert d1.leaderboard == d2.leaderboard
        assert d1.config_name == d2.config_name

    def test_sugar_wins_on_conflict(self):
        """When both spell the same knob, the explicit named keyword
        wins (documented deprecation path)."""
        a = _f32(stencil_2d(12))
        clear_memo()
        dec = select(a, formats=("rgcsr",), group_sizes=(8,),
                     knob_overrides={"group_size": (4, 16)},
                     cache=DecisionCache(path=None))
        assert [row[0] for row in dec.leaderboard] == ["rgcsr[G=8]"]

    def test_overrides_enter_cache_key(self):
        a = _f32(stencil_2d(12))
        cache = DecisionCache(path=None)
        clear_memo()
        select(a, formats=("rgcsr",), cache=cache)
        select(a, formats=("rgcsr",),
               knob_overrides={"group_size": (8,)}, cache=cache)
        assert len(cache) == 2

    def test_ignored_for_foreign_knobs(self):
        """Overrides naming knobs a format does not declare leave that
        format's sweep untouched (same contract as FormatSpec.knob_grid)."""
        a = _f32(stencil_2d(12))
        clear_memo()
        dec = select(a, formats=("sell",),
                     knob_overrides={"group_size": (8,)},
                     cache=DecisionCache(path=None))
        assert dec.config_name == "sell"


class ToyModeSpec(ToyDiagSpec):
    """Toy spec with a STRING-valued knob — config names must round-trip
    for non-integer third-party knob values too."""

    name = "toy_mode"
    knob_domains = {"mode": ("fast", "safe")}
    named_knobs = ("mode",)

    def nbytes_exact(self, fp, *, mode="fast"):
        return fp.nnz * fp.value_bytes

    def cost_terms(self, fp, *, mode="fast"):
        return CostTerms(lockstep=float(fp.nnz))


def test_string_knob_config_roundtrip():
    register(ToyModeSpec())
    try:
        spec = get_format("toy_mode")
        name = spec.encode_knobs({"mode": "safe"})
        assert name == "toy_mode[mode=safe]"
        spec2, knobs = parse_config(name)
        assert spec2 is spec and knobs == {"mode": "safe"}
    finally:
        unregister("toy_mode")


class TestStrideKnobRendering:
    def test_unknown_stride_component(self):
        with pytest.raises(ValueError):
            parse_config("csr[stride=2]")
