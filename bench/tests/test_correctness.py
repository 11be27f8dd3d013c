"""`correct` on a whole run, at a size a CPU test holds.

The harness's look for a chip is skipped; everything else of a run is
driven: set-up, the window through `Engine.submit` / `Engine.step`, and the
comparison with the reference.  A sound run is correct; a run with the
timed path broken underneath is not, for each fault a serving cell can
have; and the control, the reference in a lower precision put in the
program's place, fails the limit.
"""

import time

import pytest

from bench import check, faults, headcache, run, spec

CFG = spec.load_json(spec.BENCH / "tests" / "data" / "tiny-llama.json")
MIX = spec.load_json(spec.BENCH / "tests" / "data" / "tiny-backlog.json")
CELL = {"name": "tiny"}
BENCH = {"end_to_end": [], "per_layer": []}


@pytest.fixture(autouse=True)
def _head_cache(tmp_path, monkeypatch):
    monkeypatch.setattr(headcache, "CACHE", tmp_path / "heads")


def _measure(seed, seconds=0.8, mix=MIX):
    return run.measure(BENCH, CELL, CFG, mix, seed, seconds, False,
                       time.perf_counter())


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(fault, monkeypatch):
    run.import_program()
    faults.FAULTS[fault](monkeypatch.setattr)
    r = _measure(2**31 + 12)
    assert not r["correct"]
    c = r["compared"]["max_logit_gap"]
    assert c["value"] > c["limit"]


def test_sound_run_is_correct_and_control_is_not():
    """A sound run is within the limit.  The reference in fp8, at every
    position of the same served sequences, puts first a token that the
    reference reads further below its best than the limit allows, and the
    run's own judge finds it not correct."""
    seen = run.execute(CELL, CFG, MIX, 2**31 + 13, 0.8, False,
                       time.perf_counter())
    r = run.result_line(BENCH, CELL, seen, False)
    assert r["correct"], r["compared"]
    assert r["compared"]["tokens_compared"]["value"] >= 16
    assert list(r)[-1] == "compared"
    ref = spec.load_module("reference", CFG["architecture"]).Reference(
        CFG, 2**31 + 13)
    gaps = check.control_gaps(ref, seen.compared_requests, MIX["max_seq"],
                              "fp8")
    verdict = check.judge(gaps, CFG["correct"])
    assert not verdict["correct"]
    assert verdict["compared"]["max_logit_gap"]["value"] > \
        CFG["correct"]["max_logit_gap"]


def test_planted_fault_is_undone():
    from repro.serving.engine import Engine
    run.import_program()
    pick = Engine._select_token
    with faults.planted("token_altered"):
        assert Engine._select_token is not pick
    assert Engine._select_token is pick


def test_traced_run_marks_its_window(monkeypatch):
    """A traced run records the window and the steps inside it on the
    profiler's clock (a CPU has no device plane to reduce), and the span
    readers find the engine's spans."""
    from bench import trace
    seen = {}
    reduce = trace.reduce

    def keep(ext):
        seen["ext"] = ext
        return reduce(ext)
    monkeypatch.setattr(trace, "reduce", keep)
    bench = {"end_to_end": [], "per_layer": [
        {"name": "engine.host_ms_per_step", "unit": "ms"}]}
    r = run.measure(bench, CELL, CFG, MIX, 2**31 + 14, 0.8, True,
                    time.perf_counter())
    host = seen["ext"]["host"]
    (w,) = [(s, s + d) for n, s, d in host if n == trace.WINDOW]
    inside = [s for n, s, d in host if n == "bench.step" and w[0] <= s < w[1]]
    assert len(inside) >= 5
    assert r["metrics"]["engine.host_ms_per_step"]["value"] > 0
