"""The program's spans on the device trace's clock: idle time put down to
the innermost span, and device time per jitted module."""

import json

import numpy as np
import pytest

from bench import spans, spec, trace

MS = 1_000_000


def _ext(device, host, program, modules=None):
    return {"device": {"/device:TPU:0": device}, "host": host,
            "spans": program,
            "modules": {"/device:TPU:0": modules or []}}


def _nested():
    # Window 0-100 ms; the device is busy 20-30, 45-50 and 95-100, idle
    # 0-20, 30-45 and 50-95.  The host is in bench.step 0-60 (engine.step
    # 5-55, in it engine.decode 10-40, in it serving.sparse_apply 15-35,
    # in it kernels.upload 16-20: three levels under the step) and in
    # bench.wait 70-90; nothing covers 60-70 and 90-95.
    device = [["fusion", 20 * MS, 10 * MS], ["kern", 45 * MS, 5 * MS],
              ["fusion", 95 * MS, 5 * MS]]
    host = [["bench.window", 0, 100 * MS], ["bench.step", 0, 60 * MS],
            ["bench.wait", 70 * MS, 20 * MS]]
    program = [["kernels.upload", 16 * MS, 4 * MS],
               ["engine.decode", 10 * MS, 30 * MS],
               ["engine.step", 5 * MS, 50 * MS],
               ["serving.sparse_apply", 15 * MS, 20 * MS]]
    return _ext(device, host, program)


def test_idle_goes_to_the_innermost_span():
    ext = _nested()
    r = spans.reduce(ext)
    assert r["idle_s_by_span"] == pytest.approx({
        "bench.step": 0.005 + 0.005,             # 0-5, 55-60
        "engine.step": 0.005 + 0.005 + 0.005,    # 5-10, 40-45, 50-55
        "engine.decode": 0.005 + 0.005,          # 10-15, 35-40
        "serving.sparse_apply": 0.001 + 0.005,   # 15-16, 30-35
        "kernels.upload": 0.004,                 # 16-20
        "bench.wait": 0.020,                     # 70-90
        "unattributed": 0.010 + 0.005})          # 60-70, 90-95
    host = trace.reduce(ext)
    assert sum(r["idle_s_by_span"].values()) == pytest.approx(
        sum(host["idle_s_by_host"].values()))
    assert sum(r["idle_s_by_span"].values()) == pytest.approx(
        host["window_s"] - host["busy_s"])
    # Inside a span, its children's idle time included.
    assert r["idle_s_within"] == pytest.approx({
        "engine.step": 0.035, "engine.decode": 0.020,
        "serving.sparse_apply": 0.010, "kernels.upload": 0.004})
    assert r["span_calls"] == {"engine.step": 1, "engine.decode": 1,
                               "serving.sparse_apply": 1,
                               "kernels.upload": 1}


def test_spans_outside_the_window_are_clipped_and_not_counted():
    ext = _nested()
    ext["spans"].append(["engine.step", 90 * MS, 20 * MS])
    ext["spans"].append(["engine.step", 120 * MS, 5 * MS])
    r = spans.reduce(ext)
    assert r["span_calls"]["engine.step"] == 2
    assert r["idle_s_within"]["engine.step"] == pytest.approx(0.040)
    assert r["idle_s_by_span"]["engine.step"] == pytest.approx(0.020)
    assert r["idle_s_by_span"]["unattributed"] == pytest.approx(0.010)


def test_innermost_pieces_of_spans_that_start_together():
    # A parent and its first child start at the same instant; a second
    # child follows a gap in the parent.
    pieces = spans.innermost([["child", 0, 4], ["parent", 0, 10],
                              ["child2", 6, 2]])
    assert pieces == [(0, 4, "child"), (4, 6, "parent"), (6, 8, "child2"),
                      (8, 10, "parent")]
    assert spans.innermost([]) == []


def test_module_time_strips_the_id():
    modules = [["jit_engine_decode_hidden(12)", 10 * MS, 20 * MS],
               ["jit_engine_decode_hidden(13)", 40 * MS, 20 * MS],
               ["jit_engine_prefill(7)", 70 * MS, 5 * MS],
               ["jit_engine_prefill(7)", 98 * MS, 5 * MS],
               ["jit_engine_prefill(7)", 200 * MS, 5 * MS]]
    ext = _ext([["k", 10 * MS, 5 * MS]], [["bench.window", 0, 100 * MS]],
               [], modules)
    r = spans.reduce(ext)
    assert r["module_s"] == pytest.approx({"jit_engine_decode_hidden": 0.040,
                                           "jit_engine_prefill": 0.007})
    assert r["module_calls"] == {"jit_engine_decode_hidden": 2,
                                 "jit_engine_prefill": 2}
    assert spans.module_name("jit_f(3)") == "jit_f"
    assert spans.module_name("jit_f") == "jit_f"


def test_no_window_or_no_device_reads_nothing():
    ext = _nested()
    ext["host"] = ext["host"][1:]
    assert spans.reduce(ext) is None


def test_cut_keeps_what_overlaps_under_a_window_of_its_own():
    ext = _nested()
    part = spans.cut(ext, 12 * MS, 42 * MS)
    assert part["host"][0] == [trace.WINDOW, 12 * MS, 30 * MS]
    assert sorted(n for n, _, _ in part["spans"]) == [
        "engine.decode", "engine.step", "kernels.upload",
        "serving.sparse_apply"]
    r = spans.reduce(part)
    # Idle 12-20 and 30-42 of the 30 ms window.
    assert sum(r["idle_s_by_span"].values()) == pytest.approx(0.020)


def test_recorded_chip_trace_with_spans():
    """A quarter second of `smollm135m-chat` traced on a TPU v5e (one
    chip) with the program's spans, cut to a window of its own."""
    path = spec.BENCH / "tests" / "data" / "chip_trace_spans.json"
    rec = json.loads(path.read_text())
    ev = rec["events"]
    host = trace.reduce(ev)
    r = spans.reduce(ev)
    assert host["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    idle = sum(r["idle_s_by_span"].values())
    assert idle == pytest.approx(host["window_s"] - host["busy_s"])
    assert idle == pytest.approx(sum(host["idle_s_by_host"].values()))
    assert r["module_calls"].get("jit_engine_prefill", 0) >= 1
    assert r["module_calls"].get("jit_engine_decode_hidden", 0) >= 1
    assert [k for k in host["op_s"] if k.startswith("dtans_spmm_pallas")]
    # The program's spans name most of the idle time inside bench.step.
    assert r["idle_s_by_span"].get("bench.step", 0) < 0.1 * idle
    assert {"engine.step", "engine.prefill", "serving.sparse_apply",
            "kernels.upload", "engine.logits_d2h", "engine.sample"} \
        <= set(r["span_calls"])


def test_program_spans_land_on_the_profile(tmp_path):
    """A CPU profile of a tiny compressed-head engine: each program span
    is on the host plane, inside `bench.step`, and lasts what its JSONL
    record says to within 1 ms."""
    import jax

    from repro import obs
    from repro.configs import get_smoke
    from repro.models import api
    from repro.serving.engine import Engine
    cfg = get_smoke("smollm-135m").with_(vocab=64)
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    head = Engine.compress_lm_head(cfg, params, sparsity=0.6, value_bits=5,
                                   lane_width=32)
    eng = Engine(cfg, params, slots=2, max_seq=16, sparse_head=head,
                 metrics=obs.MetricsRegistry())
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab, size=4)
    eng.submit(prompt, 2)
    eng.run_until_drained()                  # compile outside the profile
    jsonl = tmp_path / "spans.jsonl"
    with trace.capture(str(tmp_path / "prof")):
        obs.configure_trace(jsonl)
        try:
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                eng.submit(prompt, 2)
                for _ in range(2):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        eng.step()
        finally:
            obs.configure_trace(None)
    path = trace.xplane_file(str(tmp_path / "prof"))
    ext = {**trace.extract(path), **spans.extract(path)}
    with open(jsonl) as f:
        recs = [r for r in map(json.loads, f) if r["type"] == "span"]
    names = {r["name"] for r in recs}
    assert names == {"engine.step", "engine.refill", "engine.prefill",
                     "engine.insert_slot", "engine.decode",
                     "serving.sparse_apply", "kernels.upload",
                     "engine.logits_d2h", "engine.sample"}
    steps = sorted((s, s + d) for n, s, d in ext["host"]
                   if n == "bench.step")
    assert len(steps) == 2
    for name in names:
        on_trace = sorted((s, d) for n, s, d in ext["spans"] if n == name)
        jl = sorted((r["ts"], r["dur_s"]) for r in recs
                    if r["name"] == name)
        assert len(on_trace) == len(jl), name
        for (s, d), (_, dur_s) in zip(on_trace, jl):
            assert any(a <= s and s + d <= b for a, b in steps), name
            assert abs(d * 1e-9 - dur_s) < 1e-3, name
