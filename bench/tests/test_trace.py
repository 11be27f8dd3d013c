"""Reduction of a profiler trace to busy time, idle gaps and op time."""

import json

import pytest

from bench import spec, trace

MS = 1_000_000


def _ext(device, host):
    return {"device": {"/device:TPU:0": device}, "host": host}


def test_union_merges_overlaps():
    assert trace.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]


def test_busy_idle_and_attribution():
    # Window 0-100 ms.  Device busy 10-30 (two overlapping ops) and 60-70.
    device = [["fusion.1", 10 * MS, 15 * MS], ["kern", 20 * MS, 10 * MS],
              ["kern", 60 * MS, 10 * MS], ["fusion.1", 200 * MS, 5 * MS]]
    host = [["bench.window", 0, 100 * MS], ["bench.step", 5 * MS, 30 * MS],
            ["bench.submit", 35 * MS, 5 * MS], ["bench.wait", 40 * MS, 20 * MS],
            ["bench.step", 60 * MS, 35 * MS]]
    r = trace.reduce(_ext(device, host))
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["op_s"] == pytest.approx({"fusion.1": 0.015, "kern": 0.020})
    assert r["op_calls"] == {"fusion.1": 1, "kern": 2}
    idle = r["idle_s_by_host"]
    # Idle: 0-10, 30-60, 70-100.
    assert sum(idle.values()) == pytest.approx(0.070)
    assert idle["bench.step"] == pytest.approx(0.005 + 0.005 + 0.025)
    assert idle["bench.submit"] == pytest.approx(0.005)
    assert idle["bench.wait"] == pytest.approx(0.020)
    assert idle["unattributed"] == pytest.approx(0.005 + 0.005)
    assert trace.top(idle, 2)[0][0] == "bench.step"


def test_no_window_or_no_device_reads_nothing():
    assert trace.reduce(_ext([["k", 0, 5]], [])) is None
    assert trace.reduce({"device": {}, "host": [["bench.window", 0, 9]]}) \
        is None


def _busy_by_grid(events, lo, hi, step=1000):
    """Busy time counted on a 1 us grid: the slow, obvious way."""
    import numpy as np
    grid = np.zeros((hi - lo) // step + 1, dtype=bool)
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[(a - lo) // step:(b - lo + step - 1) // step] = True
    return grid.sum() * step * 1e-9


def test_recorded_chip_trace():
    """A quarter second of `smollm135m-chat` traced on a TPU v5e (one
    chip), cut to a window of its own."""
    path = spec.BENCH / "tests" / "data" / "chip_trace.json"
    rec = json.loads(path.read_text())
    ev = rec["events"]
    r = trace.reduce(ev)
    (lo, dur), = [(s, d) for n, s, d in ev["host"] if n == trace.WINDOW]
    (ops,) = ev["device"].values()
    assert r["window_s"] == pytest.approx(dur * 1e-9)
    assert r["busy_s"] == pytest.approx(_busy_by_grid(ops, lo, lo + dur),
                                        abs=2e-6 * len(ops))
    assert r["busy_s"] == pytest.approx(rec["expect"]["busy_s"])
    assert 0 < r["busy_s"] <= r["window_s"]
    idle = sum(r["idle_s_by_host"].values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    kern = [k for k in r["op_s"] if k.startswith("dtans_spmm_pallas")]
    assert kern and r["op_calls"][kern[0]] >= 1


def test_extract_reads_a_profile(tmp_path):
    """A real profile (of the CPU backend here): the benchmark's host
    annotations come out with their times; a CPU has no device plane."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with trace.capture(str(tmp_path)):
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    ext = trace.extract(trace.xplane_file(str(tmp_path)))
    names = [n for n, _, _ in ext["host"]]
    assert names.count(trace.WINDOW) == 1 and "bench.step" in names
    (w,) = [(s, d) for n, s, d in ext["host"] if n == trace.WINDOW]
    (st,) = [(s, d) for n, s, d in ext["host"] if n == "bench.step"]
    assert w[0] <= st[0] and st[0] + st[1] <= w[0] + w[1]
    assert ext["device"] == {}
