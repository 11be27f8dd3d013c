"""The metric readers' arithmetic, on runs built by hand."""

import numpy as np
import pytest

from bench import spec
from bench.drivers.serving import Run
from bench.generator import Request

V5E = "TPU v5 lite"


def read(name, run):
    return spec.load_module("metrics", name).read(run)


def _req(i, due, times, prompt_len=10):
    r = Request(index=i, due=due, prompt=np.zeros(prompt_len, np.int32),
                max_new_tokens=len(times))
    r.token_times = list(times)
    return r


def _run(requests, open_loop=True, **kw):
    base = dict(t0=100.0, t1=110.0, t_stop=112.0, t_go=100.0,
                open_loop=open_loop,
                requests=requests, setup_s=42.5, spans=None, trace=None,
                device_kind=V5E)
    base.update(kw)
    base.setdefault("due", [r for r in requests if r.due <= 10])
    return Run(**base)


def test_ttft_is_timed_from_due_over_every_request():
    # 100 requests due at 0..99 x 0.1 s; each gets its first token 10 ms
    # after it was due, except every 10th, which waits 1 s.
    reqs = [_req(i, 0.1 * i, [100.0 + 0.1 * i + (1.0 if i % 10 == 0
                                                   else 0.01)])
            for i in range(100)]
    waits = [1.0 if i % 10 == 0 else 0.01 for i in range(100)]
    assert read("ttft_p95_ms", _run(reqs)) == pytest.approx(
        np.percentile(waits, 95) * 1e3)
    # Not from submission: a late submit does not shorten the wait.
    for r in reqs:
        r.submitted = 100.0 + r.due + 0.5
    assert read("ttft_p95_ms", _run(reqs)) == pytest.approx(
        np.percentile(waits, 95) * 1e3)


def test_ttft_counts_a_request_that_never_got_a_token():
    reqs = [_req(i, 0.1 * i, [100.0 + 0.1 * i + 0.01]) for i in range(19)]
    reqs.append(_req(19, 5.0, []))
    waits = [0.01] * 19 + [112.0 - 105.0]
    assert read("ttft_p95_ms", _run(reqs)) == pytest.approx(
        np.percentile(waits, 95) * 1e3)


def test_ttft_is_not_reported_for_a_backlog():
    assert read("ttft_p95_ms", _run([_req(0, 0, [101.0])],
                                    open_loop=False)) is None


def test_itl_pools_every_gap_of_every_request():
    """One request with many short gaps and one with few long ones: the
    percentile is over the pooled gaps, not a median of per-request
    percentiles."""
    a = _req(0, 0, 100.0 + 0.02 * np.arange(200))      # 199 gaps of 20 ms
    b = _req(1, 0, 100.0 + 0.5 * np.arange(12))        # 11 gaps of 500 ms
    gaps = [0.02] * 199 + [0.5] * 11
    got = read("itl_p95_ms", _run([a, b]))
    assert got == pytest.approx(np.percentile(gaps, 95) * 1e3)
    per_request = np.median([np.percentile([0.02] * 199, 95),
                             np.percentile([0.5] * 11, 95)]) * 1e3
    assert got != pytest.approx(per_request)


def test_itl_keeps_only_gaps_that_end_in_the_window():
    a = _req(0, 0, [99.0, 100.5, 101.0, 110.5])
    assert read("itl_p95_ms", _run([a])) == pytest.approx(
        np.percentile([1.5, 0.5], 95) * 1e3)


def test_output_rate_counts_tokens_in_the_window():
    a = _req(0, 0, [99.5, 101.0, 102.0, 111.0])
    b = _req(1, 0, [105.0, 106.0, 107.0])
    assert read("output_tok_s", _run([a, b])) == pytest.approx(5 / 10.0)
    assert read("setup_s", _run([a])) == 42.5


def _head():
    return {"nnz": 5_662_310, "bytes": 10_167_188, "d_in": 576,
            "d_out": 49152, "batch": 32}


def test_roofline_counts_and_bound():
    mod = spec.load_module("metrics", "dtans_spmm_roofline")
    h, pk = _head(), spec.peaks_of(V5E)
    ops = 2 * h["nnz"] * 32
    nbytes = h["bytes"] + 4 * 32 * (576 + 49152)
    assert mod.bound_s(h, pk) == pytest.approx(nbytes / 819e9)
    assert nbytes / 819e9 > ops / 197e12          # bytes bound the head
    trace = {"op_s": {mod.KERNEL: 0.5, "fusion.1": 0.3},
             "op_calls": {mod.KERNEL: 100, "fusion.1": 7}}
    got = read("dtans_spmm_roofline", _run([], trace=trace, head=h))
    assert got == pytest.approx(100 * 100 * (nbytes / 819e9) / 0.5)
    assert read("dtans_spmm_roofline", _run(
        [], trace={"op_s": {"fusion.1": 1.0}, "op_calls": {"fusion.1": 1}},
        head=h)) is None
    assert read("dtans_spmm_roofline", _run([], head=h)) is None


def test_mfu_counts_dense_head_and_context():
    cfg = spec.load_json(spec.BENCH / "configs" / "smollm-135m.json")
    ref = spec.load_module("reference", cfg["architecture"])
    per_layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536
    assert ref.flops_per_token(cfg, 100) == 2.0 * (
        30 * per_layer + 576 * 49152) + 4.0 * 30 * 576 * 100
    a = _req(0, 0, [101.0, 102.0, 111.0], prompt_len=7)
    spans = [{"name": "engine.step", "dur_s": 0.25},
             {"name": "engine.prefill", "dur_s": 0.1, "prompt_len": 7},
             {"name": "engine.step", "dur_s": 0.25}]
    run = _run([a], spans=spans,
               flops_per_token=lambda c: ref.flops_per_token(cfg, c))
    prefill = sum(ref.flops_per_token(cfg, c) for c in range(1, 7))
    want = (ref.flops_per_token(cfg, 7) + ref.flops_per_token(cfg, 8)
            + prefill) / (0.5 * 197e12) * 100
    assert read("decode_step.mfu", run) == pytest.approx(want)
    assert read("decode_step.mfu", _run([a])) is None


def test_host_span_readers():
    spans = [{"name": "engine.step", "id": 1, "parent": None, "dur_s": 0.030},
             {"name": "engine.refill", "id": 2, "parent": 1, "dur_s": 0.004},
             {"name": "engine.decode", "id": 3, "parent": 1, "dur_s": 0.020},
             {"name": "engine.step", "id": 4, "parent": None, "dur_s": 0.022},
             {"name": "engine.refill", "id": 5, "parent": 4, "dur_s": 0.001},
             {"name": "engine.decode", "id": 6, "parent": 4, "dur_s": 0.019},
             {"name": "serving.sparse_apply", "id": 7, "parent": 6,
              "dur_s": 0.002},
             {"name": "serving.sparse_apply", "id": 8, "parent": 3,
              "dur_s": 0.004}]
    run = _run([], spans=spans)
    assert read("engine.host_ms_per_step", run) == pytest.approx(4.0)
    assert read("head.host_ms_per_call", run) == pytest.approx(3.0)
    assert read("engine.host_ms_per_step", _run([])) is None


def test_idle_share():
    run = _run([], trace={"busy_s": 7.5, "window_s": 10.0})
    assert read("device.idle_share", run) == pytest.approx(25.0)
    assert read("device.idle_share", _run([])) is None
