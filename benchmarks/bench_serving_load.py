"""Serving load benchmark: seeded Poisson arrivals into the Engine,
dense head vs entropy-coded compressed head.

ROADMAP item 3's complaint is structural: the Engine is
continuous-batching-lite and *nothing measures throughput under load* —
there is no number a sharding or scheduler PR could claim to have
improved. This benchmark is that number. A seeded Poisson arrival
process (exponential inter-arrival gaps against the wall clock) feeds
requests into two engines built from the same params — one serving the
dense LM head, one the pruned + dtANS-compressed head through the fused
SpMM path — and reports, per head:

  * tokens/sec over the whole run (arrival to drain),
  * p50/p99 step latency (from the engine's own ``engine.step_s``
    reservoir histogram — the same numbers a production scrape reads),
  * mean slot occupancy, TTFT and end-to-end latency percentiles.

It also measures the *instrumentation overhead* the obs layer adds to
`Engine.step` with no trace sink configured, by timing an identical
drain with a real `MetricsRegistry` against one with `obs.NULL`
(every instrument a no-op). The acceptance bar is < 2%; the measured
number is written into the JSON so regressions are visible per PR.

Everything lands in ``BENCH_serving.json`` at the repo root (via
``benchmarks/run.py --only load``) — the first ``BENCH_*.json`` of the
repo, so every future PR has a perf trajectory to compare against.
Absolute numbers are CPU-interpret harness numbers, not TPU claims;
the *dense/compressed ratio* and the trajectory across PRs are the
signal.
"""

from __future__ import annotations

import json
import os
import platform
import time

import numpy as np

DEFAULT_BENCH_JSON = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "BENCH_serving.json")


def _percentiles(xs, qs=(50, 99)):
    if not len(xs):
        return {f"p{q}": float("nan") for q in qs}
    return {f"p{q}": float(np.percentile(np.asarray(xs), q)) for q in qs}


def _drive_poisson(eng, *, rng, rate_per_s: float,
                   prompt_lens, max_new_tokens: int, vocab: int,
                   max_steps: int):
    """Feed a seeded Poisson schedule into ``eng`` against the wall
    clock and drain it; returns the per-run report dict.

    Arrival times are cumulative exponential gaps drawn once up front
    (seeded — the dense and compressed runs see the *same* schedule).
    ``prompt_lens`` gives request k a prompt of ``prompt_lens[k]``
    tokens — a constant list is the uniform workload, a cycling
    {1, 3, 7, 12} list is the mixed workload that exercises per-slot
    positions and mid-flight refills. The loop submits every request
    whose arrival time has passed, steps the engine while it has work,
    and sleeps to the next arrival when idle (virtual idle time still
    counts toward wall time, exactly like a real server waiting on
    traffic).
    """
    n_requests = len(prompt_lens)
    schedule = np.cumsum(rng.exponential(1.0 / rate_per_s,
                                         size=n_requests))
    prompts = [rng.integers(0, vocab, size=int(n))
               for n in prompt_lens]
    reqs = []
    step_times = []
    steps = 0
    t0 = time.perf_counter()
    i = 0
    while i < n_requests or eng.queue or any(r is not None
                                             for r in eng.active):
        now = time.perf_counter() - t0
        while i < n_requests and schedule[i] <= now:
            reqs.append(eng.submit(prompts[i], max_new_tokens))
            i += 1
        if not (eng.queue or any(r is not None for r in eng.active)):
            # Idle pool, future arrivals: wait for the next one instead
            # of spinning empty steps.
            time.sleep(max(min(schedule[i] - now, 0.05), 0.0))
            continue
        s0 = time.perf_counter()
        eng.step()
        step_times.append(time.perf_counter() - s0)
        steps += 1
        if steps >= max_steps:
            raise RuntimeError(
                f"load run exceeded max_steps={max_steps} before "
                f"draining — results would be truncated")
    wall = time.perf_counter() - t0

    snap = eng.metrics.snapshot()
    h = snap["histograms"]
    toks = sum(len(r.out) for r in reqs)
    done = sum(r.done for r in reqs)
    ttfts = [r.t_first - r.t_submit for r in reqs
             if r.t_first is not None and r.t_submit is not None]
    e2es = [r.t_done - r.t_submit for r in reqs
            if r.t_done is not None and r.t_submit is not None]
    step_h = h.get("engine.step_s", {})
    return {
        "requests": int(done),
        "requests_submitted": int(len(reqs)),
        "truncations": int(snap["counters"].get(
            "engine.drain_truncations", 0)),
        "prompt_lens": [int(n) for n in prompt_lens],
        "tokens": int(toks),
        "wall_s": float(wall),
        "tokens_per_sec": float(toks / wall) if wall > 0 else 0.0,
        "steps": int(steps),
        # Step latency from the engine's own metrics registry (what a
        # production scrape would read) — bench-side timings agree but
        # include numpy bookkeeping.
        "p50_step_s": step_h.get("p50", float("nan")),
        "p99_step_s": step_h.get("p99", float("nan")),
        "mean_step_s": step_h.get("mean", float("nan")),
        "occupancy_mean": h.get("engine.occupancy", {}).get(
            "mean", float("nan")),
        "queue_depth_last": snap["gauges"].get("engine.queue_depth", 0.0),
        "ttft_s": _percentiles(ttfts),
        "e2e_s": _percentiles(e2es),
        "prefill_s": {"mean": h.get("engine.prefill_s", {}).get(
            "mean", float("nan"))},
        "decode_s": {"mean": h.get("engine.decode_s", {}).get(
            "mean", float("nan"))},
    }


def _instr_cost_per_step(metrics, iters: int = 20_000) -> float:
    """Seconds of pure instrumentation work per `Engine.step` against
    ``metrics``: exactly the instrument sequence `step` executes — 3
    disabled-span entries, 7 histogram observes, 3 counter adds, 2
    gauge sets (no trace sink)."""
    from repro import obs

    hs = [metrics.histogram(f"oh.h{i}") for i in range(7)]
    cs = [metrics.counter(f"oh.c{i}") for i in range(3)]
    gs = [metrics.gauge(f"oh.g{i}") for i in range(2)]
    t0 = time.perf_counter()
    for _ in range(iters):
        with obs.span("oh.step"):
            with obs.span("oh.refill"):
                pass
            with obs.span("oh.decode"):
                pass
        for h in hs:
            h.observe(0.001)
        for c in cs:
            c.add(1)
        for g in gs:
            g.set(1.0)
    return (time.perf_counter() - t0) / iters


def _measure_overhead(make_engine, *, rng, n_requests: int,
                      prompt_len: int, max_new_tokens: int, vocab: int):
    """Instrumentation overhead of `Engine.step` with no trace sink.

    Two views: (1) *direct* — microbenchmark the exact per-step
    instrument sequence with real instruments vs `obs.NULL` no-ops and
    divide the delta by the median step time (the headline number: the
    added work is ~µs on a ~ms step, far below the run-to-run variance
    of whole drains, so an end-to-end A/B alone would just report that
    variance with either sign); (2) *end-to-end* — alternating measured
    drains of otherwise identical engines, as a cross-check that
    nothing outside the instrument sequence regressed.

    Returns ``(on_s, off_s, overhead_fraction, direct_cost_s)`` where
    ``overhead_fraction = (direct real − direct null) / off_s``."""
    from repro import obs

    prompts = [rng.integers(0, vocab, size=prompt_len)
               for _ in range(n_requests)]

    def drained(eng):
        for p in prompts:
            eng.submit(p, max_new_tokens)
        times = []
        while eng.queue or any(r is not None for r in eng.active):
            s0 = time.perf_counter()
            eng.step()
            times.append(time.perf_counter() - s0)
        eng.finished.clear()
        return times

    # Each Engine owns fresh `jax.jit` closures, so the warmup drain
    # must happen per engine — a warm sibling engine absorbs nothing.
    eng_off = make_engine(metrics=obs.NULL)
    eng_on = make_engine(metrics=obs.MetricsRegistry())
    drained(eng_off)
    drained(eng_on)
    # Alternate rounds so machine drift hits both arms equally; the
    # min of per-round medians is the robust estimator against
    # interference (noise only ever adds time).
    off_meds, on_meds = [], []
    for _ in range(5):
        off_meds.append(float(np.median(drained(eng_off))))
        on_meds.append(float(np.median(drained(eng_on))))
    off_s, on_s = min(off_meds), min(on_meds)

    cost = (_instr_cost_per_step(obs.MetricsRegistry())
            - _instr_cost_per_step(obs.NULL))
    cost = max(cost, 0.0)
    frac = cost / off_s if off_s > 0 else 0.0
    return on_s, off_s, frac, cost


def run(small: bool = False, seed: int = 0,
        bench_json: str | None = DEFAULT_BENCH_JSON):
    """Benchmark rows (for ``benchmarks.run`` CSV) + BENCH_serving.json.

    ``bench_json=None`` skips the file write (unit tests).
    """
    import jax

    from repro import obs
    from repro.backend import device_label
    from repro.configs import get_smoke
    from repro.models import api
    from repro.serving.engine import Engine

    if small:
        vocab, slots, n_requests = 48, 3, 6
        prompt_len, max_new, rate = 3, 4, 8.0
    else:
        vocab, slots, n_requests = 128, 4, 16
        prompt_len, max_new, rate = 6, 8, 4.0
    cfg = get_smoke("smollm-135m").with_(vocab=vocab)
    params = api.init_params(cfg, jax.random.PRNGKey(seed))
    sparse_head = Engine.compress_lm_head(cfg, params, sparsity=0.8,
                                          value_bits=6, lane_width=32)

    def make_engine(head=None, metrics=None):
        return Engine(cfg, params, slots=slots, max_seq=64,
                      sparse_head=head,
                      metrics=metrics if metrics is not None
                      else obs.MetricsRegistry())

    # Mixed workload: prompt lengths cycle {1, 3, 7, 12} across the
    # arrival schedule, so slots decode at genuinely different
    # positions and every mid-flight refill prefills next to live
    # requests — the workload the per-slot scheduler exists for (the
    # uniform workload cannot distinguish per-slot positions from the
    # old shared-position decode).
    mixed_lens = tuple(
        (1, 3, 7, 12) * ((n_requests + 3) // 4))[:n_requests]

    def warmed_engine(head, lens):
        """Fresh engine with every distinct prompt length jit-traced
        (prefill retraces per length; the measured run should time
        steady-state steps, not tracing)."""
        eng = make_engine(head=head)
        wrng = np.random.default_rng(seed + 7)
        for ln in sorted(set(lens)):
            eng.submit(wrng.integers(0, vocab, size=int(ln)), 2)
        eng.run_until_drained()
        return eng

    results = {}
    for label, head, lens in (
            ("dense", None, (prompt_len,) * n_requests),
            ("compressed", sparse_head, (prompt_len,) * n_requests),
            ("dense_mixed", None, mixed_lens),
            ("compressed_mixed", sparse_head, mixed_lens)):
        # Same seed => same arrival schedule and prompts for both heads.
        eng = warmed_engine(head, lens)
        rng = np.random.default_rng(seed)
        results[label] = _drive_poisson(
            eng, rng=rng, rate_per_s=rate, prompt_lens=lens,
            max_new_tokens=max_new, vocab=vocab, max_steps=10_000)

    on_s, off_s, frac, cost = _measure_overhead(
        lambda metrics: make_engine(head=sparse_head, metrics=metrics),
        rng=np.random.default_rng(seed + 1), n_requests=max(slots, 2),
        prompt_len=prompt_len, max_new_tokens=max_new, vocab=vocab)
    results["obs_overhead"] = {
        "instr_cost_per_step_s": cost,
        "overhead_fraction": frac,
        "step_s_instrumented_e2e": on_s,
        "step_s_null_registry_e2e": off_s,
        "e2e_delta_fraction": (on_s - off_s) / off_s if off_s else 0.0,
        "trace_sink": False,
        "budget_fraction": 0.02,
    }

    dev = device_label()
    doc = {
        "bench": "serving_load",
        "meta": {
            "seed": seed, "small": bool(small), "arch": "smollm-135m",
            "vocab": vocab, "slots": slots, "n_requests": n_requests,
            "prompt_len": prompt_len, "max_new_tokens": max_new,
            "mixed_prompt_lens": [int(n) for n in mixed_lens],
            "arrival_rate_per_s": rate,
            "sparsity": 0.8,
            "head_compression_vs_dense":
                float(sparse_head.compression_vs_dense),
            "interpret_mode": dev["interpret"],
            "device": {k: dev[k] for k in ("platform", "kind", "count")},
            "platform": platform.platform(),
        },
        **results,
    }
    if bench_json:
        with open(bench_json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)

    rows = []
    for label in ("dense", "compressed", "dense_mixed",
                  "compressed_mixed"):
        r = results[label]
        rows.append((
            f"load/{label}", r["mean_step_s"] * 1e6,
            f"tok_s={r['tokens_per_sec']:.2f};"
            f"p50_step_ms={r['p50_step_s'] * 1e3:.2f};"
            f"p99_step_ms={r['p99_step_s'] * 1e3:.2f};"
            f"occ={r['occupancy_mean']:.2f};"
            f"reqs={r['requests']}/{r['requests_submitted']}"))
    rel = (results["compressed"]["tokens_per_sec"]
           / max(results["dense"]["tokens_per_sec"], 1e-12))
    rows.append(("load/compressed_vs_dense", 0.0,
                 f"tok_s_ratio={rel:.3f}"))
    rows.append(("load/obs_overhead", on_s * 1e6,
                 f"overhead={frac * 100:.2f}%;budget=2%"))
    if bench_json:
        rows.append(("load/bench_json", 0.0, bench_json))
    return rows


if __name__ == "__main__":
    for row in run(small=True):
        print(",".join(str(x) for x in row))
