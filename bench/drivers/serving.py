"""Serving driver: the `Engine` with its LM head compressed, under a mix.

`setup` makes the weights on the device from the seed (the head from the
configuration's own head seed), compresses the head through
`Engine.compress_lm_head` (or loads it from the checkout's cache, which
every run after a checkout's first finds), and warms exactly the shapes
the mix will use: one
prefill per prompt of the pool, the pooled decode step and the head kernel
at the mix's slot count.  `window` drives `Engine.submit` and `Engine.step`
for ``seconds``, stamping every output token on the host clock, with the
benchmark's own profiler annotations around each call.  `run` does both,
reads the device's peak memory, frees the program's state, and compares a
sample of finished requests with the reference (`bench.check`); the sample
stays on the run (``compared_requests``) for the control to read.
"""

from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import tempfile
import time

from bench import check, headcache, spec, trace
from bench.generator import Traffic

#: How long past the window an open-loop run waits for the first token of
#: a request that was due inside it; one still waiting then has failed.
LATE_S = 60.0


class Run:
    """What one run observed; the metric readers take their numbers from
    it (``bench/metrics/<name>.py``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1


class _CompileCounter:
    """Counts traces and compiles while ``on`` is set."""

    def __init__(self):
        self.on, self.count = False, 0

    def __call__(self, name, *_a, **_k):
        if self.on and name.startswith("/jax/core/compile/"):
            self.count += 1


def setup(cfg: dict, mix: dict, seed: int, t_start: float) -> Run:
    """Weights, head and engine of a run, with the mix's shapes warm."""
    import jax

    from repro import obs
    from repro.serving.engine import Engine

    ref_mod = spec.load_module("reference", cfg["architecture"])
    arch = spec.load_module("system", cfg["architecture"]).arch_config(cfg)
    hc = cfg["head"]
    phases = {"start_s": time.perf_counter() - t_start}
    weights = ref_mod.make_weights(cfg, seed)
    jax.block_until_ready(weights)
    phases["weights_s"] = time.perf_counter() - t_start
    head, head_info = headcache.load_or_build(
        cfg, lambda: Engine.compress_lm_head(
            arch, weights, sparsity=hc["sparsity"],
            value_bits=hc["value_bits"], lane_width=hc["lane_width"]))
    phases["head_s"] = time.perf_counter() - t_start
    engine = Engine(arch, weights, slots=mix["slots"], max_seq=mix["max_seq"],
                    sparse_head=head, greedy=True,
                    metrics=obs.MetricsRegistry())
    # Every prompt of the pool once (its prefill shape); that also
    # compiles the pooled decode step and the head at `slots` columns.
    for p in Traffic(mix, arch.vocab, seed, 1.0).pool:
        engine.submit(p, 1)
    engine.run_until_drained()
    phases["warm_s"] = time.perf_counter() - t_start
    return Run(cfg=cfg, mix=mix, seed=seed, vocab=arch.vocab,
               engine=engine, weights=weights, head=head,
               head_cache=head_info, phases=phases, ref_mod=ref_mod)


def _stamp(inflight: list, finished: list, now: float) -> None:
    still = []
    for r in inflight:
        while len(r.token_times) < len(r.handle.out):
            r.token_times.append(now)
        (finished if r.handle.done else still).append(r)
    inflight[:] = still


def window(system: Run, traffic: Traffic, seconds: float, traced: bool,
           t_start: float) -> Run:
    """Drive the engine with ``traffic``: ``lead_s`` of the mix first, so
    that the window opens on a system in its steady state, then the
    measured ``seconds``.  An open loop then goes on (arrivals included)
    until every request due in the window has its first token, or
    `LATE_S` has passed."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro import obs
    from repro.serving.engine import AdmissionError

    engine, slots = system.engine, system.mix["slots"]
    lead = float(system.mix.get("lead_s", 0.0))
    inflight, finished, submitted = [], [], []

    def submit(r, now):
        r.submitted = now
        submitted.append(r)
        try:
            r.handle = engine.submit(r.prompt, r.max_new_tokens)
        except AdmissionError:
            return              # never served: counts as failed
        inflight.append(r)

    open_loop = traffic.open_loop
    if not open_loop:
        # A closed backlog: every slot busy from the start.
        while len(engine.queue) < 2 * slots:
            submit(traffic.pop(), time.perf_counter())
        engine.step()
        _stamp(inflight, finished, time.perf_counter())
    compiles = _CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    span_file = os.path.join(trace_dir, "spans.jsonl") if traced else None
    tracer = trace.capture(trace_dir) if traced else contextlib.nullcontext()

    steps = []
    state = "lead"                  # lead -> window -> late
    with tracer:
        if traced:
            obs.configure_trace(span_file)
        t_go = time.perf_counter()          # arrivals are due from here
        setup_s = t_go - t_start
        t0, t_end = t_go + lead, t_go + lead + seconds
        while True:
            now = time.perf_counter()
            if state == "lead" and now >= t0:
                state, t0, wall_t0 = "window", now, time.time()
                t_end = t0 + seconds
                compiles.on = True
                # Made here: an annotation is recorded only if the
                # profiler is already on when it is made.
                win = TraceAnnotation(trace.WINDOW)
                win.__enter__()
            if state == "window" and now >= t_end:
                state = "late"
                win.__exit__(None, None, None)
                compiles.on = False
                t1 = steps[-1][1] if steps else now
                wall_t1 = wall_t0 + (t1 - t0)
            if state == "late" and (
                    not open_loop or now > t_end + LATE_S or all(
                        r.token_times or r.handle is None
                        for r in submitted if r.due + t_go >= t0
                        and r.due + t_go <= t_end)):
                break
            with TraceAnnotation("bench.submit"):
                if open_loop:
                    while traffic.peek().due + t_go <= now:
                        submit(traffic.pop(), now)
                else:
                    while len(engine.queue) < slots:
                        submit(traffic.pop(), now)
            if engine.queue or any(r is not None for r in engine.active):
                s0 = time.perf_counter()
                with TraceAnnotation("bench.step"):
                    engine.step()
                s1 = time.perf_counter()
                if state == "window":
                    steps.append((s0, s1))
                _stamp(inflight, finished, s1)
            else:
                with TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(
                        traffic.peek().due + t_go - now, 0.002)))
        t_stop = time.perf_counter()
        if traced:
            obs.configure_trace(None)
    jax.monitoring.unregister_event_duration_listener(compiles)

    ext = spans = None
    if traced:
        ext = trace.extract(trace.xplane_file(trace_dir))
        with open(span_file) as f:
            spans = [sp for sp in map(json.loads, f)
                     if wall_t0 <= sp["ts"]
                     and sp["ts"] + sp.get("dur_s", 0) <= wall_t1]
        shutil.rmtree(trace_dir, ignore_errors=True)
    if open_loop:
        due = [r for r in submitted if t0 <= r.due + t_go <= t_end]
    else:
        due = [r for r in submitted if r.token_times
               and r.token_times[0] <= t1 and r.token_times[-1] >= t0]
    return Run(
        seconds=seconds, setup_s=setup_s, t0=t0, t1=t1, t_stop=t_stop,
        t_go=t_go, open_loop=open_loop, requests=submitted, due=due,
        finished=finished, steps=steps, compiles=compiles.count,
        trace=trace.reduce(ext) if ext else None,
        spans=spans, failed=sum(1 for r in due if not r.token_times),
        lateness=[r.submitted - (t_go + r.due) for r in submitted
                  if open_loop])


class _Served:
    """A finished request as the comparison reads it."""

    def __init__(self, r):
        self.index, self.prompt = r.index, r.prompt
        self.out = list(r.handle.out)


def run(cell: dict, cfg: dict, mix: dict, seed: int, seconds: float,
        traced: bool, t_start: float) -> Run:
    import jax

    system = setup(cfg, mix, seed, t_start)
    traffic = Traffic(mix, system.vocab, seed,
                      float(mix.get("lead_s", 0.0)) + seconds)
    seen = window(system, traffic, seconds, traced, t_start)
    stats = jax.devices()[0].memory_stats() or {}
    head = system.head
    seen.cell, seen.cfg, seen.mix, seen.seed = cell, cfg, mix, seed
    seen.memory_peak = int(stats.get("peak_bytes_in_use", 0))
    seen.head = {"nnz": int(head.mat.nnz), "bytes": int(head.compressed_bytes),
                 "d_in": int(head.d_in), "d_out": int(head.d_out),
                 "batch": int(mix["slots"])}
    seen.head_cache, seen.phases = system.head_cache, system.phases
    ref_mod = system.ref_mod
    seen.flops_per_token = lambda c: ref_mod.flops_per_token(cfg, c)
    # Free the program's state before the reference takes the device.
    system = head = None
    gc.collect()

    t_ref = time.perf_counter()
    last = seen.t_stop if seen.open_loop else seen.t1
    done = [_Served(r) for r in seen.finished if r.token_times[-1] <= last]
    reference = ref_mod.Reference(cfg, seed)
    chosen = check.sample(done, seed, mix["check"])
    gaps = check.served_gaps(reference, chosen, mix["max_seq"])
    seen.compared_requests = chosen
    seen.verdict = check.judge(gaps, cfg["correct"])
    reference = None
    gc.collect()
    seen.phases["reference_s"] = time.perf_counter() - t_ref
    return seen
