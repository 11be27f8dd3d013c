"""The program's own spans on the device trace's clock.

While tracing is on, `repro.obs` enters a `jax.profiler.TraceAnnotation`
for every span, so the spans sit on the host plane of the profile that
`bench.trace.capture` records, on the clock of the device's operations.
`extract` keeps them (names under `PREFIXES`) and each device's "XLA
Modules" line; `reduce` puts every idle gap of the window down to the
innermost span the host was in, and totals device time per jitted module.

    python3 -m bench.spans --workload <cell> --seed <n> --seconds <s> \\
        [--fixture PATH]

makes one traced run of a cell, as ``bench.run --trace 1`` does, and prints
its result line with these reductions added; ``--fixture`` also writes a
quarter second of the window's events to PATH (``bench/tests/data``).
"""

from __future__ import annotations

import argparse
import bisect
import json
import re
import sys

from bench import run, spec, trace

#: Name prefixes of the program's spans (`repro.obs.span`).
PREFIXES = ("engine.", "serving.", "kernels.")
#: The line of a device plane with one event per executed jitted module.
MODULES_LINE = "XLA Modules"
#: Length of the window a fixture keeps, and the span it opens shortly
#: before (an admission, so that the fixture holds every kind of span).
FIXTURE_NS = 250_000_000
FIXTURE_AT = "engine.prefill"

_MODULE_ID = re.compile(r"\([^()]*\)$")


def extract(path: str) -> dict:
    """``{"spans": [[name, start_ns, dur_ns], ...], "modules": {plane:
    [[name, start_ns, dur_ns], ...]}}`` from an ``.xplane.pb``: the
    program's spans on the host planes, the jitted modules per device."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, modules = [], {}
    for plane in data.planes:
        on_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if on_device and line.name == MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    [e.name, int(e.start_ns), int(e.duration_ns)]
                    for e in line.events)
            elif not on_device:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events
                             if e.name.startswith(PREFIXES))
    return {"spans": spans, "modules": modules}


def module_name(name: str) -> str:
    """A module's name without its trailing ``(<id>)``."""
    return _MODULE_ID.sub("", name)


def innermost(spans) -> list[tuple[int, int, str]]:
    """Disjoint, sorted ``(start, end, name)`` pieces of the time that
    the nested ``[name, start, dur]`` spans cover: each piece goes to the
    innermost span over it (the one that started last)."""
    out, stack, t = [], [], None
    for s, e, name in sorted(((s, s + d, n) for n, s, d in spans),
                             key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s if t is None else max(t, s)
        stack.append((e, name))
    while stack:
        end, top = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


class _Idle:
    """Idle time of one device over any interval, from its sorted,
    disjoint gaps."""

    def __init__(self, gaps):
        self.starts = [s for s, _ in gaps]
        self.ends = [e for _, e in gaps]
        self.cum, total = [], 0
        for s, e in gaps:
            total += e - s
            self.cum.append(total)
        self.total = total

    def _before(self, t: int) -> int:
        k = bisect.bisect_right(self.starts, t)
        if k == 0:
            return 0
        return self.cum[k - 1] - (self.ends[k - 1] - min(t, self.ends[k - 1]))

    def within(self, s: int, e: int) -> int:
        return self._before(e) - self._before(s)


def reduce(ext: dict) -> dict | None:
    """Idle time by the innermost program span the host was in, idle
    time inside each span name (its children's included), the spans
    that started in the window, and device time and calls per module.

    ``ext`` holds `bench.trace.extract`'s keys and `extract`'s.  A part of
    a gap that no program span covers goes to the benchmark's annotation
    around it (``bench.step``, ...), else to ``unattributed``, so
    ``idle_s_by_span`` sums to the idle time of ``bench.trace.reduce``.
    Returns None where that returns None; seconds and calls are averaged
    over the devices that ran operations."""
    windows = [(s, s + d) for n, s, d in ext["host"] if n == trace.WINDOW]
    planes = {k: v for k, v in ext["device"].items() if v}
    if len(windows) != 1 or not planes:
        return None
    lo, hi = windows[0]
    idle = []
    for events in planes.values():
        busy = trace.union((max(s, lo), min(s + d, hi)) for _, s, d in events
                           if s + d > lo and s < hi)
        edges = [lo] + [x for se in busy for x in se] + [hi]
        idle.append(_Idle([(edges[i], edges[i + 1])
                           for i in range(0, len(edges), 2)
                           if edges[i + 1] > edges[i]]))
    spans = ext["spans"]
    outer = [h for h in ext["host"] if h[0] != trace.WINDOW]
    by_span, within, calls = {}, {}, {}
    for s, e, name in innermost(spans + outer):
        ns = sum(d.within(s, e) for d in idle)
        if ns:
            by_span[name] = by_span.get(name, 0) + ns
    rest = sum(d.total for d in idle) - sum(by_span.values())
    if rest:
        by_span["unattributed"] = rest
    for name, s, d in spans:
        ns = sum(i.within(max(s, lo), min(s + d, hi)) for i in idle) \
            if s + d > lo and s < hi else 0
        within[name] = within.get(name, 0) + ns
        if lo <= s < hi:
            calls[name] = calls.get(name, 0) + 1
    mod_ns, mod_n = {}, {}
    for plane in planes:
        for name, s, d in ext["modules"].get(plane, ()):
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                key = module_name(name)
                mod_ns[key] = mod_ns.get(key, 0) + b - a
                mod_n[key] = mod_n.get(key, 0) + 1
    n_dev = len(planes)

    def secs(d):
        return {k: v * 1e-9 / n_dev for k, v in d.items()}
    return {"idle_s_by_span": secs(by_span), "idle_s_within": secs(within),
            "span_calls": calls, "module_s": secs(mod_ns),
            "module_calls": {k: v / n_dev for k, v in mod_n.items()}}


def cut(ext: dict, lo: int, hi: int) -> dict:
    """The events of ``ext`` that overlap [lo, hi), with a window of
    their own over exactly that interval."""
    def keep(events):
        return [e for e in events if e[1] < hi and e[1] + e[2] > lo]
    return {"device": {k: keep(v) for k, v in ext["device"].items()},
            "host": [[trace.WINDOW, lo, hi - lo]]
            + [h for h in keep(ext["host"]) if h[0] != trace.WINDOW],
            "spans": keep(ext["spans"]),
            "modules": {k: keep(v) for k, v in ext["modules"].items()}}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--fixture", default=None)
    args = p.parse_args(argv)
    bench = spec.benchmark()
    cell = spec.find(bench["workloads"], args.workload, "workload")
    run.require_chips(cell["chips"])
    run.enable_compile_cache()
    # The harness's traced run, with its profile also read for the spans.
    seen, extract0, reduce0 = {}, trace.extract, trace.reduce

    def extract_all(path):
        seen["ext"] = ext = {**extract0(path), **extract(path)}
        return ext

    def reduce_all(ext):
        r = reduce0(ext)
        return r if r is None else {**r, **reduce(ext)}
    trace.extract, trace.reduce = extract_all, reduce_all
    try:
        observed = run.execute(cell, spec.config_of(bench, cell),
                               spec.traffic_of(cell), args.seed,
                               args.seconds, True, run.T_START)
    finally:
        trace.extract, trace.reduce = extract0, reduce0
    result = run.result_line(bench, cell, observed, True)
    print("bench: " + json.dumps(result.pop("notes")), flush=True)
    t = observed.trace
    if t is not None:
        result["breakdown"]["idle_gaps_by_span"] = trace.top(
            t["idle_s_by_span"], 20)
        result["spans"] = {k: t[k] for k in (
            "idle_s_within", "span_calls", "module_s", "module_calls")}
    if args.fixture and "ext" in seen:
        write_fixture(seen["ext"], cell["name"], args.fixture)
    print(json.dumps(result), flush=True)
    return 0


def write_fixture(ext: dict, cell: str, path: str) -> None:
    """A quarter second of the window, from shortly before the first
    `FIXTURE_AT` span of its second half."""
    (lo, d), = [(s, d) for n, s, d in ext["host"] if n == trace.WINDOW]
    mid = lo + d // 2
    lo = min((s for n, s, _ in ext["spans"]
              if n == FIXTURE_AT and mid <= s < lo + d - FIXTURE_NS),
             default=mid) - FIXTURE_NS // 5
    part = cut(ext, lo, lo + FIXTURE_NS)
    r = trace.reduce(part)
    with open(path, "w") as f:
        json.dump({"cell": cell, "events": part,
                   "expect": {"window_s": r["window_s"],
                              "busy_s": r["busy_s"]}},
                  f, separators=(",", ":"))


if __name__ == "__main__":
    sys.exit(main())
