"""Profiler trace of the measured window, and its reduction to numbers.

`capture` records the window with JAX's profiler; `extract` keeps of the
trace only what the readers need (device operations, the benchmark's own
host annotations) in a small JSON-able form; `reduce` turns that into busy
time, idle gaps attributed to what the host was doing, and time per device
operation.  The reduction is checked against a recorded trace in
``bench/tests/data``.
"""

from __future__ import annotations

import bisect
import contextlib
import glob
import os

#: The line of a device plane with one event per executed operation.
OPS_LINE = "XLA Ops"
#: Prefix of the benchmark's own host annotations.
HOST_PREFIX = "bench."
#: Annotation around the whole measured window.
WINDOW = "bench.window"


@contextlib.contextmanager
def capture(log_dir: str):
    """Profile the block into ``log_dir`` (no Python tracer: it would
    slow the host it measures)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {found}")
    return found[0]


def extract(path: str) -> dict:
    """``{"device": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}`` from an ``.xplane.pb``:
    device operations and the benchmark's host annotations."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device.setdefault(plane.name, []).extend(
                        [_short(e.name), int(e.start_ns), int(e.duration_ns)]
                        for e in line.events)
        else:
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"device": device, "host": host}


def _short(name: str) -> str:
    """An operation's HLO instruction name without its text:
    ``"%fusion.7 = f32[8]{0} fusion(...)"`` -> ``"fusion.7"``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _per_name(events, lo, hi, secs, calls):
    """Add each event's time inside [lo, hi) and its count to its name."""
    for name, s, d in events:
        c = _clip([(s, s + d)], lo, hi)
        if c:
            secs[name] = secs.get(name, 0) + c[0][1] - c[0][0]
            calls[name] = calls.get(name, 0) + 1


def reduce(ext: dict) -> dict | None:
    """Busy and idle time of the devices over the window, time and calls
    per operation, and idle time by the host annotation it fell in.

    Returns None when the trace holds no window or no device operation.
    Seconds and calls are averaged over the devices that ran operations."""
    windows = [(s, s + d) for n, s, d in ext["host"] if n == WINDOW]
    planes = {k: v for k, v in ext["device"].items() if v}
    if len(windows) != 1 or not planes:
        return None
    lo, hi = windows[0]
    busy_ns, gaps = 0, []
    op_ns, op_n = {}, {}
    for plane, events in planes.items():
        merged = union(_clip([(s, s + d) for _, s, d in events], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        _per_name(events, lo, hi, op_ns, op_n)
        edges = [lo] + [x for se in merged for x in se] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = len(planes)
    host = sorted((s, s + d, n) for n, s, d in ext["host"] if n != WINDOW)
    idle_by = {}
    for s, e in gaps:
        for label, ns in _attribute(s, e, host).items():
            idle_by[label] = idle_by.get(label, 0) + ns

    def secs(d):
        return {k: v * 1e-9 / n_dev for k, v in d.items()}

    def per_dev(d):
        return {k: v / n_dev for k, v in d.items()}
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n_dev,
        "op_s": secs(op_ns), "op_calls": per_dev(op_n),
        "idle_s_by_host": secs(idle_by),
    }


def _attribute(s: int, e: int, host) -> dict:
    """Split the idle interval [s, e) among the host annotations that
    overlap it (they do not nest); the rest is ``unattributed``."""
    out, rest = {}, e - s
    i = max(bisect.bisect_left(host, (s,)) - 1, 0)
    while i < len(host) and host[i][0] < e:
        hs, he, n = host[i]
        ov = min(he, e) - max(hs, s)
        if ov > 0:
            out[n] = out.get(n, 0) + ov
            rest -= ov
        i += 1
    if rest > 0:
        out["unattributed"] = out.get("unattributed", 0) + rest
    return out


def top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]
