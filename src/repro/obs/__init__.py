"""repro.obs: metrics and tracing for the serving stack.

The paper's core claim is a *measured* speedup; SMASH makes the same
point structurally — compression only pays when decode time hides behind
the consumer, which nothing can know without instrumentation on the real
execution path. This package is that instrumentation layer:

* **Metrics** (`repro.obs.metrics`): a `MetricsRegistry` of counters,
  gauges and histograms. Histograms keep a bounded reservoir and report
  exact p50/p95/p99 (numpy-compatible linear interpolation) while the
  sample count fits the reservoir; beyond it, seeded reservoir sampling
  keeps the quantiles representative at fixed memory. `snapshot()` is
  lock-free — it copies instrument state without stopping writers.
* **Tracing** (`repro.obs.trace`): a `span()` context manager and
  `event()` emitter recording JSONL for the path in ``$REPRO_TRACE``
  (or `configure_trace(path)`), kept in memory and written when the
  sink closes. Each span is also a `jax.profiler.TraceAnnotation`, so a
  profile holds it on the device trace's clock. With no sink
  configured both cost one predicate check and import nothing.

Instrumented layers: `serving.Engine` (step/prefill/decode/refill wall
time, occupancy, queue depth, TTFT, end-to-end latency, logits bytes
copied to the host), `serving.SparseLinear` + `kernels.ops` (decode
invocations, bytes moved per SpMM and uploaded from the host,
batch-size histogram), and `repro.autotune` (decision-cache
hits/misses, timing dispersion, selection events). `docs/observability.md`
lists every metric name and the trace schema.
"""

from repro.obs.metrics import (NULL, Counter, Gauge, Histogram,
                               MetricsRegistry, default_registry)
from repro.obs.trace import (configure_trace, event, span, trace_active,
                             trace_path)

__all__ = [
    "NULL", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "configure_trace", "default_registry", "event", "span",
    "trace_active", "trace_path",
]
