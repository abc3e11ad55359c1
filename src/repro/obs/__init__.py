"""repro.obs — one observability spine for the whole stack.

Three cooperating components, each usable alone:

  * `obs.metrics`   — process-local metrics registry (counters / gauges /
    histograms with fixed buckets, Prometheus-style labels).  The five
    historic ad-hoc ledgers (`BatchResult.timings`, `dist.service
    .WorkerStats`, `store.StoreStats`, `serve.batcher.batch_log`,
    `launch.preprocess.pipeline_report`) now mirror into it; their
    original attribute surfaces are preserved as thin views so no caller
    breaks.  `snapshot()` is JSON/pickle-safe (it backs the `metrics`
    RPC) and `render()` is Prometheus text exposition.
  * `obs.telemetry` — durable per-chunk JSONL records written MASTER-side
    at `push_result`/`complete` acceptance, so they survive SIGKILLed
    workers; a reader aggregates them into the paper's Figure-style
    per-worker load ledger.
  * `obs.tracing`   — span tracing with a run-level trace id propagated
    through the `repro.dist` RPC surface (worker spans carry the
    master-issued parent id across the pickle boundary), exported as
    Chrome trace-event JSON that loads directly in Perfetto.

Everything is zero-cost-when-off: the disabled registry and the null
tracer are shared no-op objects. With the plan's spans in its path, the
archive pass on a TPU v5e read the same `audio_rate` as without them
(PERF.md §6).
"""
from repro.obs import metrics, telemetry, tracing
from repro.obs.metrics import MetricsRegistry, NullRegistry, get_registry, set_registry
from repro.obs.telemetry import TelemetryWriter, read_records, worker_ledger
from repro.obs.tracing import NULL_TRACER, Tracer, get_tracer, set_tracer, validate_chrome_trace

__all__ = [
    "metrics", "telemetry", "tracing",
    "MetricsRegistry", "NullRegistry", "get_registry", "set_registry",
    "TelemetryWriter", "read_records", "worker_ledger",
    "Tracer", "NULL_TRACER", "get_tracer", "set_tracer",
    "validate_chrome_trace",
]
