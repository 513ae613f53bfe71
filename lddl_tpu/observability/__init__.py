"""Pipeline-wide telemetry: metrics registry, span tracing, exporters.

The observability layer every stage (preprocess, balance, loader,
resilience) reports into. It is **inert by contract**: instrumentation
never raises into the pipeline, never touches any RNG stream, and never
writes into a shard directory — and when disabled (the default) every
hook is a single env-dict lookup (see registry.py / tracing.py).

Arm it with ``LDDL_TPU_METRICS_DIR=/path`` (inherited by worker
processes) or ``observability.configure(dir=...)``; drive a run with
``benchmarks/mock_train.py --metrics-dir``. Metric names are stable API —
the README "Observability" section is the catalog.

Quick tour::

    from lddl_tpu import observability as obs

    obs.configure(dir="/tmp/metrics", periodic=True)
    with obs.span("preprocess.scatter", shard=3):
        ...
    obs.inc("preprocess_docs_total", 128)
    obs.observe("loader_batch_latency_seconds", 0.004)
    obs.set_gauge("loader_padding_efficiency", 0.87)
    print(obs.summary()["padding_efficiency"])
    obs.write_summary()          # summary-*.json + trace flush
"""

from . import alerts, attribution, fleet, series
from .exporters import (
    configure,
    disable,
    export_jsonl,
    export_prom,
    install_signal_flush,
    start_periodic_export,
    stop_periodic_export,
    summary,
    write_summary,
)
from .registry import (
    Counter,
    Gauge,
    Histogram,
    Registry,
    enabled,
    inc,
    metrics_dir,
    observe,
    registry,
    set_gauge,
)
from .tracing import event, flush, span, step_span, trace_path

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "alerts",
    "attribution",
    "configure",
    "disable",
    "enabled",
    "event",
    "export_jsonl",
    "export_prom",
    "fleet",
    "flush",
    "install_signal_flush",
    "inc",
    "metrics_dir",
    "observe",
    "registry",
    "series",
    "set_gauge",
    "span",
    "start_periodic_export",
    "step_span",
    "stop_periodic_export",
    "summary",
    "trace_path",
    "write_summary",
]
