"""Observability: decision tracing, metrics, timeline export, logging.

The telemetry subsystem every scheduling layer emits into — see
``repro.obs.trace`` for the ``TraceSink`` seam and the six decision-event
families, ``repro.obs.metrics`` for the registry and the process's
compile, attention-path and TP-boundary counters,
``repro.obs.collectives`` for the collectives of a compiled step,
``repro.obs.perfetto`` for Chrome-trace/Perfetto export,
``repro.obs.log`` for the shared ``repro`` logger.  This package never
imports the schedulers (they import us), so any later subsystem can emit
into it without cycles.
"""

from repro.obs.collectives import (collectives, count_collectives,
                                   record_collectives)
from repro.obs.log import configure_logging, get_logger
from repro.obs.metrics import (CompileCounter, Counter, Gauge, Histogram,
                               MetricsRegistry, attention_paths,
                               compile_counter,
                               metrics_from_events, pool_metrics,
                               slowdown_metrics, tp_paths)
from repro.obs.perfetto import (cluster_trace, export_cluster_trace,
                                export_pool_trace, pool_trace, write_trace)
from repro.obs.trace import (FAM_ADMISSION, FAM_CLUSTER, FAM_PLACEMENT,
                             FAM_PLANSTORE, FAM_PREEMPTION, FAM_REGION,
                             FAM_SERVICE, FAM_STRATEGY, FAMILIES, NULL_SINK,
                             NullSink, RecordingSink, TraceEvent, TraceSink)

__all__ = [
    "FAM_ADMISSION", "FAM_CLUSTER", "FAM_PLACEMENT", "FAM_PLANSTORE",
    "FAM_PREEMPTION", "FAM_REGION", "FAM_SERVICE",
    "FAM_STRATEGY", "FAMILIES", "NULL_SINK", "NullSink",
    "RecordingSink",
    "TraceEvent", "TraceSink",
    "CompileCounter", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "attention_paths", "compile_counter", "tp_paths",
    "collectives", "count_collectives", "record_collectives",
    "metrics_from_events", "pool_metrics", "slowdown_metrics",
    "cluster_trace", "export_cluster_trace",
    "export_pool_trace", "pool_trace", "write_trace",
    "configure_logging", "get_logger",
]
