"""Zero-dependency observability plane: mergeable metrics + wire traces.

``repro.obs.metrics``  — counters, gauges, fixed-log-bucket histograms
with exact (integer) merges, a process registry, and snapshot algebra.
``repro.obs.trace``    — sampled spans with coordinator->worker id
propagation over the existing frame protocol, and ``Timer``, the one
timer of a layer boundary (profiler annotation + span + histogram).
``repro.obs.dump``     — periodic JSONL dumps + a CI checker.

See README.md in this directory for the model and merge semantics.
"""

from .metrics import (Counter, Gauge, Histogram, Registry, NULL,
                      default, set_default, empty_snapshot,
                      merge_snapshots, snapshot_delta, hist_quantile,
                      hist_sum)
from .trace import (TraceCtx, Span, Tracer, NULL_SPAN, SPANS, Timer)
from . import metrics, trace

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "NULL",
    "default", "set_default", "empty_snapshot", "merge_snapshots",
    "snapshot_delta", "hist_quantile", "hist_sum",
    "TraceCtx", "Span", "Tracer", "NULL_SPAN", "SPANS", "Timer",
    "metrics", "trace",
]
