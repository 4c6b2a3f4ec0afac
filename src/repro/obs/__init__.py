"""repro.obs — array-native metrics, span tracing, export sinks.

The observability subsystem for the co-sim stack: batched engine-side
accumulators (``Collector``, ``PhaseStats``, ``StreamingHistogram``),
a Chrome-trace span tracer (``SpanTracer``), and JSONL/CSV/JSON export
(``EventLog``, ``MetricsReport``).  Everything is opt-in: every entry
point in net/fl/dist/launch takes ``collector=None`` and the disabled
path is bitwise identical to a build without this package.
"""
from repro.obs.export import (  # noqa: F401
    EventLog,
    JsonlSink,
    MetricsReport,
    write_summary_csv,
    write_summary_json,
)
from repro.obs.metrics import (  # noqa: F401
    DEFAULT_DELAY_EDGES,
    DEFAULT_UTIL_EDGES,
    Collector,
    CounterArray,
    GaugeArray,
    PhaseStats,
    StreamingHistogram,
)
from repro.obs.trace import (  # noqa: F401
    SpanTracer,
    load_trace,
    maybe_span,
    validate_trace,
)
