"""Structured telemetry: jit-safe metrics, spans, JSONL sinks.

See docs/observability.md for the metric taxonomy, the JSONL schema
and the metrics-don't-perturb-training contract.  Importing this
package registers the process's compile counter
(:mod:`repro.obs.compiles`).
"""
from repro.obs import compiles
from repro.obs.console import Console, fmt_metrics           # noqa: F401
from repro.obs.hist import (FixedHistogram,                  # noqa: F401
                            LATENCY_EDGES_S, log_edges)
from repro.obs.metrics import (MetricSpec, counter_add,      # noqa: F401
                               flush, gauge_max, gauge_set,
                               hist_observe)
from repro.obs.profiler import ProfileWindow                 # noqa: F401
from repro.obs.runlog import RunTelemetry                    # noqa: F401
from repro.obs.sink import (KINDS, SCHEMA, JsonlSink,        # noqa: F401
                            iter_records, read_records,
                            validate_record)
from repro.obs.spans import SpanClock                        # noqa: F401
from repro.obs.summary import (render, summarize,            # noqa: F401
                               summarize_file)

compiles.install()
