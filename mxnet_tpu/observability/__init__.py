"""mxnet_tpu.observability — unified runtime telemetry.

One low-overhead, thread-safe core (ring-buffer span recorder + named
counters/gauges, ``core.py``) feeds three exporters (``export.py``):
chrome://tracing JSON (merged into ``profiler.dump()``), an MXNet-style
aggregate percentile table (``profiler.dumps(aggregate=True)``), and a
Prometheus textfile for scraping long runs. ``recompile.py`` watches
jax.monitoring compile events and flags silent retraces with the
argument signature that caused them.

Two gates (``core.py``). Spans record under ``MXNET_OBS=1``,
``mx.profiler.set_state('run')`` or any live ``jax.profiler`` trace; under
a trace they are ``mx.<name>`` annotations on the profiler's host
timeline, beside the device's, and feed ``core.span_totals()``. Everything
else (counters, gauges, histograms, instants, flows, the recompile
detector) records under ``MXNET_OBS=1`` or the profiler state only. With
both off every instrumentation site reduces to one guarded branch — the
hot paths (kvstore dispatch, trainer step, io.next) stay within noise
(<2%, benchmark/allreduce_overlap_bench.py). The operator's recipe
(``set_state('run')``, N steps, ``set_state('stop')``,
``dumps(aggregate=True)``) is in docs/OBSERVABILITY.md.

Start-up keeps its own record whatever the gates say, on cold paths
only: the compile ledger (``recompile.summary()``: every trace, lowering
and executable build by jax's program name, with the persistent cache's
hits, misses and load seconds), the calls inside which it moved
(``core.cold_totals()``) and the ``startup.*`` spans
(docs/OBSERVABILITY.md "Start-up").

Instrumented out of the box: Trainer/Module step phases (forward /
backward / allreduce / update), KVStore push/pull/pushpull_fused
(per-bucket bytes, dtype lane, dispatch counts, wall time), the io.py
iterators (batch latency, prefetch wait), and the CachedOp/Executor
jit boundaries (compile spans + retrace attribution).

Multi-process jobs get the distributed half (``dist.py``,
``watchdog.py``): rank-tagged events, rank-suffixed dumps merged into
one per-rank-lane trace on a barrier-aligned timebase
(``merge_traces`` / ``tools/obs_merge.py``), cross-rank step-phase
straggler detection (``MXNET_OBS_SKEW_EVERY`` /
``MXNET_OBS_STRAGGLER_FACTOR``), and a collective hang watchdog that
dumps a post-mortem after ``MXNET_OBS_COLLECTIVE_TIMEOUT`` seconds
instead of hanging silently.

Serving gets the request-level half (``histogram.py``, ``slo.py``,
``http.py``): bounded-memory log-bucketed latency histograms
(``serving.ttft_ms``/``itl_ms``/``e2e_ms``/``queue_ms``, bucket-wise
mergeable across ranks), per-request lifecycle spans + chrome-trace
flow chains threaded through the ContinuousBatcher, ``MXNET_OBS_SLO``
violation counters with a rolling ``serving.slo_attainment`` gauge,
and a ``MXNET_OBS_HTTP`` live ``/metrics`` + ``/healthz`` scrape
endpoint (docs/OBSERVABILITY.md "Serving observability").
"""

from . import chaos
from . import core
from . import dist
from . import integrity
from . import events
from . import export
from . import flight
from . import histogram
from . import hlo
from . import http
from . import sideband
from . import slo
from . import membudget
from . import attribution
from . import profile_store
from . import costmodel
from . import goodput
from . import recompile
from . import timeseries
from . import watchdog
from .attribution import (ops_enabled, format_ops_table,
                          compare_summaries)
from .attribution import summary as ops_summary
from .core import (enabled, active, set_enabled, span, span_totals,
                   cold_totals, counter, gauge, record_span, record_instant, record_flow, records,
                   counters, dropped, reset)
from .core import histogram as get_histogram
from .histogram import Histogram
from .http import start as start_http_server
from .http import stop as stop_http_server
from .dist import (merge_traces, detect_stragglers, skew_summary,
                   exchange_phase_stats)
from .export import (chrome_trace, dump_chrome_trace, aggregate,
                     aggregate_table, prometheus_text, write_prometheus)
from .recompile import get_detector, note_call, record_retrace
from .events import event
from .flight import record_incident, note_exit
from .goodput import (compute_ledger, critical_path, elastic_downtime,
                      note_step_commit)
from .watchdog import get_watchdog

# chain the flight recorder's unhandled-exception hook when telemetry
# is on (one guarded branch — PR 2 contract — when MXNET_OBS is unset)
if core.enabled():
    flight.install()

__all__ = ["chaos", "core", "dist", "events", "export", "flight",
           "goodput", "compute_ledger", "critical_path",
           "elastic_downtime", "note_step_commit",
           "histogram", "hlo",
           "http", "sideband", "slo", "membudget", "attribution",
           "integrity", "recompile", "timeseries",
           "event", "record_incident", "note_exit",
           "watchdog", "ops_enabled", "format_ops_table",
           "compare_summaries", "ops_summary", "enabled", "active",
           "set_enabled", "span", "span_totals", "cold_totals", "counter",
           "gauge", "get_histogram",
           "Histogram", "record_span", "record_instant", "record_flow",
           "records", "counters", "dropped", "reset",
           "start_http_server", "stop_http_server",
           "chrome_trace", "dump_chrome_trace", "aggregate",
           "aggregate_table", "prometheus_text", "write_prometheus",
           "get_detector", "note_call", "record_retrace", "merge_traces",
           "detect_stragglers", "skew_summary", "exchange_phase_stats",
           "get_watchdog"]
