"""mx.profiler — profiling API over jax.profiler/XPlane + the
observability telemetry core.

Reference: python/mxnet/profiler.py:33-474 (set_config/set_state/dump +
Domain/Task/Frame/Event/Counter/Marker) backed by the native
chrome://tracing profiler (src/profiler/profiler.h:251, DumpProfile:299).

TPU-native design: device-side op timing comes from XLA's profiler
(jax.profiler.start_trace -> TensorBoard/XPlane, the TPU analogue of the
reference's chrome tracing); host-side runtime phases (step phases,
collective dispatch, input pipeline, jit boundaries — see
mxnet_tpu/observability/) record into the telemetry ring, which this
module exports the reference's two ways:

* ``dump()`` writes a chrome://tracing JSON (the ring's spans/counters,
  plus any user Domain/Task/Frame spans) to ``filename`` — load it at
  chrome://tracing / ui.perfetto.dev, alongside the XPlane trace dir.
* ``dumps(aggregate=True)`` returns the aggregate-stats percentile
  table (count/total/self/min/max/p50/p99 per phase and per counter),
  the analogue of the reference's AggregateStats::DumpTable. After a
  session that took an XLA trace it ends with the device's program
  launches and idle time by ``mx.*`` span, read from that session's own
  ``.xplane.pb``.

``set_state('run')`` force-enables telemetry recording even without
``MXNET_OBS=1``; pause/resume gate it. ``set_config(xla_trace=False)``
skips the XLA trace (host-side telemetry only — cheap enough for unit
tests and always-on dashboards). The XLA trace is taken without the
Python function tracer: the program's spans are the host timeline, and a
hook on every Python call would slow the host-bound step it measures."""

import threading

import jax

from .base import MXNetError
from .observability import core as _obs_core
from .observability import export as _obs_export
from . import _fastenv

__all__ = ["set_config", "profiler_set_config", "set_state",
           "profiler_set_state", "dump", "dumps", "pause", "resume",
           "Domain", "Task", "Frame", "Event", "Counter", "Marker"]

_config = {"filename": "profile.json", "profile_all": False,
           "profile_symbolic": True, "profile_imperative": True,
           "profile_memory": True, "profile_api": True,
           "aggregate_stats": False, "xla_trace": True}
# "dir": the XLA trace being taken; "last_dir": the session's, kept after
# the trace stops for dumps(aggregate=True) to read its .xplane.pb
_state = {"running": False, "dir": None, "last_dir": None,
          "obs_prev": None}
_records = []
_lock = threading.Lock()


def set_config(**kwargs):
    """Configure the profiler (reference profiler.set_config). The
    `filename` stem names the trace directory for the XLA trace dump;
    ``xla_trace=False`` restricts 'run' to host-side telemetry."""
    for k, v in kwargs.items():
        _config[k] = v


profiler_set_config = set_config


def _start_xla_trace():
    trace_dir = str(_config["filename"]) + ".tracedir"
    _state["dir"] = _state["last_dir"] = trace_dir
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def _stop_xla_trace():
    if _state["dir"] is not None:
        jax.profiler.stop_trace()
        _state["dir"] = None


def set_state(state="stop", profile_process="worker"):
    """'run' starts host telemetry (and a jax profiler trace unless
    xla_trace=False); 'stop' ends both — the XPlane trace lands next to
    `filename`."""
    if state not in ("run", "stop"):
        raise MXNetError("profiler state must be 'run' or 'stop'")
    if state == "run" and not _state["running"]:
        _state["obs_prev"] = _obs_core._override
        _obs_core.set_enabled(True)
        _obs_core.reset_span_totals()
        _state["last_dir"] = None
        from .observability import http as _obs_http
        _obs_http.maybe_start()    # MXNET_OBS_HTTP live scrape, if set
        if _config.get("xla_trace", True):
            _start_xla_trace()
        _state["running"] = True
    elif state == "stop" and _state["running"]:
        _stop_xla_trace()
        _obs_core.set_enabled(_state["obs_prev"])
        _state["running"] = False


profiler_set_state = set_state


def pause(profile_process="worker"):
    """Keep the session open but stop recording (reference
    profiler_pause): spans/counters hit the ring again after resume()."""
    if _state["running"]:
        _stop_xla_trace()
        _obs_core.set_enabled(False)


def resume(profile_process="worker"):
    if _state["running"]:
        _obs_core.set_enabled(True)
        if _config.get("xla_trace", True) and _state["dir"] is None:
            _start_xla_trace()
    else:
        set_state("run")


def dump(finished=True, profile_process="worker"):
    """Write the chrome://tracing JSON of everything recorded (telemetry
    ring + user profiler objects) to `filename`; stop any running XLA
    trace so its files hit disk too. Also refreshes the Prometheus
    textfile when MXNET_OBS_PROM is set.

    Multi-process runs write RANK-LOCAL files: rank 0 keeps the bare
    `filename`, rank r writes `<stem>.rank<r>.json` (no N-way clobber);
    `mxnet_tpu.observability.merge_traces(filename)` — or the
    `tools/obs_merge.py` CLI — combines them into one trace with
    per-rank lanes on the barrier-aligned timebase."""
    if _state["running"] and finished:
        set_state("stop")
    elif finished:
        _stop_xla_trace()
    from .observability import attribution as _obs_attr
    from .observability import dist as _obs_dist
    from .observability import http as _obs_http
    from . import storage as _storage
    _obs_http.maybe_start()        # MXNET_OBS_HTTP live scrape, if set
    _obs_dist.ensure_clock_anchor()
    _storage.publish_device_memory_gauges()
    # per-operator attribution: per-scope flops/bytes gauges ride the
    # ring into the chrome trace + Prometheus textfile
    _obs_attr.publish_counters()
    # performance archive: persist this run's per-scope measurements
    # (ISSUE 18) — one guarded branch, no I/O with the store unset
    from .observability import profile_store as _obs_pstore
    if _obs_pstore.enabled():
        _obs_pstore.record_run()
    # goodput ledger (ISSUE 19): publish goodput.fraction /
    # badput.<cat>_ms gauges (they ride the trace + textfile written
    # below) and archive the run's ledger into the profile store
    from .observability import goodput as _obs_goodput
    if _obs_goodput.enabled():
        _obs_goodput.on_dump()
    path = _obs_dist.rank_trace_path(str(_config["filename"]))
    _obs_export.dump_chrome_trace(path)
    _obs_export.write_prometheus()
    return path


def dumps(reset=False, aggregate=False):
    """Text dump. ``aggregate=True`` (or set_config(aggregate_stats=
    True)) returns the aggregate-stats percentile table over the
    telemetry ring — the reference's AggregateStats table, and after a
    session that took an XLA trace the device's launches and idle time
    by program span. Otherwise the legacy flat listing of user profiler
    objects."""
    if aggregate or _config.get("aggregate_stats"):
        table = _obs_export.aggregate_table(
            trace_dir=None if _state["dir"] else _state["last_dir"])
        if reset:
            _obs_core.reset()
            _state["last_dir"] = None
            with _lock:
                del _records[:]
        return table
    with _lock:
        lines = ["Profile Statistics:",
                 "%-32s %-16s %-12s" % ("Name", "Kind", "Duration/Value")]
        for name, kind, value in _records:
            lines.append("%-32s %-16s %-12s" % (name, kind, value))
        if reset:
            del _records[:]
    return "\n".join(lines)


def _record(name, kind, value):
    with _lock:
        _records.append((name, kind, value))


class Domain(object):
    """Grouping namespace for profiler objects."""

    def __init__(self, name):
        self.name = name

    def new_task(self, name):
        return Task(self, name)

    def new_frame(self, name):
        return Frame(self, name)

    def new_counter(self, name, value=None):
        return Counter(self, name, value)

    def new_marker(self, name):
        return Marker(self, name)

    def __str__(self):
        return self.name


class _Span(object):
    """start()/stop() span over ``core.span``: on the profiler's host
    timeline as ``mx.<name>`` and in the ring for the chrome-trace and
    aggregate exporters while the profiler runs; always in the legacy
    listing (without a duration when nothing recorded it)."""

    kind = "span"

    def __init__(self, domain, name):
        self.domain = domain
        self.name = name
        self._span = None

    def start(self):
        self._span = _obs_core.span(self.name, cat=self.kind,
                                    domain=str(self.domain)).start()

    def stop(self):
        if self._span is not None:
            dur_ns = self._span.stop()
            self._span = None
            _record(self.name, self.kind,
                    "-" if dur_ns is None else "%.6fs" % (dur_ns / 1e9))

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()

    def __str__(self):
        return self.name


class Task(_Span):
    kind = "task"


class Frame(_Span):
    kind = "frame"


class Event(_Span):
    kind = "event"

    def __init__(self, name):
        super(Event, self).__init__("event", name)


class Counter(object):
    def __init__(self, domain, name, value=None):
        self.domain = domain
        self.name = name
        self._value = 0
        if value is not None:
            self.set_value(value)

    def set_value(self, value):
        self._value = value
        _record(self.name, "counter", str(value))
        if _obs_core.enabled():
            _obs_core.gauge("profiler.%s" % self.name).set(value)

    def increment(self, delta=1):
        self.set_value(self._value + delta)

    def decrement(self, delta=1):
        self.set_value(self._value - delta)

    def __iadd__(self, v):
        self.increment(v)
        return self

    def __isub__(self, v):
        self.decrement(v)
        return self

    def __str__(self):
        return self.name


class Marker(object):
    def __init__(self, domain, name):
        self.domain = domain
        self.name = name

    def mark(self, scope="process"):
        _record(self.name, "marker", scope)
        if _obs_core.enabled():
            _obs_core.record_instant(self.name, cat="marker",
                                     args={"scope": scope,
                                           "domain": str(self.domain)})


def dump_profile():
    """Deprecated reference alias of dump()."""
    import warnings
    warnings.warn("profiler.dump_profile() is deprecated; use dump()",
                  DeprecationWarning)
    return dump()


def set_kvstore_handle(handle):
    """Server-side profiling wiring (reference sends profiler commands
    over the kvstore channel to ps-lite servers). dist_tpu_sync has no
    server role, so there is nothing to forward; accepted as a no-op
    for source compatibility."""


# MXNET_PROFILER_AUTOSTART (reference initialize.cc): begin profiling at
# import so short scripts need no explicit set_state. Host telemetry
# only would surprise nobody; the XLA trace obeys set_config as usual.
if _fastenv.get("MXNET_PROFILER_AUTOSTART", "0") not in ("0", "", "false"):
    set_state("run")
