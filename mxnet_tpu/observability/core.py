"""Telemetry core — ring-buffer span recorder + named counters/gauges.

Reference analogue: src/profiler/profiler.h:251 keeps a per-thread
profile record ring that DumpProfile() serializes to chrome://tracing
and AggregateStats reduces to a percentile table. Here the same role is
played by one process-wide ring of host-side records, because device-op
timing already belongs to XLA's profiler (jax.profiler / XPlane) — what
the runtime needs to observe for itself is the HOST orchestration:
step phases, collective dispatch, input pipeline, jit boundaries.

Two gates, both derived (no knob of their own):

* ``enabled()`` — ``MXNET_OBS=1`` or the profiler state machine. Gates
  everything that writes the ring or a registry: counters, gauges,
  histograms, instants, flows, the recompile detector.
* ``active()`` — ``enabled()`` OR a live ``jax.profiler`` session, whoever
  started it. Gates spans alone: while a session is live a span is also a
  ``TraceAnnotation("mx." + name)`` carrying the span's scalar arguments,
  so it lies on ``/host:CPU`` of the same ``.xplane.pb`` as the device's
  ``XLA Ops``, and its duration is added to the per-name totals
  ``span_totals()`` returns. A collection of Python's collector is such a
  span too, ``gc`` (one ``gc.callbacks`` hook, ``_on_gc``, which takes no
  lock; ``_fold_gc`` adds the finished ones from where one may be taken).

Design constraints (ISSUE 2 tentpole):

* near-zero cost when off — every instrumentation site guards on
  ``enabled()``, a module override check + one `_fastenv` dict read
  (~0.1 us); a disabled ``span`` allocates one slotted object, asks both
  gates (the session check is ~20 ns), keeps the compile ledger's
  ``seq`` and one clock reading, and on ``stop()`` compares ``seq``: only
  a span inside which something was traced, lowered, compiled or loaded
  (a COLD call: milliseconds at least) reads the clock again and adds
  itself to ``cold_totals()``. No locks, no string formatting.
* thread-safe when on — the prefetch threads (io.py), the main step
  loop and jax.monitoring callbacks all record concurrently; one lock
  guards the ring head and the counter registry, and record payloads
  are built before taking it.
* bounded memory — a fixed-capacity ring (``MXNET_OBS_RING``, default
  65536 records) overwrites the oldest records; ``dropped`` reports how
  many fell off so exporters can say the trace is a suffix.

Knobs: ``MXNET_OBS=1`` enables recording; ``MXNET_OBS_RING`` sets ring
capacity (read when the ring is (re)built). ``set_enabled()`` overrides
the env for the profiler state machine (profiler.set_state/pause).
"""

import gc
import threading
import time

from jax.profiler import TraceAnnotation as _Annotation

from .. import _fastenv

__all__ = ["enabled", "active", "set_enabled", "span", "span_totals",
           "reset_span_totals", "cold_totals", "record_startup",
           "first_session_ns", "now_ns", "counter", "gauge",
           "histogram", "record_span", "record_instant", "record_flow",
           "records", "counters", "dropped", "reset", "ring_capacity",
           "Counter", "Gauge"]

DEFAULT_RING = 65536

# perf_counter epoch shared by every record so spans from different
# threads land on one consistent trace timeline
_EPOCH_NS = time.perf_counter_ns()

# None -> follow MXNET_OBS; True/False -> profiler state machine override
_override = None

_lock = threading.Lock()
_ring = [None] * 0
_head = 0
_total = 0
_counters = {}
# name -> [count, total_ns, self_ns, max_ns] of the spans that ran under a
# profiler session; kept in memory, read by span_totals()
_totals = {}
# name -> [count, total_ns] of the spans, on or off, inside which the
# compile ledger moved (recompile.seq); read by cold_totals()
_cold = {}
# when the process's first profiler session was seen live, ns on the
# epoch: where a benchmark's set-up ends (recompile.summary(before=...))
_first_session_ns = None
_local = threading.local()
# what of a span's arguments goes into its annotation (rid, lane, behind,
# kind): the trace's events carry scalars
_SCALARS = (int, float, str, bool)

# a live jax.profiler session, whoever started it (~20 ns)
_session_live = _Annotation.is_enabled


def enabled():
    """Is recording on? Module override (profiler.set_state) beats the
    MXNET_OBS env knob. This is THE hot-path guard — keep it cheap."""
    if _override is not None:
        return _override
    v = _fastenv.get("MXNET_OBS")
    return v is not None and v not in ("", "0", "false", "False")


def active():
    """Do spans record? ``enabled()``, or a profiler session is live.
    Only spans follow this gate."""
    return enabled() or _session_live()


def set_enabled(value):
    """Override the env gate: True/False force, None reverts to env."""
    global _override
    _override = value


def ring_capacity():
    return int(_fastenv.get("MXNET_OBS_RING", DEFAULT_RING))


def _ensure_ring():
    global _ring
    if not _ring:
        _ring = [None] * max(ring_capacity(), 1)
    return _ring


def now_ns():
    """Now, in ns on the records' epoch: what the compile ledger stamps
    its entries with and ``recompile.summary(since=, before=)`` cuts by."""
    return time.perf_counter_ns() - _EPOCH_NS


def _now_us():
    return now_ns() // 1000


def _append(rec):
    global _head, _total
    with _lock:
        ring = _ensure_ring()
        ring[_head] = rec
        _head = (_head + 1) % len(ring)
        _total += 1


def record_span(name, cat, t0_ns, t1_ns, args=None, child_ns=0):
    """Record one completed span. Timestamps are perf_counter_ns values
    (callers capture them outside the lock). A span that enclosed others
    (``child_ns`` > 0) carries its own share as ``args["self_us"]``."""
    args = args or {}
    if child_ns:
        args = dict(args, self_us=max((t1_ns - t0_ns - child_ns) // 1000,
                                      0))
    _append(("X", name, cat, (t0_ns - _EPOCH_NS) // 1000,
             max((t1_ns - t0_ns) // 1000, 0),
             threading.get_ident(), args))


def record_instant(name, cat="event", args=None):
    """Record a zero-duration marker."""
    _append(("i", name, cat, _now_us(), 0, threading.get_ident(),
             args or {}))


def record_flow(name, flow_id, phase, cat="flow", args=None):
    """Record one chrome-trace flow event: ``phase`` is ``"s"``
    (start), ``"t"`` (step) or ``"f"`` (finish). Events sharing
    ``(name, flow_id)`` render as one arrowed chain across lanes and
    threads — how a serving request's admit→decode→finish is tied
    together across pipeline-depth dispatches."""
    _append(("F", name, cat, _now_us(), (str(phase), int(flow_id)),
             threading.get_ident(), args or {}))


class span(object):
    """``with span("allreduce", cat="step", bytes=n):`` — one span,
    recorded when ``active()``; a cheap no-op otherwise. Under
    ``enabled()`` it is an "X" (complete) event in the ring; under a live
    profiler session it is a ``TraceAnnotation("mx.<name>")`` on the
    profiler's clock and an entry in ``span_totals()``. A per-thread stack
    gives it its parent, to which its duration is charged as child time.
    Usable as a context manager or via explicit start()/stop(); stop()
    returns the duration in ns, or None when nothing was recorded.
    On or off, a span inside which the compile ledger moved adds its
    count and duration to ``cold_totals()``."""

    __slots__ = ("name", "cat", "args", "_t0", "_ann", "_ring",
                 "_child_ns", "_seq", "_on")

    def __init__(self, name, cat="phase", **args):
        self.name = name
        self.cat = cat
        self.args = args
        self._t0 = None
        self._ann = None

    def start(self):
        global _first_session_ns
        ring, live = enabled(), _session_live()
        self._seq = _recompile.seq
        on = self._on = ring or live
        if on:
            self._ring = ring
            self._child_ns = 0
            try:
                _local.stack.append(self)
            except AttributeError:
                _local.stack = [self]
            if live:
                self._ann = _Annotation(
                    "mx." + self.name,
                    **{k: v for k, v in self.args.items()
                       if type(v) in _SCALARS})
                self._ann.__enter__()
                if _first_session_ns is None:
                    _first_session_ns = now_ns()
                if "gc" not in _totals:
                    _seed_gc()
        self._t0 = time.perf_counter_ns()
        return self

    def stop(self):
        t0 = self._t0
        if t0 is None:
            return None
        self._t0 = None
        if not self._on:
            if self._seq != _recompile.seq:
                _add_cold(self.name, time.perf_counter_ns() - t0)
            return None
        t1 = time.perf_counter_ns()
        dur = t1 - t0
        if _gc_done:
            _fold_gc()
        if self._seq != _recompile.seq:
            _add_cold(self.name, dur)
        stack = getattr(_local, "stack", ())
        if stack and stack[-1] is self:
            stack.pop()
            if stack:
                stack[-1]._child_ns += dur
        elif self in stack:
            # explicit start()/stop() pairs that did not nest (a span
            # above this one was never stopped): drop the stale ones
            del stack[stack.index(self):]
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
            _add_total(self.name, dur, dur - self._child_ns)
        if self._ring:
            record_span(self.name, self.cat, t0, t1, self.args,
                        self._child_ns)
        return dur

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()


def _add_total(name, dur, self_dur):
    with _lock:
        t = _totals.get(name)
        if t is None:
            t = _totals[name] = [0, 0, 0, 0]
        t[0] += 1
        t[1] += dur
        t[2] += self_dur
        if dur > t[3]:
            t[3] = dur


def _seed_gc():
    """`gc` into the totals with zeros, by the first span that sees a live
    session and finds none (after a reset too): a window without a
    collection then reads 0 and not nothing."""
    with _lock:
        _totals.setdefault("gc", [0, 0, 0, 0])


# The collector's hook takes no lock and opens no `span`: a collection can
# start inside ANY allocation, one made while `_lock` is held among them,
# and the hook runs on that thread. It keeps the collection in progress
# here (the collector runs one at a time, "start" and "stop" on the thread
# that triggered it) and leaves the finished ones for `_fold_gc`.
_gc_open = []
_gc_done = []


def _on_gc(phase, info):
    """`gc.callbacks` hook: a collection while spans record is the span
    ``gc``, an annotation on the profiler's clock under a session, its
    time charged as child time to whatever span its thread had open.
    Off, one gate check a collection."""
    if phase == "start":
        if active():
            ann = None
            if _session_live():
                ann = _Annotation("mx.gc", generation=info["generation"])
                ann.__enter__()
            _gc_open.append((ann, enabled(), info["generation"],
                             time.perf_counter_ns()))
    elif _gc_open:
        ann, ring, generation, t0 = _gc_open.pop()
        t1 = time.perf_counter_ns()
        if ann is not None:
            ann.__exit__(None, None, None)
        stack = getattr(_local, "stack", None)
        if stack:
            stack[-1]._child_ns += t1 - t0
        _gc_done.append((t0, t1, ann is not None, ring, generation,
                         threading.get_ident()))


def _fold_gc():
    """The finished collections into the totals (those under a session)
    and the ring (those under ``enabled()``), from where a lock may be
    taken: a span's stop and the two readers."""
    while True:
        try:
            t0, t1, live, ring, generation, tid = _gc_done.pop(0)
        except IndexError:      # none left, or another thread folded it
            return
        if live:
            _add_total("gc", t1 - t0, t1 - t0)
        if ring:
            _append(("X", "gc", "runtime", (t0 - _EPOCH_NS) // 1000,
                     max((t1 - t0) // 1000, 0), tid,
                     {"generation": generation}))


def span_totals():
    """{name: {"count", "total_ns", "self_ns", "max_ns"}} of the spans
    that ran under a profiler session since the last reset, and of the
    ``startup.*`` spans (``record_startup``), which record whatever the
    gates. Self time is the total less the spans opened inside it on the
    same thread."""
    _fold_gc()
    with _lock:
        return {name: {"count": t[0], "total_ns": t[1], "self_ns": t[2],
                       "max_ns": t[3]}
                for name, t in _totals.items()}


def reset_span_totals():
    _fold_gc()
    with _lock:
        _totals.clear()


def _add_cold(name, dur):
    with _lock:
        c = _cold.get(name)
        if c is None:
            c = _cold[name] = [0, 0]
        c[0] += 1
        c[1] += dur


def cold_totals():
    """{name: {"count", "total_ns"}} of the spans, whatever the gates,
    inside which jax traced, lowered, compiled or loaded a program (the
    compile ledger's ``seq`` moved between start and stop): the calls
    that paid for start-up, seen from inside. Nested cold spans each
    carry their own whole duration."""
    with _lock:
        return {name: {"count": c[0], "total_ns": c[1]}
                for name, c in _cold.items()}


def record_startup(name, t0_ns):
    """Close a span of category ``startup`` that opened at ``t0_ns``
    (a ``perf_counter_ns`` reading). It records into ``span_totals()``
    whatever the gates, and into the ring under ``enabled()``, so it may
    sit only where a process passes once: the package's import, the
    first device query, a batcher's construction."""
    t1 = time.perf_counter_ns()
    dur = t1 - t0_ns
    _add_total(name, dur, dur)
    if enabled():
        record_span(name, "startup", t0_ns, t1)
    return dur


def first_session_ns():
    """When a span first saw a live profiler session, ns on the epoch
    (the ledger's ``t_ns``), or None while there has been none."""
    return _first_session_ns


class Counter(object):
    """Monotonic-by-convention named counter. ``add`` keeps running
    count/total/min/max of the deltas and drops a "C" sample in the
    ring so exporters can plot the series and compute percentiles."""

    __slots__ = ("name", "unit", "count", "total", "min", "max", "value")

    def __init__(self, name, unit=""):
        self.name = name
        self.unit = unit
        self.count = 0
        self.total = 0.0
        self.min = None
        self.max = None
        self.value = 0.0

    def add(self, delta=1):
        delta = float(delta)
        with _lock:
            self.count += 1
            self.total += delta
            self.value += delta
            self.min = delta if self.min is None else min(self.min, delta)
            self.max = delta if self.max is None else max(self.max, delta)
        _append(("C", self.name, "counter", _now_us(), self.value,
                 threading.get_ident(), {"delta": delta}))

    def set(self, value):
        with _lock:
            delta = float(value) - self.value
            self.count += 1
            self.total += delta
            self.value = float(value)
            self.min = float(value) if self.min is None \
                else min(self.min, float(value))
            self.max = float(value) if self.max is None \
                else max(self.max, float(value))
        _append(("C", self.name, "counter", _now_us(), self.value,
                 threading.get_ident(), {}))


class Gauge(Counter):
    """A counter whose ``set`` is the primary verb (last value wins);
    min/max/count still aggregate the observed values."""

    __slots__ = ()

    def set(self, value):
        value = float(value)
        with _lock:
            self.count += 1
            self.total += value
            self.value = value
            self.min = value if self.min is None else min(self.min, value)
            self.max = value if self.max is None else max(self.max, value)
        _append(("C", self.name, "gauge", _now_us(), value,
                 threading.get_ident(), {}))


def counter(name, unit=""):
    """Get-or-create the named counter (registry is process-global)."""
    c = _counters.get(name)
    if c is None:
        with _lock:
            c = _counters.get(name)
            if c is None:
                c = _counters[name] = Counter(name, unit)
    return c


def gauge(name, unit=""):
    g = _counters.get(name)
    if g is None:
        with _lock:
            g = _counters.get(name)
            if g is None:
                g = _counters[name] = Gauge(name, unit)
    return g


def histogram(name, unit=""):
    """Get-or-create the named log-bucketed histogram (bounded-memory
    distribution with mergeable buckets — ``histogram.Histogram``)."""
    from . import histogram as _h
    return _h.histogram(name, unit)


def records():
    """Snapshot of ring contents, oldest first."""
    _fold_gc()
    with _lock:
        if not _ring:
            return []
        if _total <= len(_ring):
            out = [r for r in _ring[:_head] if r is not None]
        else:
            out = [r for r in _ring[_head:] + _ring[:_head]
                   if r is not None]
    return out


def counters():
    """Snapshot of the counter registry (name -> Counter)."""
    with _lock:
        return dict(_counters)


def dropped():
    """Records that fell off the ring (trace is a suffix when > 0)."""
    with _lock:
        return max(_total - len(_ring), 0) if _ring else 0


def reset():
    """Clear the ring, the counter registry, the span totals and the
    histogram registry (tests, new profile sessions). The ring is
    rebuilt at the current
    MXNET_OBS_RING."""
    global _ring, _head, _total
    with _lock:
        _ring = [None] * 0
        _head = 0
        _total = 0
        _counters.clear()
        _totals.clear()
        _cold.clear()
        del _gc_done[:]
    from . import histogram as _h
    _h.reset()
    from . import events as _ev
    _ev.reset()
    from . import timeseries as _ts
    _ts.reset()


# the compile ledger's ``seq`` (span.start / stop). recompile imports
# this module back; every name it uses is defined above.
from . import recompile as _recompile      # noqa: E402

gc.callbacks.append(_on_gc)
