"""Recompile detector — catch silent XLA retraces with the signature
that caused them.

On this stack a "recompile" is jax re-tracing a jitted computation
because an argument signature changed (new shapes/dtypes, a flipped
static flag, a weakly-typed scalar). The reference framework never had
this failure mode — its graphs were explicit — but here a
shape-polymorphic input silently multiplies step latency by the
compile time, and nothing in the training loop says so.

Mechanism: jax.monitoring publishes per-compile duration events
(``/jax/core/compile/jaxpr_trace_duration`` on every trace,
``jaxpr_to_mlir_module_duration`` on every lowering,
``backend_compile_duration`` on every executable build or load), each
with jax's name of the function (``fun_name``: ``chunk`` for a trace,
``jit(chunk)`` for the module). One listener, installed when the
package first asks for a device (``chip.devices``), appends every one
of them to the detector's bounded ``events`` deque: THE COMPILE LEDGER,
kept whatever the telemetry gates say: a compile costs milliseconds
to minutes and an entry a deque append. ``summary()`` reduces it. A
name says which program, not which call: instrumented call sites
(CachedOp, Executor) drop a breadcrumb first — ``note_call(origin,
signature)`` into a thread-local — and an entry carries the innermost
breadcrumb live on that thread when it fires. Python-level variant
builds (a new CachedOp fn cache entry) report through
``record_retrace`` with an exact signature.

The persistent cache's part of a build (``cache_hits`` /
``cache_misses`` and ``cache_retrieval_time_sec``) fires on the same
thread inside jax's compile-or-load and is folded into the
``backend_compile`` entry that closes after it. jax fires
``cache_misses`` where it WRITES the new entry, so a build with
neither event is ``uncached`` (no cache directory, or an entry under
the cache's thresholds).

Everything else here (the budget, the warning, the ``recompile.*``
instants and counters) follows ``core.enabled()``.

Steady-state budget: first-time compiles are legitimate, so misses only
count against the budget after ``mark_steady()`` — Trainer.step /
Module.update arm it automatically once a step past
``MXNET_OBS_WARMUP_STEPS`` (default 1) completes with NO compiles, i.e.
stability is observed, not assumed. Past
``MXNET_OBS_RECOMPILE_BUDGET`` steady misses (default 2) the detector
warns once with the attributed signatures.
"""

import collections
import threading
import time
import warnings

from . import core
from .. import _fastenv

__all__ = ["JAXPR_TRACE_EVENT", "LOWER_EVENT", "BACKEND_COMPILE_EVENT",
           "RecompileDetector", "get_detector", "install", "note_call",
           "record_retrace", "step_boundary", "summary"]

JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_KINDS = {JAXPR_TRACE_EVENT: "trace", LOWER_EVENT: "lower",
          BACKEND_COMPILE_EVENT: "backend_compile"}

# entries ever appended to the ledger. core.span reads it when it
# starts and when it stops: a span inside which it moved was a cold call
seq = 0

_tls = threading.local()
_detector = None
_listener_installed = False
_lock = threading.Lock()
# (seq, the uncut summary at that seq): a router asks a replica's
# health_snapshot() at every pick, and the ledger moves only on a compile
_whole = None


def default_budget():
    return int(_fastenv.get("MXNET_OBS_RECOMPILE_BUDGET", 2))


def warmup_steps():
    return int(_fastenv.get("MXNET_OBS_WARMUP_STEPS", 1))


class RecompileDetector(object):
    """Per-process compile ledger and retrace detector. ``events``
    holds the most recent 4096 entries, always kept: dicts with kind
    ('trace'|'lower'|'backend_compile'|'variant'), fun_name (jax's),
    duration_s, t_ns (the entry's end on ``core``'s epoch), origin and
    signature (the breadcrumb), the steady flag; a 'trace' or 'lower'
    entry also nested (the traces inside it, whose entries it replaced)
    and self_s (its seconds less any nested trace whose entry stayed), a
    'backend_compile' entry cache ('hit'|'miss'|'uncached') and
    retrieval_s. The counts below move only under ``core.enabled()``."""

    def __init__(self, budget=None):
        self.budget = default_budget() if budget is None else int(budget)
        self.events = collections.deque(maxlen=4096)
        self.steady = False
        self.misses = 0          # trace events seen while recording
        self.steady_misses = 0   # trace events after mark_steady()
        self.flagged = False
        self._steps = 0
        self._step_start_misses = 0
        self._seq0 = seq         # `seq` when the deque was last empty
        self._folded = 0         # of those, replaced by an outer entry
        self._lock = threading.Lock()

    # ----------------------------------------------------- lifecycle --
    def reset(self, budget=None):
        global _whole
        with self._lock:
            if budget is not None:
                self.budget = int(budget)
            self.events.clear()
            self._seq0 = seq
            self._folded = 0
            _whole = None
            # this thread's traces that nothing has enclosed yet: their
            # entries are gone
            _tls.__dict__.pop("traces", None)
            self.steady = False
            self.misses = 0
            self.steady_misses = 0
            self.flagged = False
            self._steps = 0
            self._step_start_misses = 0

    def mark_steady(self):
        """Arm the budget: every later trace is a silent retrace."""
        self.steady = True

    def step_boundary(self):
        """One train step completed. Arm once a post-warmup step runs
        with NO compiles at all — "the graphs stabilized" observed
        rather than assumed (a fixed step count would misfire on
        programs that legitimately compile new jits for a few steps:
        metrics, logging ops, the optimizer's first update)."""
        with self._lock:
            self._steps += 1
            if not self.steady and self._steps > warmup_steps() \
                    and self.misses == self._step_start_misses:
                self.steady = True
            self._step_start_misses = self.misses

    # ------------------------------------------------------- ingest --
    def _push(self, kind, origin, signature, duration, nested=None,
              **more):
        global seq
        on = core.enabled()
        rec = {"kind": kind, "origin": origin, "signature": signature,
               "duration_s": duration, "steady": self.steady,
               "t_ns": core.now_ns()}
        rec.update(more)
        over = False
        with self._lock:
            if nested is not None:
                self._fold(rec, nested)
            self.events.append(rec)
            seq += 1
            if on and kind == "trace":
                self.misses += 1
                if self.steady:
                    self.steady_misses += 1
                    if self.steady_misses >= self.budget \
                            and not self.flagged:
                        self.flagged = True
                        over = True
        if not on or kind == "lower":
            # the ledger alone; a lowering was never an instant or a
            # counter, and goodput rebuilds compile intervals from those
            return rec
        core.record_instant(
            "recompile." + kind, cat="recompile",
            args={"origin": origin, "signature": signature,
                  "steady": rec["steady"],
                  # the goodput ledger reconstructs the compile
                  # interval [ts - duration, ts] from this instant
                  "duration_s": duration})
        core.counter("recompile." + kind).add(1)
        if kind == "backend_compile":
            # a fresh executable exists — per-operator attribution must
            # re-analyze the origin's program (attribution.py caches
            # the HLO breakdown per executable)
            from . import attribution
            attribution.on_compile(origin, kind)
        if over:
            self._warn()
        return rec

    def _fold(self, rec, nested):
        """A trace or a lowering closed over `nested` (the entries of the
        traces inside it, newest first): jax reports an inner jit's trace
        before its caller's, and the caller's seconds cover both. A
        program of a few dozen layers traces thousands of `add` and
        `multiply`; the inner entries still at the deque's end are taken
        out again and counted in the outer one, so the ledger holds a
        program's trace once and the bounded deque holds a whole
        start-up. One that another thread's entry has buried stays, and
        its seconds come off the outer entry's ``self_s`` instead."""
        count, kept = 0, 0.0
        for inner in nested:
            if self.events and self.events[-1] is inner:
                self.events.pop()
                self._folded += 1
                count += 1 + inner["nested"]
            else:
                kept += inner["duration_s"]
        rec["nested"] = count
        rec["self_s"] = max(rec["duration_s"] - kept, 0.0)

    def _warn(self):
        recent = [e for e in list(self.events)[-16:]
                  if e["steady"] and e["kind"] == "trace"]
        culprits = "; ".join(
            "%s%s" % (e["origin"] or "<jit>",
                      " " + e["signature"] if e["signature"] else "")
            for e in recent[-4:]) or "<unattributed jit>"
        warnings.warn(
            "mxnet_tpu.observability: %d XLA retraces after steady "
            "state (budget %d) — a jit is being re-traced per call, "
            "likely shape/dtype-polymorphic inputs. Recent: %s"
            % (self.steady_misses, self.budget, culprits),
            RuntimeWarning, stacklevel=3)

    def on_event(self, event, duration, fun_name=None):
        if getattr(_tls, "suppress", 0):
            # report-time re-lowering (attribution._analyze) compiles on
            # purpose; counting it would flag the profiler as the leak
            return
        kind = _KINDS[event]
        origin, signature = getattr(_tls, "call", (None, None))
        if kind == "backend_compile":
            self._push(kind, origin, signature, duration,
                       fun_name=fun_name,
                       cache=_tls.__dict__.pop("cache", "uncached"),
                       retrieval_s=_tls.__dict__.pop("retrieval", 0.0))
            return
        # a lowering rule may trace too (`mlir.lower_fun` over jitted
        # jnp functions), and the lowering's seconds cover those traces
        open_, now, nested = _enclosed(duration)
        rec = self._push(kind, origin, signature, duration,
                         nested=nested, fun_name=fun_name)
        if kind == "trace":
            open_.append((now, rec))
            # one the deque has dropped can no longer be folded
            del open_[:-self.events.maxlen]


def _enclosed(duration):
    """(this thread's traces not yet enclosed, now, the entries of those
    that the trace or lowering which just ended encloses, newest first).
    jax reports either when it ENDS: a trace that ended after this one
    began, and took no longer, ran inside it."""
    now = time.perf_counter_ns()
    start = now - int(duration * 1e9)
    open_ = _tls.__dict__.setdefault("traces", [])
    nested = []
    while open_ and open_[-1][0] >= start \
            and open_[-1][1]["duration_s"] <= duration:
        nested.append(open_.pop()[1])
    return open_, now, nested


# -------------------------------------------------- module-level API --

def _listener(event, duration, **kwargs):
    if event in _KINDS:
        get_detector().on_event(event, duration, kwargs.get("fun_name"))
    elif event == CACHE_RETRIEVAL_EVENT:
        _tls.retrieval = getattr(_tls, "retrieval", 0.0) + duration


def _cache_listener(event, **kwargs):
    if event == CACHE_HIT_EVENT:
        _tls.cache = "hit"
    elif event == CACHE_MISS_EVENT:
        _tls.cache = "miss"


def install():
    """Register the ledger's two jax.monitoring listeners, once a
    process. ``chip.devices`` calls this before the package's first
    device query, so every program the process builds is in the ledger;
    an idle registration costs nothing except on compile events."""
    global _listener_installed
    with _lock:
        if not _listener_installed:
            import jax.monitoring
            jax.monitoring.register_event_duration_secs_listener(
                _listener)
            jax.monitoring.register_event_listener(_cache_listener)
            _listener_installed = True


def get_detector():
    """The process detector (and the ledger it holds); installs the
    jax.monitoring listeners if nothing has yet."""
    global _detector
    if _detector is None:
        with _lock:
            if _detector is None:
                _detector = RecompileDetector()
    if not _listener_installed:
        install()
    return _detector


def summary(since=None, before=None):
    """The ledger reduced: what start-up (or any stretch) spent building
    programs. ``since`` / ``before`` cut by an entry's end, in ns on
    ``core``'s epoch (``core.now_ns()``; ``core.first_session_ns()`` is
    where a benchmark's set-up ended); None leaves that side open.

    trace_s, lower_s   seconds tracing and lowering (a trace nested in
                       another, or in a lowering, is counted in the
                       outer one alone): paid in full whatever the cache
                       holds
    compile_s          backend seconds less retrieval: jax times
                       compile-or-load together
    cache_load_s       seconds reading and loading cached executables
    hits, misses, uncached, programs
                       executables loaded, compiled and written,
                       compiled with no cache entry; their sum
    by_program         the ten costliest names: {"program", "seconds"
                       (trace + lower + backend), "builds", "hits",
                       "misses"}
    entries, dropped   entries counted; entries the bounded deque lost
                       before anyone read them (over the whole ledger)

    The uncut summary is kept until the ledger moves (read it, do not
    edit it).
    """
    global _whole
    det = get_detector()
    whole = since is None and before is None
    with det._lock:
        at = seq
        if whole and _whole is not None and _whole[0] == at:
            return _whole[1]
        entries = list(det.events)
        dropped = at - det._seq0 - det._folded - len(entries)
    out = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0,
           "cache_load_s": 0.0, "hits": 0, "misses": 0, "uncached": 0,
           "programs": 0, "entries": 0, "dropped": dropped}
    programs = {}
    for e in entries:
        kind = e["kind"]
        if kind == "variant" \
                or (since is not None and e["t_ns"] < since) \
                or (before is not None and e["t_ns"] >= before):
            continue
        out["entries"] += 1
        name, secs = e.get("fun_name") or "<unnamed>", e["duration_s"]
        if kind == "trace":
            # a trace is named for the function, its module "jit(<it>)"
            name, secs = "jit(%s)" % name, e.get("self_s", secs)
            out["trace_s"] += secs
        elif kind == "lower":
            secs = e.get("self_s", secs)
            out["lower_s"] += secs
        p = programs.setdefault(name, {"program": name, "seconds": 0.0,
                                       "builds": 0, "hits": 0,
                                       "misses": 0})
        p["seconds"] += secs
        if kind == "backend_compile":
            load = e.get("retrieval_s", 0.0)
            out["compile_s"] += secs - load
            out["cache_load_s"] += load
            out["programs"] += 1
            p["builds"] += 1
            cache = {"hit": "hits", "miss": "misses"}.get(e.get("cache"))
            out[cache or "uncached"] += 1
            if cache:
                p[cache] += 1
    out["by_program"] = sorted(programs.values(),
                               key=lambda p: -p["seconds"])[:10]
    if whole:
        _whole = (at, out)
    return out


def note_call(origin, signature):
    """Breadcrumb: the jit boundary about to run on this thread. Any
    compile event firing before the next note is attributed to it:
    jax's ``fun_name`` says which program, this says which call site
    and argument signature. Call only when ``core.enabled()``
    (signature formatting costs)."""
    get_detector()
    _tls.call = (origin, signature)


class suppress_events(object):
    """Context manager: compile/trace events fired on this thread are
    NOT counted by the detector (deliberate report-time lowering)."""

    def __enter__(self):
        _tls.suppress = getattr(_tls, "suppress", 0) + 1
        return self

    def __exit__(self, *exc):
        _tls.suppress -= 1


def record_retrace(origin, signature, duration=0.0):
    """Explicit retrace report for python-level variant builds (a new
    CachedOp fn-cache entry after the first, a new executor program)."""
    get_detector()._push("variant", origin, signature, duration)


def step_boundary():
    """Trainer hook: a full train step completed."""
    if _detector is not None or core.enabled():
        get_detector().step_boundary()


def signature_of(arrays, **flags):
    """Compact signature string for note_call: 'f32[2,3],f32[3] k=v'."""
    parts = []
    for a in arrays:
        dt = getattr(a, "dtype", None)
        sh = getattr(a, "shape", ())
        parts.append("%s[%s]" % (
            getattr(dt, "name", dt), ",".join(str(d) for d in sh)))
    sig = ",".join(parts)
    if flags:
        sig += " " + " ".join(
            "%s=%s" % (k, v) for k, v in sorted(flags.items()))
    return sig
