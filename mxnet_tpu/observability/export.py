"""Exporters over the telemetry ring: chrome://tracing JSON, an
MXNet-style aggregate-stats percentile table (with, after a profiler
session that took an XLA trace, the device's launches and idle time laid
against the program's ``mx.*`` spans), and a Prometheus textfile.

Reference analogues: profiler.h DumpProfile() emits chrome tracing;
AggregateStats::DumpTable() the text table. The Prometheus writer is the
long-run addition (TF's system paper argues production operation needs
scrapeable metrics, not just post-hoc traces): point a node_exporter
textfile collector at MXNET_OBS_PROM and scrape counters per step.
"""

import bisect
import json
import re

from . import core
from .. import _fastenv

__all__ = ["chrome_trace", "dump_chrome_trace", "aggregate",
           "aggregate_table", "idle_by_span", "read_xplane",
           "format_idle_by_span", "prometheus_text", "write_prometheus"]


# ------------------------------------------------------ chrome trace --

def chrome_trace(extra_events=None):
    """The ring as a chrome://tracing (catapult) JSON object. Spans are
    "X" complete events, counter samples "C" events; load the file at
    chrome://tracing or ui.perfetto.dev. Every event carries this
    process's rank as its ``pid`` so rank-local traces merge into
    per-rank lanes (``dist.merge_traces``); ``otherData`` carries the
    rank + barrier clock anchor the merge aligns timelines with."""
    from . import dist
    from . import histogram as _hist
    rank = dist.process_index()
    events = [{"name": "process_name", "ph": "M", "pid": rank,
               "args": {"name": "rank %d" % rank}}]
    last_ts = 0
    for rec in core.records():
        ph, name, cat, ts, val, tid, args = rec
        last_ts = max(last_ts, ts)
        if ph == "X":
            events.append({"name": name, "cat": cat, "ph": "X",
                           "ts": ts, "dur": val, "pid": rank, "tid": tid,
                           "args": args})
        elif ph == "C":
            events.append({"name": name, "cat": cat, "ph": "C",
                           "ts": ts, "pid": rank,
                           "args": {name.rsplit(".", 1)[-1]: val}})
        elif ph == "F":
            # flow events: val is (phase, flow_id); "s"/"t"/"f" chains
            # sharing an id render as one arrowed flow in the viewer
            fph, fid = val
            ev = {"name": name, "cat": cat, "ph": fph, "ts": ts,
                  "pid": rank, "tid": tid, "id": fid, "args": args}
            if fph == "f":
                ev["bp"] = "e"     # bind the finish to its slice
            events.append(ev)
        else:
            events.append({"name": name, "cat": cat, "ph": "i",
                           "ts": ts, "pid": rank, "tid": tid, "s": "t",
                           "args": args})
    # histogram snapshots: a counter row per histogram (quantiles
    # visible in the viewer) at the trace's end; the full mergeable
    # bucket state rides otherData.histograms
    hist_states = _hist.states()
    for name, h in sorted(_hist.histograms().items()):
        if h.count:
            events.append({"name": name, "cat": "histogram", "ph": "C",
                           "ts": last_ts, "pid": rank,
                           "args": h.quantiles()})
    if extra_events:
        events.extend(extra_events)
    trace = {"traceEvents": events, "displayTimeUnit": "ms",
             "otherData": {"recorder": "mxnet_tpu.observability",
                           "rank": rank,
                           "num_processes": dist.process_count(),
                           "clock_anchor": dist.clock_anchor(),
                           "histograms": hist_states,
                           "dropped_records": core.dropped()}}
    return trace


def dump_chrome_trace(filename, extra_events=None):
    with open(filename, "w") as f:
        json.dump(chrome_trace(extra_events), f)
    return filename


# -------------------------------------------------- aggregate stats --

def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def aggregate():
    """Reduce the ring + counter registry to per-name stats.

    Returns {"spans": {name: stats}, "counters": {name: stats}} where
    span stats are over durations (ms; ``self_ms`` leaves out the spans
    opened inside, so enclosing phases do not count twice) and counter
    stats over the added
    deltas (gauges: observed values); p50/p99 come from the ring samples
    (a suffix when the ring wrapped — count/total stay exact for
    counters because the registry accumulates independently).
    """
    span_samples = {}
    span_self = {}
    counter_samples = {}
    for rec in core.records():
        ph, name, _cat, _ts, val, _tid, args = rec
        if ph == "X":
            span_samples.setdefault(name, []).append(val / 1000.0)
            # a span that enclosed others carries its own share
            span_self[name] = span_self.get(name, 0.0) \
                + args.get("self_us", val) / 1000.0
        elif ph == "C":
            counter_samples.setdefault(name, []).append(
                args.get("delta", val))
    spans = {}
    for name, vals in sorted(span_samples.items()):
        vals.sort()
        spans[name] = {
            "count": len(vals), "total_ms": sum(vals),
            "self_ms": span_self[name],
            "min_ms": vals[0], "max_ms": vals[-1],
            "p50_ms": _percentile(vals, 0.50),
            "p99_ms": _percentile(vals, 0.99)}
    counters = {}
    for name, c in sorted(core.counters().items()):
        vals = sorted(counter_samples.get(name, []))
        counters[name] = {
            "count": c.count, "total": c.total,
            "min": c.min if c.min is not None else 0.0,
            "max": c.max if c.max is not None else 0.0,
            "p50": _percentile(vals, 0.50),
            "p99": _percentile(vals, 0.99),
            "value": c.value}
    from . import histogram as _hist
    hists = {name: h.snapshot()
             for name, h in sorted(_hist.histograms().items())}
    return {"spans": spans, "counters": counters, "histograms": hists}


def _format_timeseries():
    """The "Time-series (last window)" aggregate-table section: one
    line per sampled ring — points held, first/last values, and the
    window-mean rate for counters (timeseries.py)."""
    from . import timeseries as _ts
    if not _ts.ticks():
        return []
    win = _ts.last_window()
    lines = ["", "Time-series (last window: %d points max, %d ms "
             "interval, %d ticks)" % (win["window"], win["interval_ms"],
                                      win["ticks"])]
    fmt = "  %-40s %6s %12s %12s %12s"
    lines.append(fmt % ("Name", "Points", "First", "Last", "Rate/s"))
    for name, ent in sorted(win["series"].items()):
        vals = ent["values"]
        if not vals:
            continue
        rs = ent.get("rate_per_s") or []
        rate = ("%.3g" % (sum(rs) / len(rs))) if rs else "-"
        lines.append(fmt % (name, len(vals), "%g" % vals[0],
                            "%g" % vals[-1], rate))
    return lines


def aggregate_table(trace_dir=None):
    """The stats as a text table (reference AggregateStats::DumpTable):
    one section for span phases (ms), one for counters (raw values).
    ``trace_dir`` is the directory of a finished profiler session: its
    ``.xplane.pb`` adds the device's launches and idle time by span."""
    agg = aggregate()
    lines = ["Profile Statistics (mxnet_tpu.observability)",
             "  Note: span times in ms (Self leaves out the spans opened "
             "inside); counter rows aggregate the added deltas, Value is "
             "the running total."]
    fmt = "%-36s %8s %12s %12s %10s %10s %10s %10s"
    lines.append("")
    lines.append("Spans (phases)")
    lines.append("=" * 14)
    lines.append(fmt % ("Name", "Count", "Total(ms)", "Self(ms)", "Min",
                        "Max", "P50", "P99"))
    for name, s in agg["spans"].items():
        lines.append(fmt % (name, s["count"], "%.3f" % s["total_ms"],
                            "%.3f" % s["self_ms"],
                            "%.3f" % s["min_ms"], "%.3f" % s["max_ms"],
                            "%.3f" % s["p50_ms"], "%.3f" % s["p99_ms"]))
    fmtc = "%-36s %8s %12s %10s %10s %10s %10s %12s"
    lines.append("")
    lines.append("Counters")
    lines.append("=" * 8)
    lines.append(fmtc % ("Name", "Count", "Total", "Min", "Max",
                         "P50", "P99", "Value"))
    for name, s in agg["counters"].items():
        lines.append(fmtc % (name, s["count"], "%g" % s["total"],
                             "%g" % s["min"], "%g" % s["max"],
                             "%g" % s["p50"], "%g" % s["p99"],
                             "%g" % s["value"]))
    if agg["histograms"]:
        fmth = "%-32s %8s %12s %10s %10s %10s %10s %10s %10s"
        lines.append("")
        lines.append("Histograms (log-bucketed, exact count/sum)")
        lines.append("=" * 10)
        lines.append(fmth % ("Name", "Count", "Sum", "Mean", "P50",
                             "P90", "P99", "P99.9", "Max"))
        for name, h in agg["histograms"].items():
            lines.append(fmth % (
                name, h["count"], "%.3f" % h["sum"], "%.3f" % h["mean"],
                "%.3f" % h["p50"], "%.3f" % h["p90"],
                "%.3f" % h["p99"], "%.3f" % h["p999"],
                "%.3f" % h["max"]))
    from . import events as _events
    lines.extend(_events.format_recent())
    lines.extend(_format_timeseries())
    from . import dist
    lines.extend(dist.format_skew_table())
    from . import attribution
    lines.extend(attribution.format_ops_table())
    from . import costmodel
    lines.extend(costmodel.format_calibration_table())
    from . import goodput
    lines.extend(goodput.format_table_section())
    if trace_dir is not None:
        lines.extend(format_idle_by_span(trace_dir))
    if core.dropped():
        lines.append("")
        lines.append("(%d oldest records dropped from the ring; "
                     "percentiles cover the retained suffix)"
                     % core.dropped())
    return "\n".join(lines)


# ------------------------------------- the device, by program span ---

SPAN_PREFIX = "mx."
OUTSIDE = "(outside every span)"
_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")


def _nest(lane_spans):
    """One thread's spans [(start, end, name)], properly nested ->
    (change points [(t, name, width)]: from t on the innermost open span
    is `name`, None for none; {name: [calls, total, self]})."""
    points, stats, stack = [], {}, []     # stack: [start, end, name, child]

    def close(upto):
        while stack and (upto is None or stack[-1][1] <= upto):
            s, e, name, child = stack.pop()
            st = stats.setdefault(name, [0, 0.0, 0.0])
            st[0] += 1
            st[1] += e - s
            st[2] += e - s - child
            if stack:
                stack[-1][3] += e - s
                points.append((e, stack[-1][2],
                               stack[-1][1] - stack[-1][0]))
            else:
                points.append((e, None, None))

    for s, e, name in sorted(lane_spans, key=lambda x: (x[0], -x[1])):
        close(s)
        stack.append([s, e, name, 0.0])
        points.append((s, name, e - s))
    close(None)
    return points, stats


def idle_by_span(spans, launches, busy):
    """The device's launches and idle time laid against host spans, all on
    one clock. `spans`: [(start, end, name, lane)] (lane: the host thread),
    `launches`: [start] of device programs, `busy`: [(start, end)] in
    which the device ran an operation. A launch belongs to the innermost
    span open at its start, a gap between busy intervals to the innermost
    span open at its midpoint (the narrowest when threads overlap), else
    to OUTSIDE. Returns {name: {"calls", "total", "self", "launches",
    "idle"}}; times in the unit given."""
    lanes = {}
    for s, e, name, lane in spans:
        lanes.setdefault(lane, []).append((s, e, name))
    rows, lookups = {}, []

    def row(name):
        return rows.setdefault(name, {"calls": 0, "total": 0.0,
                                      "self": 0.0, "launches": 0,
                                      "idle": 0.0})

    for lane_spans in lanes.values():
        points, stats = _nest(lane_spans)
        lookups.append(([p[0] for p in points], points))
        for name, (calls, total, own) in stats.items():
            r = row(name)
            r["calls"] += calls
            r["total"] += total
            r["self"] += own

    def innermost(t):
        best, width = OUTSIDE, None
        for times, points in lookups:
            i = bisect.bisect_right(times, t) - 1
            if i >= 0 and points[i][1] is not None \
                    and (width is None or points[i][2] < width):
                best, width = points[i][1], points[i][2]
        return best

    for t in launches:
        row(innermost(t))["launches"] += 1
    cur_e = None
    for s, e in sorted(busy):
        if cur_e is not None and s > cur_e:
            row(innermost((cur_e + s) / 2.0))["idle"] += s - cur_e
        cur_e = e if cur_e is None else max(cur_e, e)
    return rows


def read_xplane(path):
    """(spans, launches, busy, device plane name) of one `.xplane.pb`, in
    ns on the profiler's clock: the `mx.*` annotations of `/host:CPU`, and
    the `XLA Modules` starts and `XLA Ops` intervals of the first device
    plane (None and empty lists when the trace has none, as on a CPU)."""
    from jax.profiler import ProfileData
    spans, launches, busy, device = [], [], [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for lane, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns,
                                      e.name, lane))
        elif device is None and _DEVICE_PLANE.match(plane.name):
            device = plane.name
            for line in plane.lines:
                if line.name == "XLA Modules":
                    launches = [e.start_ns for e in line.events]
                elif line.name == "XLA Ops":
                    busy = [(e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
    return spans, launches, busy, device


def format_idle_by_span(trace_dir):
    """The "Device by program span" section of the aggregate table, from
    the newest `.xplane.pb` under `trace_dir`; [] when there is none."""
    import glob
    import os
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    spans, launches, busy, device = read_xplane(files[-1])
    rows = idle_by_span(spans, launches, busy)
    idle_all = sum(r["idle"] for r in rows.values())
    lines = ["", "Device by program span (%s; %d launches, %.3f ms idle "
             "between operations)" % (
                 device or "no device plane in this trace",
                 len(launches), idle_all / 1e6),
             "=" * 22]
    fmt = "%-36s %8s %12s %12s %10s %12s %8s"
    lines.append(fmt % ("Name", "Calls", "Total(ms)", "Self(ms)",
                        "Launches", "Idle(ms)", "Idle%"))
    for name in sorted(rows, key=lambda n: (n == OUTSIDE, n)):
        r = rows[name]
        lines.append(fmt % (
            name, r["calls"] or "-", "%.3f" % (r["total"] / 1e6),
            "%.3f" % (r["self"] / 1e6), r["launches"],
            "%.3f" % (r["idle"] / 1e6),
            "%.1f" % (100.0 * r["idle"] / idle_all) if idle_all else "-"))
    return lines


# ------------------------------------------------- prometheus --------

def _prom_name(name):
    """One name sanitized to the Prometheus charset [a-zA-Z0-9_]
    (leading digits get a ``_`` prefix). Lossy on its own — named
    scopes like ``block[0]/attn`` and ``block(0).attn`` collapse to
    the same series — so exposition paths use :func:`_prom_name_map`
    for a collision-free mapping over the whole name set."""
    out = []
    for ch in name:
        out.append(ch if ch.isalnum() or ch == "_" else "_")
    s = "".join(out)
    if s and s[0].isdigit():
        s = "_" + s
    return s or "_"


def _prom_name_map(names):
    """{original -> sanitized-and-unique} over ``names``. Collisions
    (distinct originals sanitizing to the same series name) get a
    deterministic ``_2``/``_3``... suffix in sorted-original order —
    the sorted-first original keeps the bare name, so the mapping is
    stable for a given name set regardless of iteration order."""
    by_sanitized = {}
    for name in sorted(set(names)):
        by_sanitized.setdefault(_prom_name(name), []).append(name)
    out = {}
    used = set(by_sanitized)
    for base in sorted(by_sanitized):
        members = by_sanitized[base]
        out[members[0]] = base
        n = 2
        for name in members[1:]:
            cand = "%s_%d" % (base, n)
            while cand in used:
                n += 1
                cand = "%s_%d" % (base, n)
            used.add(cand)
            out[name] = cand
            n += 1
    return out


def prometheus_text():
    """Prometheus exposition format: spans as summary-style series
    (count/sum + p50/p99 quantile samples), counters as *_total plus a
    last-value gauge. Suitable for a node_exporter textfile collector
    on long runs."""
    agg = aggregate()
    lines = [
        "# HELP mxnet_obs_span_ms host-side phase spans "
        "(mxnet_tpu.observability)",
        "# TYPE mxnet_obs_span_ms summary"]
    for name, s in agg["spans"].items():
        lab = 'phase="%s"' % name
        lines.append('mxnet_obs_span_ms_count{%s} %d' % (lab, s["count"]))
        lines.append('mxnet_obs_span_ms_sum{%s} %.6f'
                     % (lab, s["total_ms"]))
        for q, key in (("0.5", "p50_ms"), ("0.99", "p99_ms")):
            lines.append('mxnet_obs_span_ms{%s,quantile="%s"} %.6f'
                         % (lab, q, s[key]))
    cmap = _prom_name_map(agg["counters"])
    lines.append("# HELP mxnet_obs_counter_total accumulated counter "
                 "deltas")
    lines.append("# TYPE mxnet_obs_counter_total counter")
    for name, s in agg["counters"].items():
        lines.append('mxnet_obs_counter_total{name="%s"} %g'
                     % (cmap[name], s["total"]))
    lines.append("# HELP mxnet_obs_value last recorded value per "
                 "counter/gauge")
    lines.append("# TYPE mxnet_obs_value gauge")
    for name, s in agg["counters"].items():
        lines.append('mxnet_obs_value{name="%s"} %g'
                     % (cmap[name], s["value"]))
    from . import histogram as _hist
    hists = _hist.histograms()
    if hists:
        lines.append("# HELP mxnet_obs_hist log-bucketed latency "
                     "histograms (serving.* request distributions)")
        lines.append("# TYPE mxnet_obs_hist histogram")
        hmap = _prom_name_map(hists)
        for name, h in sorted(hists.items()):
            pname = hmap[name]
            for le, cum in h.cumulative_buckets():
                lines.append(
                    'mxnet_obs_hist_bucket{name="%s",le="%s"} %d'
                    % (pname,
                       "+Inf" if le == float("inf") else "%g" % le,
                       cum))
            lines.append('mxnet_obs_hist_sum{name="%s"} %.6f'
                         % (pname, h.sum))
            lines.append('mxnet_obs_hist_count{name="%s"} %d'
                         % (pname, h.count))
            for q, label in _hist.QUANTILES:
                lines.append(
                    'mxnet_obs_hist_quantile{name="%s",quantile="%s"} '
                    '%.6f' % (pname, q, h.percentile(q)))
    anomalies = [(name, s) for name, s in agg["counters"].items()
                 if name.startswith("obs.anomaly.")]
    if anomalies:
        lines.append("# HELP mxnet_obs_anomaly trend-detector firings "
                     "(timeseries.py detectors over fleet history)")
        lines.append("# TYPE mxnet_obs_anomaly counter")
        amap = _prom_name_map(n[len("obs.anomaly."):]
                              for n, _s in anomalies)
        for name, s in anomalies:
            lines.append('mxnet_obs_anomaly_%s %g'
                         % (amap[name[len("obs.anomaly."):]],
                            s["value"]))
    from . import goodput
    lines.extend(goodput.prometheus_lines())
    from . import dist
    lines.append("# HELP mxnet_obs_rank this process's rank (label the "
                 "scrape per worker in multi-host jobs)")
    lines.append("# TYPE mxnet_obs_rank gauge")
    lines.append('mxnet_obs_rank %d' % dist.process_index())
    lines.append('mxnet_obs_dropped_records %d' % core.dropped())
    return "\n".join(lines) + "\n"


def write_prometheus(path=None):
    """Write the textfile; ``path`` defaults to MXNET_OBS_PROM. The
    write goes through a .tmp rename so a concurrent scrape never sees
    a torn file. Returns the path, or None when no target configured.
    Multi-process runs rank-suffix the file (rank 0 keeps the bare
    name) — one textfile per worker, no clobbering."""
    import os
    path = path or _fastenv.get("MXNET_OBS_PROM")
    if not path:
        return None
    from . import dist
    path = dist.rank_trace_path(path)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(prometheus_text())
    os.replace(tmp, path)
    return path
