"""Reduction of a JAX profiler trace (`*.xplane.pb`) to the numbers the
per-layer metrics read. Looked at by hand first (PR 24, one v5e): each
chip is a plane `/device:TPU:<n>`; its line `XLA Modules` holds one event
per program launch, its line `XLA Ops` one event per operation, with
`start_ns`/`duration_ns` on the profiler's clock; `/host:CPU` holds the
host threads, `jax.profiler.TraceAnnotation` spans on the line `python`.

    busy_s     union of the intervals in which an operation ran, per
               device plane, averaged over the planes
    launches   events on the `XLA Modules` lines (all device planes)
    device_ops the operations that took most time, [[name, seconds]]
    idle_gaps  the time the device sat idle inside the window, by the
               benchmark's own host span that was open at the middle of
               each gap, [[span, seconds]]
"""

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError("no .xplane.pb under %s" % trace_dir)
    return files[-1]


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def union_ns(intervals):
    """Total length of the union of [(start, end)] intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals):
    """[(start, end)] of the holes between merged intervals."""
    out, cur_e = [], None
    for s, e in sorted(intervals):
        if cur_e is not None and s > cur_e:
            out.append((cur_e, s))
        cur_e = e if cur_e is None else max(cur_e, e)
    return out


def _short(name):
    """'%fusion.3 = bf16[..] fusion(...)' -> 'fusion.3'."""
    return name.split(" = ")[0].lstrip("%")[:60]


def reduce(profile, top=10):
    """ProfileData -> {"busy_s", "launches", "planes", "ops", "device_ops",
    "idle_gaps"}. The two rankings are of the first device plane."""
    busy, launches, planes = [], 0, 0
    op_time, first_ops, spans = {}, [], []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            first = planes == 0
            planes += 1
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    for e in line.events:
                        ops.append((e.start_ns, e.start_ns + e.duration_ns))
                        if first:
                            key = _short(e.name)
                            op_time[key] = op_time.get(key, 0.0) \
                                + e.duration_ns
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        modules.append((e.start_ns,
                                        e.start_ns + e.duration_ns))
            if first:
                first_ops = ops if ops else modules
            launches += len(modules)
            busy.append(union_ns(ops if ops else modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns, e.name))
    gap_time = {}
    for s, e in _gaps(first_ops):
        mid, label, width = (s + e) / 2.0, "outside the benchmark's spans", None
        for ss, se, name in spans:          # the innermost open span
            if ss <= mid <= se and (width is None or se - ss < width):
                label, width = name, se - ss
        gap_time[label] = gap_time.get(label, 0.0) + (e - s)

    def ranked(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
            "launches": launches, "planes": planes,
            "ops": len(first_ops),
            "device_ops": ranked(op_time), "idle_gaps": ranked(gap_time)}


def reduce_dir(trace_dir):
    return reduce(load(find_xplane(trace_dir)))
