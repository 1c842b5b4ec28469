"""Per-operator attribution — named-scope propagation + compiled-program
cost/memory breakdown (ISSUE 4 tentpole).

The reference framework's defining observability feature was the
per-operator profiler (``profiler.set_config(profile_all=True)`` emitted
one lane per executed op). On this stack the executed unit is a fused
XLA program, so per-op attribution has two halves:

1. **Scope propagation** (write side). When telemetry is on, Gluon
   ``Block.__call__`` binds ``jax.named_scope(block.name)`` around
   forward and ``executor.build_graph_fn`` binds
   ``jax.named_scope(node.name)`` around every symbol node's primitive
   emission. XLA preserves those frames as ``op_name`` metadata on every
   optimized (even fused) instruction, so each instruction names the
   block that produced it. Off, both sites reduce to one guarded branch
   (the PR 2 contract); scope names reach the HLO only if telemetry was
   on when the program was TRACED.

2. **Program breakdown** (read side). The instrumented jit boundaries
   (CachedOp, Executor) register each distinct executable here — the
   jitted callable plus the abstract ``ShapeDtypeStruct`` signature, no
   device buffers held — and the recompile detector's backend-compile
   events invalidate stale analyses. On demand (profiler.dump, the
   aggregate table, tools/obs_ops.py) each program is lowered and its
   optimized HLO parsed (``observability.hlo``): per-instruction flops /
   HBM bytes / output bytes grouped by source scope, plus a
   def-to-last-use peak-watermark attribution, cached per executable.

Reporting: ``format_ops_table()`` (appended to
``profiler.dumps(aggregate=True)``) ranks scopes by estimated roofline
time share; ``publish_counters()`` exports ``ops.<scope>.flops`` /
``ops.<scope>.hbm_bytes`` gauges through the normal chrome-trace /
Prometheus paths; ``summary()`` is the JSON the perf-regression
sentinel (``tools/obs_regression.py``) diffs against a committed
baseline; ``compare_summaries()`` is the diff itself.

Knobs: ``MXNET_OBS_OPS`` (default on when MXNET_OBS is on) gates both
halves; ``MXNET_OBS_OPS_TOPK`` table depth. The roofline used for the
bound/share columns is the chip's published peaks by ``device_kind``
(``mxnet_tpu/chip.py``).
"""

import threading

from . import core
from . import hlo
from .. import _fastenv

__all__ = ["ops_enabled", "note_scope", "known_scopes", "register_program",
           "needs_program", "abstract_args", "on_compile", "analyses",
           "program_analysis",
           "summary", "format_ops_table", "publish_counters",
           "compare_summaries", "reset", "DEFAULT_TOLERANCES"]

_MAX_PROGRAMS = 64
UNATTRIBUTED = "(unattributed)"

_lock = threading.Lock()
_scopes = set()          # named scopes stamped at trace time
_programs = {}           # (origin, signature) -> entry dict, insertion order


def ops_enabled():
    """Master gate for scope propagation + breakdown: telemetry on AND
    MXNET_OBS_OPS not disabled (default on)."""
    if not core.enabled():
        return False
    v = _fastenv.get("MXNET_OBS_OPS", "1")
    return v not in ("", "0", "false", "False")


def topk():
    return int(_fastenv.get("MXNET_OBS_OPS_TOPK", 10))


def peak_flops():
    """Roofline compute peak (flop/s) for the bound/share columns: the
    attached chip's published bf16 peak by device_kind (mxnet_tpu/
    chip.py; the modelled v5e in a CPU-pinned process)."""
    from .. import chip
    return chip.peaks().bf16_flops


def hbm_bw():
    """Roofline HBM bandwidth (bytes/s), from the same table."""
    from .. import chip
    return chip.peaks().hbm_bytes_per_s


def note_scope(name):
    """Record a named scope stamped at trace time (the read side only
    attributes op_name components it saw the runtime emit)."""
    if name and name not in _scopes:
        with _lock:
            _scopes.add(name)


def known_scopes():
    with _lock:
        return set(_scopes)


# --------------------------------------------------- program registry --

def abstract_args(tree):
    """The args pytree with every array leaf reduced to its aval —
    holds shapes/dtypes for a later ``fn.lower``, never buffers."""
    import jax

    def leaf(a):
        if hasattr(a, "shape") and hasattr(a, "dtype"):
            sharding = getattr(a, "sharding", None)
            if sharding is not None:
                # keep the sharding so a mesh program (the kvstore's
                # bucketed reduce) re-lowers to the SAME collective
                try:
                    return jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=sharding)
                except TypeError:
                    pass
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return a
    return jax.tree.map(leaf, tree)


def needs_program(origin, signature):
    """True until ``register_program`` has seen (origin, signature) —
    lets call sites skip building analysis closures on the warm path."""
    return (origin, signature) not in _programs


def register_program(origin, signature, fn, args):
    """An instrumented jit boundary (CachedOp.__call__, Executor
    forward/backward) reporting the executable it is about to run.
    Idempotent per (origin, signature) — one dict probe on the warm
    path. ``args`` are the live call arguments; only their abstract
    signature is retained."""
    key = (origin, signature)
    if key in _programs:
        return
    with _lock:
        if key in _programs:
            return
        while len(_programs) >= _MAX_PROGRAMS:
            _programs.pop(next(iter(_programs)))
        _programs[key] = {"origin": origin, "signature": signature,
                          "fn": fn, "abstract_args": abstract_args(args),
                          "analysis": None}


def on_compile(origin, kind):
    """Recompile-detector hook: a fresh XLA executable was built —
    any cached analysis for that origin is stale."""
    if kind != "backend_compile":
        return
    with _lock:
        for (org, _sig), ent in _programs.items():
            if origin is None or org == origin:
                ent["analysis"] = None


def _analyze(entry):
    """Lower + compile the registered program from its abstract
    signature and break the optimized HLO down per scope. Lowering
    re-traces (the live executable is not reachable through public
    jax API), so this runs only at report time and is cached."""
    from . import recompile
    fn, args = entry["fn"], entry["abstract_args"]
    with recompile.suppress_events():
        compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    # no runtime-registered scopes (a raw-jax program like the kvstore
    # reduce or a bench's hand-built step): fall back to the heuristic
    # op_name path split so the table still names source structure
    known = known_scopes() or None
    rows = hlo.attribute_rows(hlo.parse_hlo(text), known)
    scopes, totals = hlo.group_by_scope(rows,
                                        unattributed=UNATTRIBUTED)
    peak, peak_scopes = hlo.peak_watermark(rows,
                                           unattributed=UNATTRIBUTED)
    analysis = {
        "origin": entry["origin"], "signature": entry["signature"],
        "scopes": scopes, "totals": totals,
        "peak_bytes": peak, "peak_scopes": peak_scopes,
        "xla_cost": hlo.compiled_cost(compiled),
    }
    try:
        ma = compiled.memory_analysis()
        analysis["memory"] = {
            k: int(getattr(ma, k)) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "alias_size_in_bytes",
             "generated_code_size_in_bytes") if hasattr(ma, k)}
    except Exception:
        analysis["memory"] = {}
    return analysis


def analyses(refresh=False):
    """Per-program breakdowns for every registered executable (computed
    lazily, cached until the next backend compile for the origin)."""
    with _lock:
        entries = list(_programs.values())
    out = []
    for entry in entries:
        if entry["analysis"] is None or refresh:
            try:
                entry["analysis"] = _analyze(entry)
            except Exception as exc:     # backend without as_text, etc.
                entry["analysis"] = {
                    "origin": entry["origin"],
                    "signature": entry["signature"],
                    "scopes": {}, "totals": {}, "peak_bytes": 0,
                    "peak_scopes": {}, "error": str(exc)}
        out.append(entry["analysis"])
    return out


def program_analysis(origin, signature=None):
    """The cached breakdown for ONE registered executable — the memory
    budget's preflight source (``membudget.preflight`` reads
    ``memory`` / ``peak_bytes`` / ``peak_scopes`` from it). Exact
    (origin, signature) when the caller has the recompile-detector
    signature, else the first entry for ``origin``. None when the
    program was never registered; computes (and caches) the analysis on
    first use, same as :func:`analyses`."""
    with _lock:
        entry = _programs.get((origin, signature))
        if entry is None:
            for (org, _sig), ent in _programs.items():
                if org == origin:
                    entry = ent
                    break
    if entry is None:
        return None
    if entry["analysis"] is None:
        try:
            entry["analysis"] = _analyze(entry)
        except Exception as exc:         # backend without as_text, etc.
            entry["analysis"] = {
                "origin": entry["origin"],
                "signature": entry["signature"],
                "scopes": {}, "totals": {}, "peak_bytes": 0,
                "peak_scopes": {}, "error": str(exc)}
    return entry["analysis"]


# ----------------------------------------------------------- summary --

def summary(refresh=False):
    """Aggregate across every registered program: overall totals plus
    per-scope flops / HBM bytes / counts — the sentinel's unit of
    comparison. Peak-watermark attribution comes from the program with
    the highest peak (the step's memory high-water mark)."""
    per = [a for a in analyses(refresh) if not a.get("error")]
    scopes = {}
    totals = {"flops": 0.0, "hbm_bytes": 0, "out_bytes": 0, "count": 0,
              "attributed_flops": 0.0, "attributed_hbm_bytes": 0,
              "programs": len(per)}
    peak_prog = None
    for a in per:
        t = a["totals"]
        for k in ("flops", "hbm_bytes", "out_bytes", "count",
                  "attributed_flops", "attributed_hbm_bytes"):
            totals[k] += t.get(k, 0)
        for scope, ent in a["scopes"].items():
            dst = scopes.setdefault(scope, {"count": 0, "flops": 0.0,
                                            "hbm_bytes": 0,
                                            "out_bytes": 0})
            for k in dst:
                dst[k] += ent.get(k, 0)
        if peak_prog is None or a["peak_bytes"] > peak_prog["peak_bytes"]:
            peak_prog = a
    totals["peak_bytes"] = peak_prog["peak_bytes"] if peak_prog else 0
    return {"totals": totals, "scopes": scopes,
            "peak_scopes": dict(peak_prog["peak_scopes"])
            if peak_prog else {},
            "programs": [{"origin": a["origin"],
                          "signature": a["signature"],
                          "totals": a["totals"],
                          "peak_bytes": a["peak_bytes"]} for a in per]}


def _ranked(scopes):
    """Scopes ranked by estimated roofline time (the resource each is
    actually bound by), heaviest first."""
    pf, bw = peak_flops(), hbm_bw()

    def t_est(ent):
        return max(ent["flops"] / pf, ent["hbm_bytes"] / bw)
    return sorted(scopes.items(), key=lambda kv: -t_est(kv[1])), t_est


def format_ops_table(summ=None, k=None):
    """The per-scope top-K table as text lines — appended to
    ``profiler.dumps(aggregate=True)`` after the counter/skew sections.
    Empty when no compiled program has been registered."""
    if summ is None:
        if not _programs:
            return []
        summ = summary()
    scopes = summ.get("scopes") or {}
    if not scopes:
        return []
    k = topk() if k is None else k
    ranked, t_est = _ranked(scopes)
    t_total = sum(t_est(e) for _, e in ranked) or 1.0
    pf = peak_flops()
    totals = summ["totals"]
    fmt = "%-44s %6s %10s %10s %8s %5s %6s %6s"
    lines = ["",
             "Per-operator attribution (%d program%s, top %d scopes by "
             "roofline time)" % (totals.get("programs", 0),
                                 "" if totals.get("programs") == 1
                                 else "s", min(k, len(ranked))),
             "=" * 26,
             fmt % ("Scope", "Instrs", "GFLOP", "HBM MB", "FLOP/B",
                    "Bound", "Time%", "MFU%")]
    for scope, ent in ranked[:k]:
        ai = ent["flops"] / max(ent["hbm_bytes"], 1)
        t = t_est(ent)
        bound = "mxu" if ent["flops"] / pf >= ent["hbm_bytes"] / hbm_bw() \
            else "hbm"
        mfu = ent["flops"] / (t_total * pf)
        lines.append(fmt % (
            scope[-44:], ent["count"], "%.3f" % (ent["flops"] / 1e9),
            "%.2f" % (ent["hbm_bytes"] / 1e6), "%.1f" % ai, bound,
            "%.1f" % (100.0 * t / t_total), "%.2f" % (100.0 * mfu)))
    att_f = totals.get("attributed_flops", 0.0)
    att_b = totals.get("attributed_hbm_bytes", 0)
    lines.append(
        "  attributed: %.1f%% of %.3f GFLOP, %.1f%% of %.2f MB HBM; "
        "peak watermark %.2f MB"
        % (100.0 * att_f / max(totals.get("flops", 0.0), 1e-9),
           totals.get("flops", 0.0) / 1e9,
           100.0 * att_b / max(totals.get("hbm_bytes", 0), 1),
           totals.get("hbm_bytes", 0) / 1e6,
           totals.get("peak_bytes", 0) / 1e6))
    return lines


def publish_counters(summ=None):
    """Export the per-scope numbers as ``ops.<scope>.flops`` /
    ``ops.<scope>.hbm_bytes`` gauges — they ride the existing ring ->
    chrome-trace / Prometheus paths. Called by ``profiler.dump()``."""
    if not core.enabled() or not _programs:
        return
    summ = summary() if summ is None else summ
    for scope, ent in summ["scopes"].items():
        core.gauge("ops.%s.flops" % scope).set(ent["flops"])
        core.gauge("ops.%s.hbm_bytes" % scope).set(ent["hbm_bytes"])
    core.gauge("ops.peak_bytes").set(summ["totals"].get("peak_bytes", 0))


# ---------------------------------------------------------- sentinel --

DEFAULT_TOLERANCES = {"flops": 0.15, "hbm_bytes": 0.15,
                      "out_bytes": 0.25, "peak_bytes": 0.25,
                      "count": 0.5}


def compare_summaries(baseline, current, tolerances=None):
    """Diff a run's attribution summary against a committed baseline.

    A metric REGRESSES when ``current > baseline * (1 + tol)`` —
    checked on the aggregate totals and per-scope flops/hbm_bytes.
    Returns {"regressions": [...], "improvements": [...],
    "notes": [...]}; the sentinel exits nonzero iff regressions is
    non-empty. Scopes present only on one side produce notes (renames /
    structure changes), not failures — the aggregate totals still catch
    real growth hiding behind a rename.
    """
    tol = dict(DEFAULT_TOLERANCES)
    tol.update(tolerances or {})
    regressions, improvements, notes = [], [], []

    def check(path, metric, base, cur):
        t = tol.get(metric, 0.15)
        if base is None or cur is None:
            return
        base = float(base)
        cur = float(cur)
        if cur > base * (1.0 + t) + 1e-9:
            regressions.append(
                {"where": path, "metric": metric, "baseline": base,
                 "current": cur,
                 "ratio": cur / base if base else float("inf"),
                 "tolerance": t})
        elif base > 0 and cur < base * (1.0 - t):
            improvements.append(
                {"where": path, "metric": metric, "baseline": base,
                 "current": cur, "ratio": cur / base})

    bt = baseline.get("totals", {})
    ct = current.get("totals", {})
    for metric in ("flops", "hbm_bytes", "out_bytes", "peak_bytes"):
        check("totals", metric, bt.get(metric), ct.get(metric))
    bs = baseline.get("scopes", {})
    cs = current.get("scopes", {})
    for scope in sorted(set(bs) | set(cs)):
        if scope not in cs:
            notes.append("scope %r in baseline but not in current run "
                         "(renamed or removed)" % scope)
            continue
        if scope not in bs:
            notes.append("scope %r new in current run" % scope)
            continue
        for metric in ("flops", "hbm_bytes"):
            check("scope:%s" % scope, metric, bs[scope].get(metric),
                  cs[scope].get(metric))
    return {"regressions": regressions, "improvements": improvements,
            "notes": notes}


def reset():
    """Forget scopes + registered programs (tests, fresh sessions)."""
    with _lock:
        _scopes.clear()
        _programs.clear()
