"""Calibrated roofline cost model over the performance archive.

The PR 4 attribution layer already derives analytic flops/HBM-bytes
per scope; the roofline bound (``flops/peak`` vs ``bytes/bw``) is a
*shape* of the truth but not a clock — real kernels land at some
achieved fraction of peak that differs per scope family. This module
closes the gap the way TVM's learned cost model does, but with the
cheapest learner that works: fit the archived measurements
(observability/profile_store.py) against the two roofline terms by
least squares, per scope family, and report how well the fit explains
the data (median relative error = the calibration error).

    model = costmodel.fit()                  # from MXNET_OBS_PROFILE_DIR
    costmodel.predict(scope="paged_decode_kernel")   # -> predicted ms
    costmodel.predict(flops=f, hbm_bytes=b)          # -> predicted ms

Fit form per family: ``ms ~= a * flops_ms + b * bytes_ms + c`` where
``flops_ms = flops / peak_flops * 1e3`` and ``bytes_ms = hbm_bytes /
hbm_bw * 1e3`` (peaks from ``mxnet_tpu/chip.py``'s table, by
``device_kind``). With fewer
than 3 points a single achieved-fraction scale ``ms ~= alpha *
max(flops_ms, bytes_ms)`` is fitted instead; a family with no
archived points falls back to the global fit.

Consumers: ``export.aggregate_table()`` / ``tools/obs_ops.py`` append
the predicted-vs-measured calibration table (worst-calibrated scopes
named — a bad fit means the analytic model is missing traffic, the
autotuner pre-flight signal); ``kernels/common.choose_block_k``
consults ``archived_block_k()`` so a measured winner beats the static
heuristic; ``membudget.predicted_step_ms`` exposes the prediction to
admission decisions. All entry points are no-ops returning None/[]
when the archive is off or empty, and never raise.
"""

import math
import os
import threading

from . import profile_store

__all__ = ["fit", "cached_fit", "predict", "predict_ms",
           "calibration_report", "format_calibration_table",
           "archived_block_k", "reset_cache"]

MIN_LSQ_POINTS = 3       # below this, fit the single-scale model
_EPS = 1e-9

_cache_lock = threading.Lock()
_fit_cache = [None]      # (stamp, records, model)


def _peaks():
    from . import attribution
    return attribution.peak_flops(), attribution.hbm_bw()


def _roofline_terms(flops, hbm_bytes, peak_flops, hbm_bw):
    """(flops_ms, bytes_ms): the two analytic time terms."""
    return (1e3 * float(flops or 0) / max(peak_flops, _EPS),
            1e3 * float(hbm_bytes or 0) / max(hbm_bw, _EPS))


def _points(records):
    """Measured (family, scope, sig, flops_ms, bytes_ms, measured_ms)
    tuples from scope records that carry both a timing and an
    attribution estimate."""
    peak_flops, hbm_bw = _peaks()
    pts = []
    for r in records:
        if r.get("kind") != "scope":
            continue
        stats = r.get("stats") or {}
        y = stats.get("p50_ms")
        if not y or y <= 0:
            continue
        flops, hbm = r.get("flops", 0), r.get("hbm_bytes", 0)
        if not flops and not hbm:
            continue
        f_ms, b_ms = _roofline_terms(flops, hbm, peak_flops, hbm_bw)
        pts.append((profile_store.normalize_scope(r.get("scope", "")),
                    r.get("scope", ""), r.get("sig", ""),
                    f_ms, b_ms, float(y)))
    return pts


def _fit_points(pts):
    """Fit one family's points -> model dict with kind 'lsq' (normal
    least squares over [flops_ms, bytes_ms, 1]) or 'scale' (achieved
    fraction of the roofline bound) plus its calibration error."""
    if not pts:
        return None
    ys = [p[5] for p in pts]
    if len(pts) >= MIN_LSQ_POINTS:
        try:
            import numpy as np
            X = np.array([[p[3], p[4], 1.0] for p in pts])
            y = np.array(ys)
            coef, _res, _rank, _sv = np.linalg.lstsq(X, y, rcond=None)
            model = {"kind": "lsq", "coef": [float(c) for c in coef],
                     "n": len(pts)}
        except Exception:
            model = None
        if model is not None:
            model["calib_err"] = _calib_err(model, pts)
            return model
    ratios = sorted(p[5] / max(max(p[3], p[4]), _EPS) for p in pts)
    alpha = ratios[len(ratios) // 2]
    model = {"kind": "scale", "alpha": float(alpha), "n": len(pts)}
    model["calib_err"] = _calib_err(model, pts)
    return model


def predict_ms(model, flops_ms, bytes_ms):
    """Apply one fitted family model to the two roofline terms."""
    if model is None:
        return None
    if model["kind"] == "lsq":
        a, b, c = model["coef"]
        return max(a * flops_ms + b * bytes_ms + c, 0.0)
    return model["alpha"] * max(flops_ms, bytes_ms)


def _calib_err(model, pts):
    """Median relative error of the fit over its own points."""
    errs = sorted(abs((predict_ms(model, p[3], p[4]) or 0) - p[5])
                  / max(p[5], _EPS) for p in pts)
    return errs[len(errs) // 2] if errs else float("inf")


def fit(records=None, dirpath=None, exclude_scope=None):
    """Fit per-family models (+ a global fallback) against the archive.
    ``exclude_scope`` holds one normalized scope out of the fit (the
    held-out calibration check). Returns {"families": {...}, "global":
    model-or-None, "n": points} — {"families": {}, "global": None,
    "n": 0} when the archive is off/empty."""
    if records is None:
        records, _ev = profile_store.load(dirpath)
    pts = _points(records)
    if exclude_scope:
        held = profile_store.normalize_scope(exclude_scope)
        pts = [p for p in pts if p[0] != held]
    fams = {}
    for p in pts:
        fams.setdefault(p[0], []).append(p)
    return {"families": {fam: _fit_points(fpts)
                         for fam, fpts in sorted(fams.items())},
            "global": _fit_points(pts), "n": len(pts)}


def _archive_stamp(dirpath=None):
    """Cheap change stamp of the archive dir: (path, mtime_ns, size)
    per file. Appends grow the size, prune's os.replace bumps the
    mtime — either invalidates the cache. None when the store is
    off."""
    d = dirpath or profile_store.store_dir()
    if not d:
        return None
    stamp = [d]
    for p in profile_store.list_files(d):
        try:
            st = os.stat(p)
            stamp.append((p, st.st_mtime_ns, st.st_size))
        except OSError:
            stamp.append((p, -1, -1))
    return tuple(stamp)


def cached_fit(dirpath=None):
    """(records, model) memoized on the archive's mtime/size stamp —
    the hot-caller entry point (membudget's per-admission
    ``predicted_step_ms``), which must not pay a full archive reload +
    lstsq refit per call when nothing changed on disk."""
    stamp = _archive_stamp(dirpath)
    if stamp is None:
        return [], fit(records=[])
    with _cache_lock:
        hit = _fit_cache[0]
        if hit is not None and hit[0] == stamp:
            return hit[1], hit[2]
    records, _ev = profile_store.load(dirpath)
    model = fit(records=records)
    with _cache_lock:
        _fit_cache[0] = (stamp, records, model)
    return records, model


def reset_cache():
    """Drop the cached_fit memo (tests)."""
    with _cache_lock:
        _fit_cache[0] = None


def predict(signature=None, scope=None, flops=None, hbm_bytes=None,
            model=None, records=None, dirpath=None):
    """Predicted per-call ms for a workload.

    Identify the workload by its archive signature key, by scope name,
    or by explicit ``flops``/``hbm_bytes``. When flops/bytes are not
    given they come from the newest archived record matching the
    signature/scope. Returns None when the workload is unknown or the
    archive holds nothing to fit — a caller that gets None falls back
    to its own heuristic."""
    if records is None:
        records, _ev = profile_store.load(dirpath)
    if model is None:
        model = fit(records=records)
    fam = None
    if flops is None and hbm_bytes is None:
        match = None
        for r in reversed(records):     # newest last (load sorts by ts)
            if r.get("kind") != "scope":
                continue
            if signature is not None and r.get("sig") == signature:
                match = r
                break
            if (scope is not None and match is None and
                    profile_store.normalize_scope(r.get("scope", ""))
                    == profile_store.normalize_scope(scope)):
                match = r
                if signature is None:
                    break
        if match is None:
            return None
        flops = match.get("flops", 0)
        hbm_bytes = match.get("hbm_bytes", 0)
        fam = profile_store.normalize_scope(match.get("scope", ""))
    elif scope is not None:
        fam = profile_store.normalize_scope(scope)
    elif signature is not None:
        fam = signature.split("|", 1)[0]
    peak_flops, hbm_bw = _peaks()
    f_ms, b_ms = _roofline_terms(flops, hbm_bytes, peak_flops, hbm_bw)
    m = model["families"].get(fam) if fam else None
    if m is None:
        m = model["global"]
    return predict_ms(m, f_ms, b_ms)


def calibration_report(records=None, dirpath=None):
    """Per-scope predicted-vs-measured rows, worst-calibrated first:
    [{"scope", "sig", "predicted_ms", "measured_ms", "calib_err",
    "n"}]. Empty when the archive is off or holds no usable points."""
    if records is None:
        records, _ev = profile_store.load(dirpath)
    model = fit(records=records)
    if not model["n"]:
        return []
    peak_flops, hbm_bw = _peaks()
    newest = {}
    for r in records:               # load() sorts by ts: last wins
        if r.get("kind") == "scope" and (r.get("stats") or {}).get(
                "p50_ms"):
            newest[r.get("sig", "")] = r
    rows = []
    for sig, r in sorted(newest.items()):
        flops, hbm = r.get("flops", 0), r.get("hbm_bytes", 0)
        if not flops and not hbm:
            continue
        fam = profile_store.normalize_scope(r.get("scope", ""))
        m = model["families"].get(fam) or model["global"]
        if m is None:
            continue
        f_ms, b_ms = _roofline_terms(flops, hbm, peak_flops, hbm_bw)
        measured = float(r["stats"]["p50_ms"])
        predicted = predict_ms(m, f_ms, b_ms)
        rows.append({"scope": fam, "sig": sig,
                     "predicted_ms": predicted,
                     "measured_ms": measured,
                     "calib_err": m["calib_err"], "n": m["n"]})
    rows.sort(key=lambda r: (-r["calib_err"], r["scope"]))
    return rows


def format_calibration_table(records=None, dirpath=None):
    """The aggregate-table section: predicted vs measured per scope
    with the fit's calibration error, worst-calibrated scopes named.
    [] when the archive is off/empty (the section simply disappears
    from ``profiler.dumps(aggregate=True)``). Never raises."""
    try:
        if records is None and dirpath is None \
                and not profile_store.enabled():
            return []
        rows = calibration_report(records=records, dirpath=dirpath)
    except Exception:
        return []
    if not rows:
        return []
    fmt = "%-36s %14s %14s %10s %7s"
    lines = ["", "Cost model calibration (performance archive)",
             "=" * 10,
             fmt % ("Scope", "Predicted(ms)", "Measured(ms)",
                    "CalibErr", "Points")]
    for r in rows:
        lines.append(fmt % (r["scope"][:36],
                            "%.3f" % (r["predicted_ms"] or 0),
                            "%.3f" % r["measured_ms"],
                            "%.0f%%" % (100 * r["calib_err"]),
                            r["n"]))
    worst = [r["scope"] for r in rows[:3] if r["calib_err"] > 0.25]
    if worst:
        lines.append("  worst-calibrated: %s (analytic model missing "
                     "traffic?)" % ", ".join(worst))
    return lines


def archived_block_k(t_max, multiple=1,
                     families=("paged_decode_kernel",
                               "paged_verify_kernel"),
                     dirpath=None):
    """The measured block_k winner for the paged decode-kernel scope
    families, from COMPARABLE measurements only. Archived kernel-scope
    records are grouped by (scope family, normalized program
    signature) — the config fingerprint is deliberately excluded from
    the group key, since it encodes the MXNET_PAGED_BLOCK_K knob being
    compared — and a winner must come from ONE group holding >= 2
    distinct candidates that tile this ``t_max`` (an actual measured
    A/B on the same workload shape): a block_k measured only on small
    paged workloads must not win a pooled median and get applied to a
    much larger cache, and flash_decode (which does not honor the
    paged knob) is out of the default families. Within the
    best-evidenced group (most distinct candidates, then most
    measurements) each candidate scores by its median measured p50;
    the fastest wins. None when no group holds a comparable A/B — the
    caller keeps its static heuristic. The predict-and-prune entry
    point ROADMAP item 5 deferred."""
    records, _ev = profile_store.load(dirpath)
    groups = {}
    for r in records:
        if r.get("kind") != "scope":
            continue
        fam = profile_store.normalize_scope(r.get("scope", ""))
        if fam not in families:
            continue
        y = (r.get("stats") or {}).get("p50_ms")
        raw = (r.get("config") or {}).get("env", {}).get(
            "MXNET_PAGED_BLOCK_K")
        if not y or not raw:
            continue
        try:
            bk = int(raw)
        except ValueError:
            continue
        if bk <= 0 or bk % multiple or t_max % bk or bk > t_max:
            continue
        key = (fam, profile_store.normalize_signature(
            r.get("signature", "")))
        groups.setdefault(key, {}).setdefault(bk, []).append(float(y))
    best_rank, best_by_bk = None, None
    for key, by_bk in sorted(groups.items()):
        if len(by_bk) < 2:      # one candidate is not a comparison
            continue
        rank = (len(by_bk), sum(len(v) for v in by_bk.values()))
        if best_rank is None or rank > best_rank:
            best_rank, best_by_bk = rank, by_bk
    if best_by_bk is None:
        return None
    best, best_ms = None, math.inf
    for bk, ys in sorted(best_by_bk.items()):
        ys.sort()
        med = ys[len(ys) // 2]
        if med < best_ms:
            best, best_ms = bk, med
    return best


_ = os   # parity with sibling modules' env-driven exit paths
