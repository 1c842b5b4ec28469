"""Perf-regression sentinel over the per-operator attribution summary.

Diffs a run's aggregate totals + per-scope flops/HBM-bytes table
against a committed baseline JSON with per-metric tolerances and exits
nonzero on regression — the TIER1_OBS lane runs it on the obs_ops
smoke workload against ``ci/obs_baseline.json``, so a PR that silently
doubles the bytes a block moves fails CI with the offending scope and
ratio in the output instead of surfacing weeks later as a slower
BENCH row.

    # CI form: run the deterministic smoke workload, diff vs baseline
    python tools/obs_regression.py --baseline ci/obs_baseline.json

    # diff two saved summaries (any obs_ops --json artifacts)
    python tools/obs_regression.py --baseline base.json --current run.json

    # intentional change? refresh the committed numbers
    python tools/obs_regression.py --baseline ci/obs_baseline.json --update

    # the PR 16 megakernel sentinel: run the paged decode + spec-verify
    # serving workload with MXNET_PAGED_DECODE_PALLAS=1 and diff the
    # paged_decode_kernel / paged_verify_kernel scope rows against the
    # baseline file's "kernels" section
    python tools/obs_regression.py --baseline ci/obs_baseline.json --kernels

    # rolling-window timing drift against the performance archive
    # (observability/profile_store.py): the newest archived run's
    # per-scope p50 vs the median of the prior MXNET_OBS_PROFILE_HISTORY
    # runs, flagged past --tol p50_ms (default 50%) naming the scope
    python tools/obs_regression.py --history --profile-dir /data/perf

Tolerances: ``--tol metric=frac`` (repeatable) overrides, then the
baseline file's ``tolerances`` map, then attribution.DEFAULT_TOLERANCES
(flops/hbm_bytes 15%, out_bytes/peak_bytes 25%, count 50%). A metric
regresses when ``current > baseline * (1 + tol)``; scopes appearing or
disappearing are reported as notes, not failures (renames happen — the
aggregate totals still catch growth hiding behind one), and
improvements past the same tolerance are listed so an intentional
optimization reminds you to --update. ``--kernels`` additionally runs
both sides through the profile store's signature normalization first,
so a harmless shape-signature rename (a re-jit with a widened batch
axis turning ``paged_decode_kernel`` into ``paged_decode_kernel_1``)
is merged back and reported as a note, not a failure.
"""

import argparse
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_OBS", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _load_summary(path):
    with open(path) as f:
        doc = json.load(f)
    return doc.get("summary", doc), doc


HISTORY_TOL = 0.5    # timing is noisier than byte accounting


def _normalize_scopes(summ):
    """Run a summary's scope keys through the profile store's
    signature normalization (trailing ``_<n>`` rename counters from a
    re-jit stripped), merging rows that collapse onto one key. Returns
    (normalized summary, notes) — a rename is a note, not a failure."""
    from mxnet_tpu.observability import profile_store
    scopes = summ.get("scopes", {}) or {}
    out, notes = {}, []
    for name in sorted(scopes):
        row = scopes[name]
        norm = profile_store.normalize_scope(name)
        if norm != name:
            notes.append("scope %r normalized to %r "
                         "(shape-signature rename)" % (name, norm))
        if norm in out:
            for k, v in row.items():
                if isinstance(v, (int, float)):
                    out[norm][k] = out[norm].get(k, 0) + v
        else:
            out[norm] = dict(row)
    new = dict(summ)
    new["scopes"] = out
    return new, notes


def run_history(args, cli_tol):
    """--history: the newest archived run's per-signature p50 (or
    --history-metric) against the median of the prior rolling window.
    Exit 0 in tolerance / nothing to compare yet, 1 on drift (scope
    named), 2 on no archive."""
    from mxnet_tpu.observability import profile_store
    d = args.profile_dir or profile_store.store_dir()
    if not d or not os.path.isdir(d):
        print("[obs_regression] FAIL: --history needs an archive "
              "(--profile-dir or MXNET_OBS_PROFILE_DIR)")
        return 2
    records, evidence = profile_store.load(d)
    for ev in evidence:
        print("[obs_regression] note: skipped %s frame at %s+%d"
              % (ev["evidence"], os.path.basename(ev["file"]),
                 ev["offset"]))
    runs = profile_store.runs_in(records)
    if len(runs) < 2:
        print("[obs_regression] history: %d archived run(s) in %s — "
              "need >= 2 to compare" % (len(runs), d))
        return 0
    window = args.window or profile_store.history()
    latest = runs[-1]
    window_runs = runs[:-1][-window:]
    metric = args.history_metric
    tol = cli_tol.get(metric, HISTORY_TOL)
    regressions = []
    for sig, g in sorted(profile_store.merge_by_signature(
            records).items()):
        series = {run: val for run, _ts, val
                  in profile_store.run_series(g, metric=metric)}
        cur = series.get(latest)
        base = sorted(series[r] for r in window_runs if r in series)
        if cur is None or not base:
            continue
        ref = base[len(base) // 2]
        if ref <= 0:
            continue
        if cur > ref * (1.0 + tol) + 1e-9:
            regressions.append((g["scope"], sig, ref, cur))
    if regressions:
        print("[obs_regression] FAIL: %d scope(s) drifted past %.0f%% "
              "of the %d-run rolling median (%s):"
              % (len(regressions), 100 * tol, len(window_runs),
                 metric))
        for scope, sig, ref, cur in regressions:
            print("  %-28s %12.4g -> %12.4g  (%.2fx)  [%s]"
                  % (scope, ref, cur, cur / ref, sig))
        return 1
    print("[obs_regression] OK: run %s within %.0f%% of the %d-run "
          "window across %d archived signature(s)"
          % (latest, 100 * tol, len(window_runs),
             len(profile_store.merge_by_signature(records))))
    return 0


def _fmt(rows):
    out = []
    for r in rows:
        out.append("  %-28s %-10s %12.4g -> %12.4g  (%.2fx, tol %.0f%%)"
                   % (r["where"], r["metric"], r["baseline"],
                      r["current"], r["ratio"],
                      100.0 * r.get("tolerance", 0.0)))
    return out


def main(argv=None):
    print("[obs_regression] CPU structure check by design: JAX_PLATFORMS=%s "
          "(pinned by this script when unset); counts, bytes and "
          "orderings only — no time or rate below is a device number"
          % os.environ["JAX_PLATFORMS"], flush=True)
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--baseline", default=None,
                   help="committed baseline JSON (ci/obs_baseline.json)")
    p.add_argument("--current", default=None,
                   help="summary JSON to check; default: run the "
                        "tools/obs_ops.py smoke workload")
    p.add_argument("--tol", action="append", default=[],
                   metavar="METRIC=FRAC",
                   help="tolerance override, e.g. --tol hbm_bytes=0.1")
    p.add_argument("--update", action="store_true",
                   help="write the current summary over --baseline "
                        "(keeps the file's tolerances block)")
    p.add_argument("--kernels", action="store_true",
                   help="guard the paged megakernel scopes instead: "
                        "run the obs_ops kernel workload (Pallas "
                        "forced on) and diff the baseline's 'kernels' "
                        "section")
    p.add_argument("--history", action="store_true",
                   help="check the newest archived run against the "
                        "rolling window of prior runs in the "
                        "performance archive instead of a committed "
                        "baseline")
    p.add_argument("--profile-dir", default=None,
                   help="--history archive directory (default "
                        "MXNET_OBS_PROFILE_DIR)")
    p.add_argument("--history-metric", default="p50_ms",
                   help="--history span stat to guard (default "
                        "p50_ms)")
    p.add_argument("--window", type=int, default=None,
                   help="--history rolling-window size (default "
                        "MXNET_OBS_PROFILE_HISTORY=8)")
    args = p.parse_args(argv)

    cli_tol = {}
    for spec in args.tol:
        metric, _, frac = spec.partition("=")
        if not frac:
            p.error("--tol wants METRIC=FRAC, got %r" % spec)
        cli_tol[metric] = float(frac)

    if args.history:
        return run_history(args, cli_tol)
    if not args.baseline:
        p.error("--baseline is required (except with --history)")

    if args.current:
        current, _ = _load_summary(args.current)
    else:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "obs_ops", os.path.join(ROOT, "tools", "obs_ops.py"))
        obs_ops = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(obs_ops)
        if args.kernels:
            os.environ.setdefault("MXNET_OBS_OPS", "1")
            current = obs_ops.run_kernel_workload()
        else:
            current = obs_ops.run_workload()
        if not current["totals"].get("programs"):
            print("[obs_regression] FAIL: workload registered no "
                  "compiled program (MXNET_OBS off at trace time?)")
            return 2
        if args.kernels:
            from mxnet_tpu.observability import profile_store
            have = {profile_store.normalize_scope(k)
                    for k in current.get("scopes", {})}
            missing = [k for k in ("paged_decode_kernel",
                                   "paged_verify_kernel")
                       if k not in have]
            if missing:
                print("[obs_regression] FAIL: kernel workload is "
                      "missing megakernel scope(s) %s — did the Pallas "
                      "path (MXNET_PAGED_DECODE_PALLAS=1) not engage?"
                      % ", ".join(missing))
                return 2

    baseline_doc = {}
    if os.path.exists(args.baseline):
        baseline, baseline_doc = _load_summary(args.baseline)
    elif args.update:
        baseline = None
    else:
        print("[obs_regression] FAIL: baseline %s not found (generate "
              "with --update)" % args.baseline)
        return 2

    if args.kernels:
        kern_doc = baseline_doc.get("kernels", {})
        baseline = kern_doc.get("summary")
        if baseline is None and not args.update:
            print("[obs_regression] FAIL: baseline %s has no 'kernels' "
                  "section (generate with --kernels --update)"
                  % args.baseline)
            return 2

    if args.update:
        if args.kernels:
            doc = dict(baseline_doc)
            doc["kernels"] = {
                "workload": "tools/obs_ops.py run_kernel_workload "
                            "(paged decode + spec-verify serving, "
                            "MXNET_PAGED_DECODE_PALLAS=1)",
                "summary": current}
        else:
            doc = {"workload": "tools/obs_ops.py smoke (two-block "
                               "conv+dense Gluon model, 2 train steps)",
                   "tolerances": baseline_doc.get("tolerances", {}),
                   "summary": current}
            if "kernels" in baseline_doc:
                doc["kernels"] = baseline_doc["kernels"]
        with open(args.baseline, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print("[obs_regression] baseline updated -> %s%s"
              % (args.baseline,
                 " (kernels section)" if args.kernels else ""))
        return 0

    if args.kernels:
        # the store's signature normalization: a re-jit's harmless
        # scope rename must merge back onto the baseline row
        baseline, base_notes = _normalize_scopes(baseline)
        current, cur_notes = _normalize_scopes(current)
        for note in base_notes + cur_notes:
            print("[obs_regression] note: %s" % note)

    from mxnet_tpu.observability import attribution
    tol = dict(baseline_doc.get("tolerances", {}))
    tol.update(cli_tol)
    report = attribution.compare_summaries(baseline, current,
                                           tolerances=tol)
    for note in report["notes"]:
        print("[obs_regression] note: %s" % note)
    if report["improvements"]:
        print("[obs_regression] improvements past tolerance (baseline "
              "stale? --update):")
        print("\n".join(_fmt(report["improvements"])))
    if report["regressions"]:
        print("[obs_regression] FAIL: %d metric(s) regressed past "
              "tolerance:" % len(report["regressions"]))
        print("\n".join(_fmt(report["regressions"])))
        return 1
    print("[obs_regression] OK: totals + %d scope(s) within tolerance "
          "of %s" % (len(baseline.get("scopes", {})), args.baseline))
    return 0


if __name__ == "__main__":
    sys.exit(main())
