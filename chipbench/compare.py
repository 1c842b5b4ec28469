"""The comparisons that decide `correct`. Each returns a list of checks
{"name", "value", "limit", "ok"}; a run is correct when every check of its
cell is ok. Limits come from `chipbench/limits/<workload>.json` and were set
from readings on the chip (PERF.md section 2)."""

import math
import statistics


def check(name, value, limit, detail=None):
    ok = bool(value is not None and math.isfinite(value) and value <= limit)
    out = {"name": name, "value": value, "limit": limit, "ok": ok}
    if detail:
        out["detail"] = detail
    return out


def leaf_gaps(got, ref):
    """{leaf: gap between the program's norm and the reference's, against
    the reference's norm of that leaf or of the median leaf, whichever is
    larger}; None when the leaves differ."""
    if set(got) != set(ref):
        return None
    med = statistics.median(ref.values())
    return {k: abs(got[k] - r) / max(r, med, 1e-30) for k, r in ref.items()}


def worst_leaf_gap(got, ref):
    """Largest leaf gap. Returns (gap, leaf)."""
    return leaf_stat(got, ref, "worst")


def leaf_stat(got, ref, stat):
    """One number of the leaf gaps: "worst", "median" or "p90" (the gap
    that nine leaves in ten stay under). Returns (value, leaf or note)."""
    gaps = leaf_gaps(got, ref)
    if gaps is None:
        return float("inf"), "leaves differ: %s" % sorted(
            set(got) ^ set(ref))[:4]
    for k, g in gaps.items():
        if not math.isfinite(g):
            return float("inf"), k
    ranked = sorted(gaps.items(), key=lambda kv: kv[1])
    at = {"worst": len(ranked) - 1, "median": (len(ranked) - 1) // 2,
          "p90": math.ceil(0.9 * len(ranked)) - 1}[stat]
    leaf, gap = ranked[at]
    return float(gap), leaf


def _limit(limits, name):
    """A limit is a number (over the worst leaf) or {"stat", "limit"}."""
    spec = limits[name]
    if isinstance(spec, dict):
        return spec.get("stat", "worst"), spec["limit"]
    return "worst", spec


def training_checks(got, ref, limits):
    """got/ref: {"losses", "grad_norms", "delta_norms"}."""
    loss_gap = max((abs(a - b) / max(abs(b), 1e-30)
                    for a, b in zip(got["losses"], ref["losses"])),
                   default=float("inf"))
    if len(got["losses"]) != len(ref["losses"]):
        loss_gap = float("inf")
    out = [check("loss_gap", loss_gap, _limit(limits, "loss_gap")[1],
                 "program %s reference %s" % (got["losses"], ref["losses"]))]
    if "sample_loss_gap" in limits:
        # the first step's loss row by row: the mean hides zero-mean
        # rounding noise, the rows do not
        a, b = got.get("sample_losses"), ref.get("sample_losses")
        gap = float("inf")
        if a and b and len(a) == len(b):
            gap = math.sqrt(sum((x - y) ** 2 for x, y in zip(a, b))
                            / len(b)) / abs(sum(b) / len(b))
        out.append(check("sample_loss_gap", gap,
                         _limit(limits, "sample_loss_gap")[1],
                         "rms over %d rows / mean" % len(b or ())))
    for name, key in (("grad_norm_gap", "grad_norms"),
                      ("delta_norm_gap", "delta_norms")):
        stat, limit = _limit(limits, name)
        value, leaf = leaf_stat(got[key], ref[key], stat)
        out.append(check(name, value, limit, "%s leaf: %s" % (stat, leaf)))
    return out


def serving_checks(gaps, malformed, finished, limits):
    """gaps: per sampled stream, the served tokens' logit gaps: by how much
    each served token's reference logit lies below the reference's best.
    malformed: finished streams that lost their prompt or their length."""
    flat = [g for s in gaps for g in s]
    widest = max(flat) if flat else float("inf")
    return [check("served_logit_gap", widest, limits["served_logit_gap"],
                  "%d tokens of %d sampled streams" % (len(flat), len(gaps))),
            check("malformed_streams", float(malformed), 0.0,
                  "of %d finished" % finished)]


def print_checks(checks, out):
    for c in checks:
        out.write("check %-34s value=%-22r limit=%-10r %s%s\n" % (
            c["name"], c["value"], c["limit"], "ok" if c["ok"] else "FAILED",
            ("  (%s)" % c["detail"]) if c.get("detail") else ""))
