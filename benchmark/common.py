"""Shared helpers for the benchmark scripts."""


def fetch_barrier(out):
    """A device barrier: fetch a scalar computed from ``out``.

    A host ``float()`` of a value data-dependent on the result cannot
    return before the device has produced it, whatever the runtime,
    and fetching a single element keeps the barrier itself cheap.
    Works for any pytree of arrays: syncing one leaf is enough because
    a single device executes its queue in order. Whether
    ``block_until_ready`` agrees with it on the attached chip is
    recorded in PERF.md (PR 22 findings, "Barriers").
    """
    import jax
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(leaf[(0,) * leaf.ndim])


def print_obs_table():
    """Print the observability aggregate-stats table when telemetry is
    on (MXNET_OBS=1 / --obs flags): bench numbers then come with the
    phase breakdown behind them (docs/OBSERVABILITY.md), so PERF.md
    rows can cite where the wall time went."""
    from mxnet_tpu.observability import core, export
    if not core.enabled():
        return
    print()
    print(export.aggregate_table())


def print_ops_table(compiled=None):
    """--obs-ops: print the per-scope top-K attribution table
    (docs/OBSERVABILITY.md "Per-operator attribution").

    With ``compiled`` (a jax compiled executable, e.g. the leg a bench
    just lowered) the table comes from that program's optimized HLO
    directly; without it, from whatever jit boundaries the attribution
    layer registered during the run (CachedOp/Executor/KVStore).
    Heuristic op_name attribution applies when no Gluon scopes were
    stamped — hand-built jax legs still get a source-structure split.
    """
    from mxnet_tpu.observability import attribution, core, hlo
    if not core.enabled() or not attribution.ops_enabled():
        return
    if compiled is None:
        lines = attribution.format_ops_table()
    else:
        rows = hlo.attribute_rows(hlo.parse_hlo(compiled.as_text()),
                                  attribution.known_scopes() or None)
        scopes, totals = hlo.group_by_scope(rows)
        peak, _peak_scopes = hlo.peak_watermark(rows)
        totals["peak_bytes"] = peak
        totals["programs"] = 1
        lines = attribution.format_ops_table(
            {"totals": totals, "scopes": scopes})
    if lines:
        print("\n".join(lines))
    else:
        print("[obs-ops] no compiled program registered (nothing "
              "crossed an instrumented jit boundary)")


def record_bench_profile(leg, value=None, unit=None, metric=None,
                         **extra):
    """Append one measured bench result to the performance archive
    (observability/profile_store.py) with the run's config fingerprint,
    so bench rows carry provenance and
    ``tools/perf_timeline.py`` can trend them across runs. One guarded
    branch: with MXNET_OBS_PROFILE_DIR unset this is a single env read
    and no I/O; never raises — archiving must not fail a bench."""
    import os
    if not os.environ.get("MXNET_OBS_PROFILE_DIR"):
        return None
    try:
        from mxnet_tpu.observability import profile_store
        return profile_store.append_bench(leg, value=value, unit=unit,
                                          metric=metric,
                                          extra=extra or None)
    except Exception:
        return None


def obs_ops_requested(argv=None):
    """Shared --obs-ops detection for the stdin-run benches (their
    argv is free-form words, not argparse): present -> turn telemetry
    on NOW so the programs traced later carry named scopes."""
    import os
    import sys
    argv = sys.argv[1:] if argv is None else argv
    if not any(a in ("--obs-ops", "obs-ops") for a in argv):
        return False
    os.environ.setdefault("MXNET_OBS", "1")
    os.environ.setdefault("MXNET_OBS_OPS", "1")
    return True
