"""What start-up spent, from the record the program keeps of it whatever
its telemetry gates say (`mxnet_tpu.observability`, since PR 39):

    table "ledger"   `recompile.summary(before=core.first_session_ns())`:
                     the compile ledger up to the instant the run's one
                     profiler session went live. Set-up ends there; the
                     float32 reference compiles its own programs after it,
                     in the same process. args {"fields": [names]}: their
                     sum (`trace_s`, `lower_s`, `compile_s`, `cache_load_s`
                     in seconds; `misses`, `programs`, ... as counts)
    table "spans"    `core.span_totals()`: the `startup.*` spans, which
                     record with no session live. args {"spans": [names]}:
                     their total, in seconds
    table "cold"     `core.cold_totals()`: the spans inside which the
                     ledger moved, i.e. the calls that traced, lowered,
                     compiled or loaded a program. args {"spans": [names]}:
                     their total, in seconds

None when the program keeps no such record (a parent commit from before
it), when no span of `spans` is in the table, for the ledger when no span
of the program ran under the session (it then has no cut: the LM training
cell, whose step is a bare `jax.jit`), in an untraced run, and on the CPU
platform: a host time taken on a CPU is not a number of this benchmark, and
the ledger's counts stay out with it, because the accepted tests of
`tests/bench_harness/` pin the exact set of metrics a traced CPU cell
reports and only a `benchmark` PR may edit them.
"""


def _tables():
    try:
        from mxnet_tpu.observability import core, recompile
        summary, cut = recompile.summary, core.first_session_ns()
        return {"ledger": None if cut is None else summary(before=cut),
                "spans": core.span_totals(), "cold": core.cold_totals()}
    except (ImportError, AttributeError):
        return None


def read(ctx, args):
    if not ctx.get("trace"):
        return None
    if ctx["device"]["platform"] == "cpu":
        return None
    tables = _tables()
    if tables is None:
        return None
    table = tables[args["table"]]
    if table is None:
        return None
    if args["table"] == "ledger":
        return float(sum(table[f] for f in args["fields"]))
    names = [n for n in args["spans"] if n in table]
    if not names:
        return None
    return sum(table[n]["total_ns"] for n in names) / 1e9
