"""`program_counter.py`'s ratio, on a chip only: None on the CPU platform.

A count is a count on any platform, and `program_counter` reports it there.
This reader is for a counter metric added to cells whose traced CPU run the
accepted tests of `tests/bench_harness/` pin to an exact set of metrics
(`dispatch_ahead_share.serve`, PR 39): only a `benchmark` PR may edit those
tests, and it can then point the metric's file at `program_counter`.
"""

from chipbench.readers import program_counter


def read(ctx, args):
    if not ctx.get("trace") or ctx["device"]["platform"] == "cpu":
        return None
    return program_counter.read(ctx, args)
