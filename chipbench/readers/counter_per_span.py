"""One of the program's counters over the host seconds of its spans, both
over the traced sub-window: `program_counter.py`'s values over
`program_span.py`'s totals, so a rate of the program's own work inside its
own spans (tokens an admission prefilled a second of `serving.prefill`).

args: {"counter": name, "spans": [names]}

    value of `counter` / total seconds of `spans`

None when the program has no such counter or no such span (a parent commit
from before them, or a window in which none fired), and on the CPU
platform: a host time taken on a CPU is not a number of this benchmark.
"""

from chipbench.readers import program_counter, program_span


def read(ctx, args):
    if not ctx.get("trace") or ctx["device"]["platform"] == "cpu":
        return None
    values, totals = program_counter._values(), program_span._totals()
    if not values or args["counter"] not in values or not totals:
        return None
    ns = sum(totals[n]["total_ns"] for n in args["spans"] if n in totals)
    if not ns:
        return None
    return values[args["counter"]] / (ns / 1e9)
