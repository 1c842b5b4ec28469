"""The `program_span_max` reader (PR 52) and the seven metrics that came
with it: the longest single call of a span on hand-made totals, what the
reader leaves out, and that each metric's file reads the span or the
counters PERF.md section 3 names for it, in the cells that report the
end-to-end metric it moves."""

import pytest

from chipbench import manifest
from chipbench.readers import (program_counter, program_counter_chip,
                               program_span, program_span_max)

MAN = manifest.Manifest()
MS = 1000000
CTX = {"device": {"platform": "tpu"},
       "trace": {"steps": 4, "window_s": 3.0}}


def _t(count, total_ms, max_ms):
    return {"count": count, "total_ns": total_ms * MS,
            "self_ns": total_ms * MS, "max_ns": max_ms * MS}


TOTALS = {"serving.sync": _t(300, 2100, 118.5),
          "serving.dispatch": _t(301, 240, 2.25),
          "serving.first_token": _t(20, 500, 41),
          "serving.step": _t(300, 2500, 120),
          "gc": _t(0, 0, 0)}
COUNTERS = {"serving.gaps": 7200.0, "serving.gap_ns": 72.0e9,
            "serving.gaps_behind_admit": 480.0,
            "serving.gap_admit_ns": 14.4e9}
FOUR = {"cerebras-gpt-1.3b-serve-closed24", "jamba2-3b-serve-chat64",
        "kimi-linear-48b-serve-reason32", "kimi-k2.6-serve-agent32"}


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(program_span, "_totals", lambda: dict(TOTALS))
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))


def _spec(metric):
    return manifest.load_layer_metric(metric, MAN.root)


def test_it_returns_the_largest_max_among_its_spans(program):
    read = program_span_max.read
    assert read(CTX, {"spans": ["serving.sync"]}) == pytest.approx(118.5)
    assert read(CTX, {"spans": ["serving.dispatch", "serving.sync",
                                "no.such.span"]}) == pytest.approx(118.5)
    assert read(CTX, {"spans": ["serving.dispatch"]}) == pytest.approx(2.25)
    # a span the program seeded and that never fired reads 0, not nothing
    assert read(CTX, {"spans": ["gc"]}) == 0.0


@pytest.mark.parametrize("ctx", [
    dict(CTX, trace=None), {"device": {"platform": "tpu"}},
    dict(CTX, device={"platform": "cpu"})],
    ids=["untraced", "no-trace-key", "cpu"])
def test_it_reads_nothing_without_a_trace_or_on_the_cpu(program, ctx):
    assert program_span_max.read(ctx, {"spans": ["serving.sync"]}) is None


@pytest.mark.parametrize("totals", [None, {}, {"forward": _t(1, 1, 1)}],
                         ids=["no-totals", "empty", "others-only"])
def test_a_program_without_the_span_reads_none(monkeypatch, totals):
    """The parent commit: no `gc` span, no `serving.first_token`."""
    monkeypatch.setattr(program_span, "_totals", lambda: totals)
    for metric in ("gc_max_ms.serve", "gc_max_ms.train_img"):
        assert program_span_max.read(CTX, _spec(metric)["args"]) is None
    spec = _spec("first_token_wait_ms.serve")
    assert program_span.read(CTX, spec["args"]) is None


@pytest.mark.parametrize("values", [None, {}, {"serving.dispatches": 5.0}],
                         ids=["no-registry", "no-counter", "others-only"])
def test_a_program_without_the_ledger_reads_none(monkeypatch, values):
    """The parent commit, or a window without a counted delivery."""
    monkeypatch.setattr(program_counter, "_values", lambda: values)
    for metric in ("itl_behind_admit_share.serve", "gap_admit_share.serve"):
        assert program_counter_chip.read(CTX, _spec(metric)["args"]) is None


def test_it_reads_the_programs_own_totals(tmp_path):
    import jax
    from mxnet_tpu.observability import core
    core.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with core.span("serving.sync"):
            pass
        with core.span("serving.sync"):
            pass
    finally:
        jax.profiler.stop_trace()
    t = core.span_totals()["serving.sync"]
    got = program_span_max.read(CTX, {"spans": ["serving.sync"]})
    gc_ms = program_span_max.read(CTX, _spec("gc_max_ms.serve")["args"])
    core.reset()
    assert got == pytest.approx(t["max_ns"] / 1e6)
    assert t["max_ns"] < t["total_ns"]
    assert gc_ms is not None and gc_ms >= 0.0       # seeded by the span


WANT = {
    # metric: (reader, value on the totals above, source, layer, moves)
    "sync_max_ms.serve": ("program_span_max", 118.5, "program_span",
                          "serving scheduler + cache", "serve_tok_s"),
    "dispatch_max_ms.serve": ("program_span_max", 2.25, "program_span",
                              "serving scheduler + cache",
                              "serve_itl_p95_ms"),
    "gc_max_ms.serve": ("program_span_max", 0.0, "program_span",
                        "serving scheduler + cache", "serve_tok_s"),
    "gc_max_ms.train_img": ("program_span_max", 0.0, "program_span",
                            "frontend dispatch", "train_img_s"),
    "first_token_wait_ms.serve": ("program_span", 25.0, "program_span",
                                  "serving scheduler + cache",
                                  "serve_itl_p95_ms"),      # 500 / 20
    "itl_behind_admit_share.serve": ("program_counter_chip", 100 * 480
                                     / 7200.0, "program_counter",
                                     "serving scheduler + cache",
                                     "serve_itl_p95_ms"),
    "gap_admit_share.serve": ("program_counter_chip", 20.0,
                              "program_counter",
                              "serving scheduler + cache", "serve_tok_s")}


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_metric_reads_what_perf_md_names_for_it(program, metric):
    reader, value, source, layer, moves = WANT[metric]
    spec = _spec(metric)
    assert spec["reader"] == reader
    module = {"program_span_max": program_span_max,
              "program_span": program_span,
              "program_counter_chip": program_counter_chip}[reader]
    assert module.read(CTX, spec["args"]) == pytest.approx(value)
    assert module.read(dict(CTX, device={"platform": "cpu"}),
                       spec["args"]) is None
    entry = MAN.per_layer[metric]
    assert (entry["source"], entry["layer"], entry["moves"],
            entry["better"]) == (source, layer, moves, "lower")
    assert entry["unit"] == ("%" if metric.endswith("share.serve")
                             else "ms")


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_metric_lists_cells_that_report_what_it_moves(metric):
    entry = MAN.per_layer[metric]
    cells = set(entry["workloads"])
    assert cells <= set(MAN.end_to_end[entry["moves"]]["workloads"])
    if entry["moves"] == "serve_itl_p95_ms":
        assert cells == FOUR
    elif entry["moves"] == "serve_tok_s":
        # of the six that report it, the three whose accepted tests do not
        # pin the cell's exact set of metrics (PERF.md section 7)
        assert cells == FOUR - {"kimi-k2.6-serve-agent32"}
    else:
        assert cells == {"resnet50-gluon-train-bs128"}
