"""The `program_startup` reader on hand-made tables: each of its metric
files, what it leaves out, and that it reads the program's own record."""

import pytest

from chipbench import manifest
from chipbench.readers import (program_counter, program_counter_chip,
                               program_startup)

MAN = manifest.Manifest()
S = 1000000000


def _t(count, seconds):
    return {"count": count, "total_ns": int(seconds * S)}


TABLES = {
    "ledger": {"trace_s": 6.5, "lower_s": 3.25, "compile_s": 0.125,
               "cache_load_s": 17.75, "hits": 34, "misses": 0,
               "uncached": 0, "programs": 27, "entries": 400, "dropped": 0,
               "by_program": []},
    "spans": {"startup.import": _t(1, 4.5), "startup.backend": _t(1, 12.25),
              "startup.batcher": _t(1, 1.75), "serving.step": _t(90, 2.0)},
    "cold": {"serving.admit": _t(9, 8.0), "serving.step": _t(3, 2.5),
             "forward": _t(2, 20.0), "backward": _t(1, 11.0),
             "trainer.step": _t(1, 3.0), "serving.prefill": _t(9, 7.0)}}
CTX = {"device": {"platform": "tpu"}, "trace": {"window_s": 3.0}}

WANT = {"backend_init_s.startup": 16.75,      # import + first device query
        "trace_lower_s.startup": 9.75,
        "compile_s.startup": 0.125,
        "cache_load_s.startup": 17.75,
        "cache_misses.startup": 0.0,
        "programs.startup": 27.0,
        "cold_call_s.serve": 10.5,            # admit + step, not prefill
        "cold_call_s.train_img": 34.0,
        "batcher_build_s.serve": 1.75}


@pytest.fixture
def tables(monkeypatch):
    monkeypatch.setattr(program_startup, "_tables", lambda: TABLES)


def _spec(metric):
    return manifest.load_layer_metric(metric, MAN.root)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_each_metric_reads_its_table(tables, metric):
    spec = _spec(metric)
    assert spec["reader"] == "program_startup"
    got = program_startup.read(CTX, spec["args"])
    assert isinstance(got, float) and got == pytest.approx(WANT[metric])
    entry = MAN.per_layer[metric]
    assert (entry["layer"], entry["moves"]) == ("start-up", "setup_s")
    assert entry["unit"] == ("s" if metric.split(".")[0].endswith("_s")
                             else "count")
    # the ledger's numbers are counters, the span tables' are spans
    assert entry["source"] == ("program_counter"
                               if spec["args"]["table"] == "ledger"
                               else "program_span")


@pytest.mark.parametrize("metric", sorted(WANT))
def test_a_cpu_run_and_an_untraced_run_report_nothing(tables, metric):
    # seconds of a CPU's start-up are no numbers; the counts stay out with
    # them: the accepted tests pin what a traced CPU cell reports
    args = _spec(metric)["args"]
    assert program_startup.read(dict(CTX, device={"platform": "cpu"}),
                                args) is None
    assert program_startup.read(dict(CTX, trace=None), args) is None


def test_a_span_that_is_in_no_table_is_left_out(tables):
    for table in ("spans", "cold"):
        assert program_startup.read(
            CTX, {"table": table, "spans": ["kvstore.push"]}) is None
    assert program_startup.read(
        CTX, {"table": "cold", "spans": ["kvstore.push", "backward"]}) \
        == pytest.approx(11.0)


@pytest.mark.parametrize("name", ["cold_totals", "first_session_ns"])
def test_a_program_without_the_record_reports_nothing(monkeypatch, name):
    from mxnet_tpu.observability import core
    monkeypatch.delattr(core, name)                  # a parent commit
    assert program_startup._tables() is None
    for metric in WANT:
        assert program_startup.read(CTX, _spec(metric)["args"]) is None


def test_a_ledger_without_a_cut_reports_nothing(monkeypatch):
    """No span of the program ran under the session (the LM training cell:
    its step is a bare jax.jit), so nothing says where set-up ended."""
    from mxnet_tpu.observability import core
    monkeypatch.setattr(core, "_first_session_ns", None)
    tables = program_startup._tables()
    assert tables["ledger"] is None and tables["spans"] is not None
    monkeypatch.setattr(program_startup, "_tables", lambda: tables)
    assert program_startup.read(
        CTX, _spec("programs.startup")["args"]) is None
    assert "cerebras-gpt-1.3b-train-8k" not in \
        MAN.per_layer["programs.startup"]["workloads"]
    assert "cerebras-gpt-1.3b-train-8k" in \
        MAN.per_layer["backend_init_s.startup"]["workloads"]


def test_a_program_without_a_summary_reports_nothing(monkeypatch):
    from mxnet_tpu.observability import recompile
    monkeypatch.delattr(recompile, "summary")
    assert program_startup._tables() is None


def test_it_reads_the_programs_own_record(tmp_path):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.observability import core, recompile
    core.reset()
    recompile.get_detector().reset()

    def before_the_window(x):
        return jnp.tanh(x) * 39.0

    def after_the_window(x):
        return jnp.cos(x) * 39.5
    with core.span("serving.admit", cat="serving"):
        jax.jit(before_the_window)(jnp.ones((3,)))
    core.record_startup("startup.batcher", 0)
    old = core._first_session_ns
    core._first_session_ns = None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with core.span("serving.step", cat="serving"):
            pass
    finally:
        jax.profiler.stop_trace()
    jax.jit(after_the_window)(jnp.ones((3,)))        # the reference's
    try:
        tables = program_startup._tables()
        got = {m: program_startup.read(CTX, _spec(m)["args"])
               for m in WANT}
    finally:
        core._first_session_ns = old
        core.reset()
        recompile.get_detector().reset()
    names = {p["program"] for p in tables["ledger"]["by_program"]}
    assert "jit(before_the_window)" in names
    assert "jit(after_the_window)" not in names
    assert got["programs.startup"] >= 1 and got["trace_lower_s.startup"] > 0
    assert got["cold_call_s.serve"] > 0 and got["batcher_build_s.serve"] > 0
    assert got["cold_call_s.train_img"] is None


def test_dispatch_ahead_share_reads_the_pipelines_two_counters(monkeypatch):
    spec = _spec("dispatch_ahead_share.serve")
    assert spec["reader"] == "program_counter_chip"
    monkeypatch.setattr(program_counter, "_values", lambda: {
        "serving.dispatches": 170.0, "serving.dispatch_ahead": 169.0})
    read = program_counter_chip.read
    assert read(CTX, spec["args"]) == pytest.approx(100.0 * 169 / 170)
    assert read(dict(CTX, trace=None), spec["args"]) is None
    assert read(dict(CTX, device={"platform": "cpu"}), spec["args"]) is None
    entry = MAN.per_layer["dispatch_ahead_share.serve"]
    assert entry["moves"] == "serve_tok_s" and entry["better"] == "higher"
    assert entry["source"] == "program_counter"
