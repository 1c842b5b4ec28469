"""The `program_span` reader: its modes on hand-made totals, what it leaves
out, and that every layer-metric file names a reader and a cell that
exist."""

import glob
import importlib
import json
import os

import pytest

from chipbench import manifest
from chipbench.readers import program_span

MAN = manifest.Manifest()
MS = 1000000


def _t(count, total_ms):
    return {"count": count, "total_ns": total_ms * MS,
            "self_ns": total_ms * MS, "max_ns": total_ms * MS}


TOTALS = {"forward": _t(8, 80), "backward": _t(4, 200),
          "trainer.step": _t(4, 1600), "allreduce": _t(4, 600),
          "update": _t(4, 960),
          "serving.admit": _t(10, 700), "serving.prefill": _t(10, 500),
          "serving.step": _t(100, 2900), "serving.sync": _t(100, 2500)}
CTX = {"device": {"platform": "tpu"},
       "trace": {"steps": 4, "window_s": 2.0}}


@pytest.fixture
def totals(monkeypatch):
    monkeypatch.setattr(program_span, "_totals", lambda: dict(TOTALS))


def _spec(metric):
    return manifest.load_layer_metric(metric, MAN.root)


NEW = {"forward_host_ms.train_img": 20.0,           # 80 / 4 steps
       "backward_host_ms.train_img": 50.0,
       "allreduce_host_ms.train_img": 150.0,
       "update_host_ms.train_img": 240.0,
       # wall step 500 less (80 + 200 + 1600) / 4
       "unspanned_host_ms.train_img": 30.0,
       "admit_ms.serve": 70.0,                      # 700 / 10 calls
       "prefill_ms.serve": 50.0,
       "round_host_ms.serve": 4.0,                  # (2900 - 2500) / 100
       "sync_wait_share.serve": 125.0}              # 2500 of 2000 ms


@pytest.mark.parametrize("metric", sorted(NEW))
def test_each_new_metric_reads_its_spans(totals, metric):
    spec = _spec(metric)
    assert spec["reader"] == "program_span"
    assert program_span.read(CTX, spec["args"]) == pytest.approx(NEW[metric])


def test_the_phases_and_the_rest_add_up_to_the_wall_step(totals):
    parts = ["forward_host_ms.train_img", "backward_host_ms.train_img",
             "unspanned_host_ms.train_img"]
    got = sum(program_span.read(CTX, _spec(m)["args"]) for m in parts)
    got += program_span.read(CTX, {"spans": ["trainer.step"], "per": "step"})
    assert got == pytest.approx(1e3 * 2.0 / 4)


@pytest.mark.parametrize("args", [
    {"spans": ["kvstore.push"], "per": "step"},
    {"spans": ["kvstore.push"], "per": "call"},
    {"spans": ["kvstore.push"], "per": "window"},
    {"spans": ["kvstore.push"], "per": "step", "complement": True}])
def test_a_span_that_never_fired_is_left_out(totals, args):
    assert program_span.read(CTX, args) is None


def test_one_span_of_several_is_enough(totals):
    assert program_span.read(CTX, {"spans": ["forward", "kvstore.push"],
                                   "per": "call"}) == pytest.approx(10.0)


def test_a_cpu_run_and_an_untraced_run_report_nothing(totals):
    args = {"spans": ["forward"], "per": "step"}
    assert program_span.read(dict(CTX, device={"platform": "cpu"}),
                             args) is None
    assert program_span.read(dict(CTX, trace=None), args) is None
    assert program_span.read(dict(CTX, trace={"window_s": 2.0}),
                             args) is None          # no steps: a serve cell


def test_a_program_without_span_totals_reports_nothing(monkeypatch):
    from mxnet_tpu.observability import core
    monkeypatch.delattr(core, "span_totals")         # a parent commit
    assert program_span._totals() is None
    assert program_span.read(CTX, {"spans": ["forward"],
                                   "per": "step"}) is None


def test_an_unknown_mode_is_an_error(totals):
    with pytest.raises(ValueError):
        program_span.read(CTX, {"spans": ["forward"], "per": "token"})


def test_it_reads_the_programs_own_totals(tmp_path):
    import jax
    from mxnet_tpu.observability import core
    core.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with core.span("forward"):
            pass
    finally:
        jax.profiler.stop_trace()
    got = program_span.read(CTX, {"spans": ["forward"], "per": "call"})
    core.reset()
    assert got is not None and 0 <= got < 1e3


FILES = sorted(glob.glob(os.path.join(manifest.HERE, "layer_metrics",
                                      "*.json")))


@pytest.mark.parametrize("path", FILES, ids=[os.path.basename(p)[:-5]
                                              for p in FILES])
def test_every_layer_metric_file_names_a_reader_and_a_cell(path):
    name = os.path.basename(path)[:-5]
    with open(path) as f:
        spec = json.load(f)
    reader = importlib.import_module("chipbench.readers." + spec["reader"])
    assert callable(reader.read)
    entry = MAN.per_layer[name]
    assert entry["workloads"] and all(w in MAN.cells
                                      for w in entry["workloads"])
    if spec["reader"] == "program_span":
        assert entry["source"] == "program_span"
        assert spec["args"]["per"] in ("step", "call", "window")
