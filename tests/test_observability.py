"""Unified runtime telemetry (mxnet_tpu/observability/): ring recorder,
profiler state machine + exporters, recompile detector, and the
instrumented Trainer step end to end (ISSUE 2)."""

import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.observability import attribution, core, export, recompile


@pytest.fixture
def obs_on(monkeypatch):
    """Clean, enabled telemetry state for one test; restores env +
    recorder + detector afterwards."""
    monkeypatch.setenv("MXNET_OBS", "1")
    core.set_enabled(None)
    core.reset()
    recompile.get_detector().reset()
    yield core
    core.set_enabled(None)
    core.reset()
    recompile.get_detector().reset()
    attribution.reset()


# ------------------------------------------------------------- core --

def test_disabled_records_nothing(monkeypatch):
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    core.reset()
    assert not core.enabled()
    with core.span("nope", cat="x"):
        pass
    assert core.records() == []


def test_span_and_counter_recording(obs_on):
    with core.span("phase_a", cat="step", tag=7):
        pass
    core.counter("hits").add(2)
    core.counter("hits").add(3)
    recs = core.records()
    kinds = [r[0] for r in recs]
    assert kinds == ["X", "C", "C"]
    ph, name, cat, ts, dur, tid, args = recs[0]
    assert (name, cat, args) == ("phase_a", "step", {"tag": 7})
    c = core.counters()["hits"]
    assert (c.count, c.total, c.min, c.max, c.value) == (2, 5.0, 2.0,
                                                         3.0, 5.0)
    core.reset()
    assert core.records() == [] and core.counters() == {}


def test_ring_overwrites_oldest(obs_on, monkeypatch):
    monkeypatch.setenv("MXNET_OBS_RING", "4")
    core.reset()          # rebuild at the new capacity
    for i in range(10):
        core.record_instant("ev%d" % i)
    recs = core.records()
    assert len(recs) == 4
    assert [r[1] for r in recs] == ["ev6", "ev7", "ev8", "ev9"]
    assert core.dropped() == 6


def test_gauge_last_value_wins(obs_on):
    g = core.gauge("temp")
    g.set(5.0)
    g.set(2.0)
    assert g.value == 2.0 and g.min == 2.0 and g.max == 5.0


# -------------------------------------------------------- exporters --

def test_aggregate_percentiles_synthetic(obs_on):
    # 100 spans of 1..100 ms: p50 and p99 land on known samples
    for ms in range(1, 101):
        core.record_span("work", "step", 0, ms * 1_000_000)
    agg = export.aggregate()["spans"]["work"]
    assert agg["count"] == 100
    assert agg["min_ms"] == pytest.approx(1.0)
    assert agg["max_ms"] == pytest.approx(100.0)
    assert agg["p50_ms"] == pytest.approx(51.0)
    assert agg["p99_ms"] == pytest.approx(100.0)
    assert agg["total_ms"] == pytest.approx(5050.0)
    table = export.aggregate_table()
    assert "work" in table and "P99" in table


def test_chrome_trace_shape(obs_on):
    with core.span("alpha", cat="step"):
        pass
    core.counter("beta").add(1)
    trace = export.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    phs = {e["name"]: e["ph"] for e in trace["traceEvents"]}
    assert phs["alpha"] == "X" and phs["beta"] == "C"


def test_prometheus_textfile(obs_on, tmp_path):
    with core.span("p", cat="step"):
        pass
    core.counter("q").add(4)
    text = export.prometheus_text()
    assert 'mxnet_obs_span_ms_count{phase="p"} 1' in text
    assert 'mxnet_obs_counter_total{name="q"} 4' in text
    target = tmp_path / "obs.prom"
    assert export.write_prometheus(str(target)) == str(target)
    assert target.read_text() == text
    # no target configured -> no-op
    assert export.write_prometheus(None) is None


# --------------------------------------------------- profiler layer --

def test_profiler_state_machine_roundtrip(obs_on, tmp_path):
    fname = str(tmp_path / "profile.json")
    mx.profiler.set_config(filename=fname, xla_trace=False)
    try:
        mx.profiler.set_state("run")
        d = mx.profiler.Domain("test")
        with d.new_task("stage1"):
            pass
        mx.profiler.pause()
        with d.new_task("ignored_while_paused"):
            pass
        mx.profiler.resume()
        with d.new_task("stage2"):
            pass
        mx.profiler.set_state("stop")
        path = mx.profiler.dump()
        trace = json.load(open(path))
        names = {e["name"] for e in trace["traceEvents"]}
        assert "stage1" in names and "stage2" in names
        assert "ignored_while_paused" not in names
        # legacy flat listing still carries every explicit span
        flat = mx.profiler.dumps()
        assert "stage1" in flat
        table = mx.profiler.dumps(aggregate=True)
        assert "stage1" in table and "P50" in table
    finally:
        mx.profiler.set_config(filename="profile.json", xla_trace=True)


# ------------------------------------------------ recompile detector --

def test_recompile_detector_flags_polymorphic_jit(obs_on):
    import jax
    import jax.numpy as jnp
    det = recompile.get_detector()
    det.reset(budget=2)
    det.mark_steady()
    recompile.note_call("poly_fn", "warmup")
    with pytest.warns(RuntimeWarning, match="retraces after steady"):
        f = jax.jit(lambda x: x * 2.0 + 1.0)
        for n in (1, 2, 3):        # new shape every call -> retrace
            f(jnp.ones((n,), jnp.float32))
    assert det.flagged
    assert det.steady_misses >= 2
    traces = [e for e in det.events if e["kind"] == "trace"]
    assert traces and traces[-1]["origin"] == "poly_fn"


def test_step_boundary_arms_on_compile_free_step(obs_on):
    """Auto-arming waits for an OBSERVED compile-free step past the
    warmup, so programs that legitimately compile new jits for a few
    steps (metrics, logging) do not count them as retraces."""
    det = recompile.get_detector()
    det.reset()
    det.step_boundary()                        # warmup step
    assert not det.steady
    det._push("trace", "legit", None, 0.0)     # step 2 compiled
    det.step_boundary()
    assert not det.steady
    det.step_boundary()                        # step 3 compile-free
    assert det.steady
    assert det.steady_misses == 0 and not det.flagged


def test_recompile_variant_recorded(obs_on):
    det = recompile.get_detector()
    det.reset()
    recompile.record_retrace("CachedOp[x]", "train=True diff=2")
    entry = dict(det.events[-1])
    assert entry.pop("t_ns") >= 0          # its time on core's epoch
    assert entry == {
        "kind": "variant", "origin": "CachedOp[x]",
        "signature": "train=True diff=2", "duration_s": 0.0,
        "steady": False}
    assert not det.flagged


def test_cached_op_retrace_attribution(obs_on):
    """A hybridized block re-called under a new shape retraces; the
    detector records the trace with the CachedOp signature breadcrumb."""
    det = recompile.get_detector()
    det.reset(budget=100)          # observe, don't warn
    net = nn.Dense(3, in_units=4)
    net.initialize()
    net.hybridize()
    net(mx.nd.ones((2, 4)))
    before = det.misses
    net(mx.nd.ones((5, 4)))        # new batch size -> silent retrace
    assert det.misses > before
    origins = {e["origin"] for e in det.events
               if e["kind"] == "trace" and e["origin"]}
    assert any(o.startswith("CachedOp[") for o in origins)


# ------------------------------------------------- end-to-end step --

def test_trainer_step_trace_and_aggregate(obs_on, tmp_path):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=mx.kvstore.create("device"))
    loss_fn = gluon.loss.L2Loss()
    x = mx.nd.random.uniform(shape=(8, 10))
    y = mx.nd.random.uniform(shape=(8, 4))
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(8)

    fname = str(tmp_path / "step_trace.json")
    mx.profiler.set_config(filename=fname, xla_trace=False)
    try:
        path = mx.profiler.dump()
    finally:
        mx.profiler.set_config(filename="profile.json", xla_trace=True)
    trace = json.load(open(path))
    names = {e["name"] for e in trace["traceEvents"]}
    # the four step phases, per acceptance criteria
    assert {"forward", "backward", "allreduce", "update"} <= names
    # per-bucket collective counters
    assert "kvstore.bucket" in names
    assert "kvstore.collectives" in names
    counters = core.counters()
    assert counters["kvstore.keys"].total == 4          # 2x(W,b)
    assert counters["kvstore.bucket_bytes"].total > 0
    table = mx.profiler.dumps(aggregate=True)
    for phase in ("forward", "backward", "allreduce", "update"):
        assert phase in table


def test_kvstore_per_key_path_counts(obs_on):
    kv = mx.kvstore.create("local")
    kv.init(0, mx.nd.zeros((4,)))
    kv.push(0, mx.nd.ones((4,)))
    out = mx.nd.empty((4,))
    kv.pull(0, out=out)
    assert kv.dispatch_stats["collectives"] == 1
    c = core.counters()
    assert c["kvstore.collectives"].total == 1
    assert c["kvstore.bytes_reduced"].total == 16
    names = {r[1] for r in core.records()}
    assert "kvstore.push" in names and "kvstore.pull" in names


def test_io_iterator_instrumented(obs_on):
    it = mx.io.NDArrayIter(np.zeros((10, 3), np.float32),
                           np.zeros((10,), np.float32), batch_size=5)
    batches = list(it)
    assert len(batches) == 2
    c = core.counters()
    assert c["io.batches"].total == 2
    assert c["io.bytes"].total > 0
    assert any(r[1] == "io.next" for r in core.records())


# ------------------------------------------------------- monitor ----

def test_monitor_gluon_block_hook(obs_on):
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, in_units=4))
        net.add(nn.Dense(2, in_units=8))
    net.initialize()
    mon = mx.monitor.Monitor(interval=1, pattern=".*output.*")
    mon.install_block(net)
    mon.tic()
    net(mx.nd.ones((3, 4)))
    res = mon.toc()
    assert res, "forward hook observed no outputs"
    names = [n for _, n, _ in res]
    assert any("output" in n for n in names)
    # stats also landed as observability gauges
    assert any(k.startswith("monitor.") for k in core.counters())


def test_monitor_inactive_outside_tic(obs_on):
    net = nn.Dense(2, in_units=3)
    net.initialize()
    mon = mx.monitor.Monitor(interval=1)
    mon.install_block(net)
    net(mx.nd.ones((1, 3)))      # before tic: nothing recorded
    assert mon.queue == []


# ------------------------------------------------ overhead guard ----

def test_disabled_span_is_cheap(monkeypatch):
    """Not a benchmark — a structural guard that the disabled path does
    no syscalls/locks: a million disabled spans must run in well under
    a second even on the 1-core CI host."""
    import time
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    t0 = time.perf_counter()
    for _ in range(100_000):
        with core.span("x", cat="y"):
            pass
    dt = time.perf_counter() - t0
    assert dt < 2.0, "disabled span overhead regressed: %.3fs" % dt
