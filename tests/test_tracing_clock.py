"""One gate and one clock (ISSUE 25): the program's spans record under
`MXNET_OBS`/the profiler state OR any live `jax.profiler` session; under a
session they are `mx.<name>` annotations on the profiler's host timeline
and entries of `core.span_totals()`, with parent and self time; nothing
else of the telemetry follows the session; `profiler.Task/Frame/Event` are
the same span; and `dumps(aggregate=True)` lays the device's launches and
idle time against the spans of the session's own `.xplane.pb`."""

import gc
import glob
import os
import threading
import time

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import core, export, recompile
from mxnet_tpu.observability import histogram as hist


@pytest.fixture
def dark(monkeypatch):
    """Telemetry off and empty: no MXNET_OBS, no profiler state."""
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    core.reset()
    recompile.get_detector().reset()
    yield core
    core.set_enabled(None)
    core.reset()
    recompile.get_detector().reset()


class session(object):
    """A bare `jax.profiler` session, as the benchmark opens one: no
    MXNET_OBS, no profiler state, no Python function tracer."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "trace")

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        jax.profiler.stop_trace()

    def host_events(self, prefixes=("mx.", "bench."), args=False):
        """[(name, start_ns, end_ns, line)] of `/host:CPU`; with `args`
        each with the dict of its event's arguments behind."""
        path = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
        out = []
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name == "/host:CPU":
                for i, line in enumerate(plane.lines):
                    for e in line.events:
                        if e.name.startswith(prefixes):
                            out.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns, i)
                                       + ((dict(e.stats),) if args else ()))
        return out


# ------------------------------------------------------- the program ---

def _gluon_step():
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(3))
    net.initialize(mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=mx.kvstore.create("device"))
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.nd.array(np.random.RandomState(0).randn(4, 5))
    y = mx.nd.array(np.array([0, 1, 2, 1]))

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(1)
        return loss
    return step


def _batcher(**kw):
    cfg = tf.TransformerConfig(vocab_size=97, d_model=16, n_heads=2,
                               n_layers=1, d_ff=32, max_len=48,
                               dtype=jax.numpy.float32)
    return ContinuousBatcher(tf.init_params(cfg, seed=0), cfg,
                             **dict({"max_batch": 2}, **kw))


# what the batcher counts while its spans record (models/serving.py
# _count_dispatch and _count_prefill; since PR 48 a model with K/V layers,
# as this one, its decode contractions by the path they took,
# _count_kv_contractions, and since PR 53 an admission's chunk
# contractions, attn.chunk_*; a model with routed experts adds moe.*, one with
# latent layers mla.*, one with window layers kv.rows_*, one with
# hyper-connections hc.rows; since PR 52 the gap ledger's four,
# _count_gaps, made together at the first counted delivery), and since
# PR 49 what a recorded call of a hybridized block hands its backward
# (cached_op.py)
GAP_LEDGER = ("serving.gaps", "serving.gap_ns",
              "serving.gaps_behind_admit", "serving.gap_admit_ns")
WHILE_SPANS_RECORD = set(GAP_LEDGER) | {
    "serving.dispatches", "serving.dispatch_ahead",
    "serving.prefill_tokens", "serving.prefill_rows", "kv.decode_kernel",
    "kv.decode_reference", "attn.chunk_calls", "attn.chunk_kernel",
    "cachedop.recorded_calls",
    "cachedop.saved_buffers", "cachedop.saved_bytes"}


def _serve(srv, rounds):
    srv.admit([3, 4, 5, 6], 40)
    srv.admit([7, 8, 9], 40)
    for _ in range(rounds):
        srv.step()


STEPS = ROUNDS = 3


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Span totals and host events of STEPS Gluon steps and ROUNDS rounds
    of two requests, each under a bare profiler session (warmed first).
    The collector is held still meanwhile: a collection is a span of its
    own since PR 52, and one inside a phase would take its time out of
    the self times the cases below compare exactly."""
    os.environ.pop("MXNET_OBS", None)
    core.set_enabled(None)
    core.reset()
    gc.disable()
    try:
        return _traced(tmp_path_factory)
    finally:
        gc.enable()
        core.reset()


def _traced(tmp_path_factory):
    out = {"records": []}
    step = _gluon_step()
    step()
    srv = _batcher()
    _serve(srv, 1)
    with session(tmp_path_factory.mktemp("gluon")) as s:
        for _ in range(STEPS):
            with jax.profiler.TraceAnnotation("bench.step"):
                step()
    out["gluon"] = (core.span_totals(), s.host_events())
    for name, kw in (("serve", {}),
                     ("serve_depth1", {"pipeline_depth": 1})):
        core.reset()
        _serve(_batcher(**kw), 1)
        srv = _batcher(**kw)
        with session(tmp_path_factory.mktemp(name)) as s:
            _serve(srv, ROUNDS)
        out[name] = (core.span_totals(), s.host_events())
        out["records"] += core.records()
    return out


# ------------------------------------------------------------ the gate ---

def test_no_session_and_no_knob_records_nothing(dark):
    assert not core.active() and not core.enabled()
    with core.span("forward", cat="step"):
        pass
    assert core.span("x").start().stop() is None
    step = _gluon_step()
    step()
    srv = _batcher()
    _serve(srv, 2)
    gc.collect()
    assert core.records() == []
    # but for the start-up spans, which record whatever the gates (no
    # `gc` among them: the collector's hook opened nothing)
    assert {"startup.batcher"} <= set(core.span_totals()) \
        <= {"startup.batcher", "startup.backend"}
    assert core.counters() == {}
    # no request was stamped and the admission ledger never moved
    live = [r for r in srv._slots if r is not None]
    assert len(live) == 2 and all(r.emitted > 1 for r in live)
    for r in live:
        assert (r.t_admit_ns, r.t_first_ns, r.t_last_ns, r.admit_clock,
                r.admit_seq) == (None,) * 5
    assert (srv._admit_seq, srv._admit_clock_ns) == (0, 0)


def test_a_session_switches_spans_on_and_nothing_else(dark, tmp_path):
    step = _gluon_step()
    step()
    srv = _batcher()
    _serve(srv, 1)
    with session(tmp_path):
        assert core.active() and not core.enabled()
        step()
        _serve(_batcher(), 2)
    assert not core.active()
    totals = core.span_totals()
    assert {"forward", "backward", "trainer.step", "serving.admit",
            "serving.step"} <= set(totals)
    # the ring, the counter and gauge registry, the histograms and the
    # recompile detector stay as they were: off, but for the counters
    # that follow the spans' gate
    assert {r[:2] for r in core.records()} \
        == {("C", name) for name in WHILE_SPANS_RECORD}
    assert set(core.counters()) == WHILE_SPANS_RECORD
    assert hist.histograms() == {}
    # (the compile ledger is always kept: tests/test_startup_ledger.py)
    det = recompile.get_detector()
    assert det.misses == 0 and det._steps == 0 and not det.flagged
    assert not any(r[1].startswith("recompile.") for r in core.records())


def test_mxnet_obs_alone_still_fills_the_ring_and_no_totals(dark,
                                                            monkeypatch):
    monkeypatch.setenv("MXNET_OBS", "1")
    assert core.active()
    with core.span("trainer.step", cat="step"):
        with core.span("update", cat="step"):
            pass
    names = [r[1] for r in core.records() if r[0] == "X"]
    assert names == ["update", "trainer.step"]
    assert core.span_totals() == {}


# ----------------------------------------------------------- the clock ---

def test_a_span_lies_inside_the_enclosing_annotation(dark, tmp_path):
    with session(tmp_path) as s:
        with jax.profiler.TraceAnnotation("bench.step"):
            with core.span("forward", cat="step"):
                time.sleep(0.002)
    ev = {name: (a, b, line) for name, a, b, line in s.host_events()}
    assert set(ev) == {"bench.step", "mx.forward"}
    outer, inner = ev["bench.step"], ev["mx.forward"]
    assert outer[2] == inner[2]                     # the same thread
    assert outer[0] <= inner[0] < inner[1] <= outer[1]
    t = core.span_totals()["forward"]
    assert t["count"] == 1 and t["total_ns"] >= 2e6
    # the annotation and the total are the same interval, on two clocks
    assert abs((inner[1] - inner[0]) - t["total_ns"]) < 1e6


# ------------------------------------------------ parent and self time ---

def _nested():
    with core.span("trainer.step", cat="step"):
        with core.span("allreduce", cat="step"):
            time.sleep(0.003)
        with core.span("update", cat="step"):
            time.sleep(0.002)
        time.sleep(0.001)


def test_self_time_is_total_less_the_spans_inside(dark, tmp_path):
    with session(tmp_path):
        _nested()
        _nested()
    t = core.span_totals()
    assert t["trainer.step"]["count"] == 2
    assert t["trainer.step"]["self_ns"] == (
        t["trainer.step"]["total_ns"] - t["allreduce"]["total_ns"]
        - t["update"]["total_ns"])
    assert t["trainer.step"]["self_ns"] >= 2e6
    assert t["update"]["self_ns"] == t["update"]["total_ns"]
    assert t["allreduce"]["max_ns"] <= t["allreduce"]["total_ns"]


def test_the_aggregate_table_reports_self_beside_total(dark, monkeypatch):
    monkeypatch.setenv("MXNET_OBS", "1")
    _nested()
    spans = export.aggregate()["spans"]
    step = spans["trainer.step"]
    assert step["self_ms"] == pytest.approx(
        step["total_ms"] - spans["allreduce"]["total_ms"]
        - spans["update"]["total_ms"], abs=0.01)
    assert spans["update"]["self_ms"] == spans["update"]["total_ms"]
    assert "Self(ms)" in export.aggregate_table()


def test_each_thread_has_its_own_parents(dark, tmp_path):
    inside = threading.Event()
    done = threading.Event()

    def other():
        inside.wait()
        with core.span("io.next", cat="io"):
            time.sleep(0.002)
        done.set()

    th = threading.Thread(target=other)
    with session(tmp_path):
        th.start()
        with core.span("forward", cat="step"):
            inside.set()
            done.wait()
        th.join()
    t = core.span_totals()
    # the other thread's span ran while `forward` was open, and is not
    # its child
    assert t["forward"]["self_ns"] == t["forward"]["total_ns"]
    assert t["io.next"]["count"] == 1


def test_a_span_left_open_does_not_adopt_later_ones(dark, tmp_path):
    with session(tmp_path):
        stale = core.span("serving.prefill").start()   # never stopped
        outer = core.span("serving.admit").start()
        outer.stop()
        with core.span("serving.step"):
            pass
        stale.stop()
    t = core.span_totals()
    assert t["serving.step"]["count"] == 1
    assert t["serving.prefill"]["self_ns"] < t["serving.prefill"]["total_ns"]


# ------------------------------------------------- profiler's own spans ---

def test_profiler_objects_are_the_same_span(dark, tmp_path):
    mx.profiler.dumps(reset=True)
    d = mx.profiler.Domain("unit")
    with d.new_task("off_task"):            # profiler not running
        pass
    mx.profiler.set_config(filename=str(tmp_path / "p.json"),
                           xla_trace=False)
    try:
        mx.profiler.set_state("run")
        with d.new_task("tsk"):
            with d.new_frame("frm"):
                pass
        with mx.profiler.Event("evt"):
            pass
        mx.profiler.set_state("stop")
    finally:
        mx.profiler.set_config(filename="profile.json", xla_trace=True)
    recs = {r[1]: r for r in core.records() if r[0] == "X"}
    assert set(recs) == {"tsk", "frm", "evt"}
    assert recs["tsk"][2] == "task" and recs["frm"][2] == "frame"
    assert recs["tsk"][6]["domain"] == "unit"
    assert "self_us" in recs["tsk"][6] and "self_us" not in recs["frm"][6]
    flat = mx.profiler.dumps(reset=True)
    for name in ("off_task", "tsk", "frm", "evt"):
        assert name in flat
    import inspect
    src = inspect.getsource(mx.profiler._Span)
    assert "perf_counter" not in src and "TraceAnnotation" not in src


def test_set_state_run_and_dumps_reset_clear_the_totals(dark, tmp_path):
    with session(tmp_path):
        with core.span("forward"):
            pass
    assert "forward" in core.span_totals()
    mx.profiler.set_config(filename=str(tmp_path / "p.json"),
                           xla_trace=False)
    try:
        mx.profiler.set_state("run")
        assert core.span_totals() == {}
        mx.profiler.set_state("stop")
    finally:
        mx.profiler.set_config(filename="profile.json", xla_trace=True)
    with session(tmp_path):
        with core.span("forward"):
            pass
    mx.profiler.dumps(reset=True, aggregate=True)
    assert core.span_totals() == {}


# ------------------------------------------------ the device, by span ---

def test_idle_goes_to_the_innermost_span_at_the_gaps_middle():
    spans = [(0, 100, "mx.trainer.step", 0),
             (10, 40, "mx.allreduce", 0),
             (50, 90, "mx.update", 0),
             (200, 300, "mx.forward", 0),
             (60, 70, "mx.io.next", 1)]         # another thread, narrower
    busy = [(0, 5), (3, 12),                    # merged: no gap at 5..3
            (30, 45),                           # gap 12-30, middle 21
            (55, 64), (66, 80),                 # gaps 45-55 (50), 64-66 (65)
            (110, 130),                         # gap 80-110, middle 95
            (180, 250)]                         # gap 130-180, middle 155
    launches = [1, 11, 39, 40, 65, 95, 150, 299, 300]
    rows = export.idle_by_span(spans, launches, busy)
    assert rows["mx.allreduce"]["idle"] == 18           # 12..30
    assert rows["mx.update"]["idle"] == 10              # 45..55
    assert rows["mx.io.next"]["idle"] == 2              # narrowest wins
    assert rows["mx.trainer.step"]["idle"] == 30        # 80..110 at 95
    assert rows[export.OUTSIDE]["idle"] == 50           # 130..180
    assert "idle" in rows["mx.forward"] and rows["mx.forward"]["idle"] == 0
    assert sum(r["idle"] for r in rows.values()) == 110
    # launches: 1 -> step, 11 and 39 -> allreduce, 40 -> step (allreduce
    # has ended), 65 -> io.next, 95 -> step, 150 -> outside, 299 -> forward,
    # 300 -> outside
    assert rows["mx.trainer.step"]["launches"] == 3
    assert rows["mx.allreduce"]["launches"] == 2
    assert rows["mx.io.next"]["launches"] == 1
    assert rows["mx.forward"]["launches"] == 1
    assert rows[export.OUTSIDE]["launches"] == 2
    # host totals and self time from the same intervals
    step = rows["mx.trainer.step"]
    assert (step["calls"], step["total"], step["self"]) == (1, 100, 30)
    assert rows["mx.io.next"]["self"] == 10             # its own thread


def test_idle_with_no_spans_is_all_outside():
    rows = export.idle_by_span([], [5], [(0, 10), (20, 30)])
    assert rows == {export.OUTSIDE: {"calls": 0, "total": 0.0, "self": 0.0,
                                     "launches": 1, "idle": 10}}


def test_dumps_aggregate_ends_with_the_device_by_span(dark, tmp_path):
    step = _gluon_step()
    step()
    mx.profiler.set_config(filename=str(tmp_path / "prof.json"))
    try:
        mx.profiler.set_state("run")
        assert mx.profiler.dumps(aggregate=True).count(
            "Device by program span") == 0          # still being taken
        for _ in range(2):
            step()
        mx.profiler.set_state("stop")
        table = mx.profiler.dumps(aggregate=True)
    finally:
        mx.profiler.set_config(filename="profile.json")
    assert "Self(ms)" in table
    tail = table[table.index("Device by program span"):]
    for name in ("mx.forward", "mx.backward", "mx.trainer.step",
                 "mx.allreduce", "mx.update"):
        assert name in tail
    # a CPU has no device plane: the host columns are there, the device's
    # are empty
    assert "no device plane in this trace" in tail
    row = [l for l in tail.splitlines() if l.startswith("mx.backward")][0]
    assert row.split()[1] == "2"
    mx.profiler.dumps(reset=True, aggregate=True)
    assert "Device by program span" not in mx.profiler.dumps(aggregate=True)


# ------------------------------- one case per span the benchmark reads ---

PER_STEP = {"forward": 2,          # the network and the loss block
            "backward": 1, "trainer.step": 1, "allreduce": 1, "update": 1}
PER_ROUND = {"serving.step": 1, "serving.dispatch": 1, "serving.sync": 1}
# the default keeps two rounds in flight: the first step() dispatches both
FILL = {"serving.dispatch": 1}


@pytest.mark.parametrize("name", sorted(PER_STEP))
def test_gluon_span_fires_this_often_a_step(traced, name):
    totals, events = traced["gluon"]
    assert totals[name]["count"] == PER_STEP[name] * STEPS
    mine = [e for e in events if e[0] == "mx." + name]
    assert len(mine) == PER_STEP[name] * STEPS
    steps = [e for e in events if e[0] == "bench.step"]
    assert len(steps) == STEPS
    for _, a, b, line in mine:          # inside a step of the benchmark
        assert any(sa <= a and b <= sb and sl == line
                   for _, sa, sb, sl in steps)


def test_gluon_step_phases_nest_as_the_metrics_assume(traced):
    totals, events = traced["gluon"]
    step = totals["trainer.step"]
    inside = totals["allreduce"]["total_ns"] + totals["update"]["total_ns"]
    # kvstore push/pull are spans of their own inside allreduce
    assert step["self_ns"] == step["total_ns"] - inside
    assert 0 <= step["self_ns"] < step["total_ns"]
    assert totals["allreduce"]["self_ns"] < totals["allreduce"]["total_ns"]
    for name in ("forward", "backward"):
        assert totals[name]["self_ns"] == totals[name]["total_ns"]
    assert {r[1] for r in traced["records"]} <= WHILE_SPANS_RECORD


@pytest.mark.parametrize("name", sorted(PER_ROUND) + ["serving.admit",
                                                     "serving.prefill"])
def test_serving_span_fires_this_often(traced, name):
    totals, events = traced["serve"]
    want = 2 if name in ("serving.admit", "serving.prefill") \
        else PER_ROUND[name] * ROUNDS + FILL.get(name, 0)
    assert totals[name]["count"] == want
    assert len([e for e in events if e[0] == "mx." + name]) == want
    if name in PER_ROUND:               # a window of one: no fill
        assert traced["serve_depth1"][0][name]["count"] \
            == PER_ROUND[name] * ROUNDS


def test_a_window_of_one_syncs_its_own_dispatch_inside_one_step(traced):
    """At pipeline_depth=1 the n-th serving.sync follows the n-th
    serving.dispatch, its own round's, inside the n-th serving.step:
    a round's tokens come back in the step() that dispatched it, and
    the wait is no part of the dispatch's time."""
    totals, events = traced["serve_depth1"]

    def of(name):
        return sorted((a, b) for n, a, b, _ in events if n == "mx." + name)
    rounds = list(zip(of("serving.step"), of("serving.dispatch"),
                      of("serving.sync")))
    assert len(rounds) == ROUNDS
    for (sa, sb), (da, db), (ya, yb) in rounds:
        assert sa <= da < db <= ya < yb <= sb
    for (aa, ab), (pa, pb) in zip(of("serving.admit"),
                                  of("serving.prefill")):
        assert aa <= pa < pb <= ab
    assert totals["serving.sync"]["self_ns"] \
        == totals["serving.sync"]["total_ns"]
    assert totals["serving.dispatch"]["self_ns"] \
        == totals["serving.dispatch"]["total_ns"]
    assert totals["serving.admit"]["self_ns"] == (
        totals["serving.admit"]["total_ns"]
        - totals["serving.prefill"]["total_ns"])


@pytest.mark.parametrize("kw", [dict(), dict(pipeline_depth=2),
                                dict(spec_k=2), dict(pipeline_depth=1),
                                dict(spec_k=2, pipeline_depth=1)],
                         ids=["default", "pipelined", "speculative",
                              "depth1", "speculative-depth1"])
def test_every_step_variant_is_one_serving_step_a_round(dark, tmp_path, kw):
    srv = _batcher(**kw)
    _serve(srv, 1)
    core.reset()
    srv = _batcher(**kw)
    with session(tmp_path):
        _serve(srv, ROUNDS)
    t = core.span_totals()
    assert t["serving.step"]["count"] == ROUNDS
    assert t["serving.sync"]["count"] == ROUNDS
    assert t["serving.admit"]["count"] == 2
    # sync is inside step, never inside dispatch, at every depth
    assert t["serving.dispatch"]["self_ns"] == t["serving.dispatch"][
        "total_ns"]
    assert t["serving.step"]["self_ns"] <= (
        t["serving.step"]["total_ns"] - t["serving.sync"]["total_ns"])


# ------------------------------------ the gap ledger (PR 52), scripted ---

ADMIT_NS, ROUND_NS = 7_000_000, 10_000_000


class _Clock(object):
    """serving.py's `time`, scripted: it stands still unless the test
    moves it, and every admission moves it by ADMIT_NS (below)."""

    def __init__(self):
        self.t = 1_000_000_000

    def perf_counter_ns(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    from mxnet_tpu.models import serving
    c = _Clock()
    monkeypatch.setattr(serving, "time", c)
    fresh_row = ContinuousBatcher._fresh_row

    def slow_row(self, cfg=None):       # inside admit(), after its t0
        c.t += ADMIT_NS
        return fresh_row(self, cfg)
    monkeypatch.setattr(ContinuousBatcher, "_fresh_row", slow_row)
    return c


def _round(srv, clock):
    clock.t += ROUND_NS
    return srv.step()


def _ledger():
    c = core.counters()
    return tuple(int(c[name].value) if name in c else None
                 for name in GAP_LEDGER)


@pytest.mark.parametrize("depth", [1, 2])
def test_a_gap_behind_an_admission_is_counted_with_its_duration(
        dark, tmp_path, clock, depth):
    """Two lanes decoding, a third admitted between two syncs: both
    wait behind it, by its admit()'s duration each; its own first gap
    is not behind itself."""
    srv = _batcher(max_batch=3, pipeline_depth=depth)
    with session(tmp_path):
        srv.admit([3, 4, 5, 6], 40)
        srv.admit([7, 8, 9], 40)
        _round(srv, clock)
        _round(srv, clock)
        gaps, ns, behind, admit_ns = _ledger()
        # the first request waited behind the second's admission, once
        assert (gaps, behind, admit_ns) == (4, 1, ADMIT_NS)
        assert ns == 4 * ROUND_NS + ADMIT_NS
        rid = srv.admit([1, 2, 3], 40)
        new = next(r for r in srv._slots if r.rid == rid)
        assert (new.admit_seq, new.admit_clock) == (3, 3 * ADMIT_NS)
        assert new.t_last_ns == clock.t
        for _ in range(3):
            _round(srv, clock)
    assert new.emitted > 1              # its first gaps are among them
    gaps2, ns2, behind2, admit_ns2 = _ledger()
    assert behind2 - behind == 2
    assert admit_ns2 - admit_ns == 2 * ADMIT_NS
    # 3 rounds of the two older lanes; the new one's tokens of the
    # in-flight rounds dispatched before it was admitted belong to no one
    assert gaps2 - gaps == 6 + new.emitted - 1
    assert ns2 - ns == 2 * (3 * ROUND_NS + ADMIT_NS) \
        + (clock.t - new.t_first_ns)
    assert admit_ns2 <= ns2


def test_a_chunk_of_k_tokens_is_k_gaps_and_one_behind(dark, tmp_path,
                                                     clock):
    srv = _batcher(max_batch=2, chunk_size=3, pipeline_depth=1)
    with session(tmp_path):
        srv.admit([3, 4, 5, 6], 40)
        _round(srv, clock)
        assert _ledger() == (3, ROUND_NS, 0, 0)
        srv.admit([7, 8, 9], 40)
        _round(srv, clock)
    # the older lane: 3 more gaps, ONE of them behind the admission; the
    # new lane: its first chunk, behind nothing
    assert _ledger() == (9, 3 * ROUND_NS + ADMIT_NS, 1, ADMIT_NS)


def test_a_session_that_opens_mid_request_skips_its_first_delivery(
        dark, tmp_path, clock):
    srv = _batcher(max_batch=2, pipeline_depth=1)
    srv.admit([3, 4, 5, 6], 40)
    _round(srv, clock)
    req = srv._slots[0]
    assert req.emitted == 2 and req.t_last_ns is None
    with session(tmp_path):
        _round(srv, clock)              # stamped here, and not counted
        assert req.t_last_ns == clock.t and req.admit_seq == 0
        assert not set(GAP_LEDGER) & set(core.counters())
        _round(srv, clock)
        assert _ledger() == (1, ROUND_NS, 0, 0)
    # and a stamp from a session that has closed is not a gap's start
    for _ in range(3):
        _round(srv, clock)
    assert req.t_last_ns == clock.t - 3 * ROUND_NS
    with session(tmp_path / "again"):
        _round(srv, clock)
        _round(srv, clock)
    assert _ledger() == (2, 2 * ROUND_NS, 0, 0)


def test_a_continuation_moves_the_ledger_as_an_admission_does(
        dark, tmp_path, clock):
    srv = _batcher(max_batch=2, pipeline_depth=1)
    with session(tmp_path):
        srv.admit([3, 4, 5, 6], 40)
        _round(srv, clock)
        rid = srv.admit_continuation([7, 8, 9, 10], 20, emitted=1)
        new = next(r for r in srv._slots if r.rid == rid)
        assert (new.admit_seq, new.admit_clock) == (2, 2 * ADMIT_NS)
        _round(srv, clock)
    assert _ledger() == (3, 3 * ROUND_NS + ADMIT_NS, 1, ADMIT_NS)


# ------------------ the fetch that ends an admission, and what a span's ---
# ------------------------------------------ arguments look like in a trace

def test_first_token_is_one_span_an_admission_with_its_rid(dark, tmp_path):
    _serve(_batcher(), 1)                                   # warm
    core.reset()
    srv = _batcher()
    with session(tmp_path) as s:
        _serve(srv, ROUNDS)
    t = core.span_totals()
    assert t["serving.first_token"]["count"] == 2
    assert t["serving.prefill"]["self_ns"] <= (
        t["serving.prefill"]["total_ns"]
        - t["serving.first_token"]["total_ns"])
    ev = s.host_events(args=True)

    def of(name):
        return sorted((a, b, args) for n, a, b, _, args in ev
                      if n == "mx." + name)
    fetches = of("serving.first_token")
    assert [args["rid"] for _, _, args in fetches] == [0, 1]
    assert [args["lane"] for _, _, args in fetches] == [0, 1]
    for (aa, ab, _), (pa, pb, pargs), (fa, fb, fargs) in zip(
            of("serving.admit"), of("serving.prefill"), fetches):
        assert aa <= pa <= fa < fb <= pb <= ab
        # the spans of one request share its rid in the trace
        assert pargs["rid"] == fargs["rid"]
    # every scalar argument of a span is in its event
    assert {args["behind"] for _, _, args in of("serving.sync")} == {1}
    assert {args["kind"] for _, _, args in of("serving.patch")} == {"admit"}


def test_a_continuation_fetches_nothing_and_has_no_first_token_span(
        dark, tmp_path):
    srv = _batcher()
    with session(tmp_path):
        srv.admit_continuation([7, 8, 9, 10], 5, emitted=1)
        srv.step()
    t = core.span_totals()
    assert t["serving.prefill"]["count"] == 1
    assert "serving.first_token" not in t


# ---------------------------------------------- a collection is a span ---

@pytest.fixture
def still():
    """The collector held still, so that the only collections are the
    test's own."""
    gc.collect()
    gc.disable()
    yield
    gc.enable()


def test_a_collection_under_a_session_is_one_gc_span(dark, tmp_path,
                                                     still):
    gc.collect()
    assert "gc" not in core.span_totals()           # no session: nothing
    with session(tmp_path) as s:
        with core.span("serving.step", cat="serving"):
            # seeded by the first span that saw the session
            assert core.span_totals()["gc"] == {
                "count": 0, "total_ns": 0, "self_ns": 0, "max_ns": 0}
            gc.collect()
    t = core.span_totals()
    assert t["gc"]["count"] == 1 and t["gc"]["max_ns"] == t["gc"]["total_ns"]
    # nested in the span that was open: its self time does not carry it
    assert t["serving.step"]["self_ns"] \
        == t["serving.step"]["total_ns"] - t["gc"]["total_ns"]
    (name, a, b, _, args), = s.host_events(prefixes=("mx.gc",), args=True)
    assert args["generation"] == 2
    gc.collect()
    assert core.span_totals()["gc"]["count"] == 1   # the session is over


def test_a_collection_under_mxnet_obs_is_a_record(dark, monkeypatch, still):
    monkeypatch.setenv("MXNET_OBS", "1")
    gc.collect(0)
    recs = [r for r in core.records() if r[0] == "X"]
    assert [(r[1], r[2], r[6]["generation"]) for r in recs] \
        == [("gc", "runtime", 0)]
    assert core.span_totals() == {}


def test_a_collection_that_starts_under_the_modules_lock_takes_none(
        dark, monkeypatch, still):
    """A collection can start inside any allocation, one made while
    core's lock is held among them; its hook runs there and then."""
    monkeypatch.setenv("MXNET_OBS", "1")
    with core._lock:
        gc.collect(0)
    assert [r[1] for r in core.records() if r[0] == "X"] == ["gc"]


def test_collections_on_many_threads_are_each_one_record(
        dark, monkeypatch, still):
    """The hook leaves finished collections in a list that spans' stops
    and the readers fold from any thread: none is lost or counted twice,
    and nobody waits for anybody."""
    import sys
    monkeypatch.setenv("MXNET_OBS", "1")
    each, workers = 50, 8
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)

    def work():
        for _ in range(each):
            with core.span("serving.step", cat="serving"):
                gc.collect(0)
            core.span_totals()

    ran = []        # a collect() that finds one in progress runs none

    def count(phase, info):
        if phase == "stop":
            ran.append(info["generation"])

    threads = [threading.Thread(target=work) for _ in range(workers)]
    gc.callbacks.append(count)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        gc.callbacks.remove(count)
        sys.setswitchinterval(old)
    assert not any(th.is_alive() for th in threads)
    names = [r[1] for r in core.records() if r[0] == "X"]
    assert names.count("serving.step") == each * workers
    assert each <= names.count("gc") == len(ran)
    assert core.dropped() == 0


# -------------------- the inside count is the outside count (PR 52) ---

def test_the_ledger_counts_the_gaps_a_closed_loop_client_counts(
        dark, tmp_path):
    import json
    from chipbench import manifest
    from chipbench.runners import serve_lm
    from chipbench.traffic import ClosedLoop, request_stream
    here = os.path.join(os.path.dirname(__file__), "bench_harness")
    with open(os.path.join(here, "tiny", "lm.json")) as f:
        lm = json.load(f)
    traffic = dict(
        manifest.load_traffic("closed24"), clients=3, pool=6, max_total=64,
        prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 40},
        output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20})
    sess = serve_lm.build(lm, traffic, 5)
    loop = ClosedLoop(sess, traffic,
                      request_stream(traffic, 5, lm["vocab_size"]))
    t_open = time.perf_counter()
    with session(tmp_path):
        loop.run_until(turned_over=8)
    m = loop.reduce(t_open, time.perf_counter())
    c = core.counters()
    assert m["gaps"] > 40 and len(m["finished"]) >= 8
    assert c["serving.gaps"].value == m["gaps"]
    assert 0 < c["serving.gaps_behind_admit"].value < m["gaps"]
    assert 0 < c["serving.gap_admit_ns"].value <= c["serving.gap_ns"].value
