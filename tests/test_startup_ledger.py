"""Start-up's own record (ISSUE 39): the compile ledger that is always
kept (`observability/recompile.py`), the cold calls charged to the spans
that made them and the `startup.*` spans (`observability/core.py`), all of
it with `MXNET_OBS` unset and no profiler session."""

import time

import jax
import jax.numpy as jnp
import pytest

from mxnet_tpu import chip
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import core, recompile


@pytest.fixture
def dark(monkeypatch):
    """Telemetry off and empty, the ledger installed and empty."""
    monkeypatch.delenv("MXNET_OBS", raising=False)
    core.set_enabled(None)
    core.reset()
    recompile.get_detector().reset()
    assert not core.active()
    yield recompile.get_detector()
    core.set_enabled(None)
    core.reset()
    recompile.get_detector().reset()


@pytest.fixture
def disk_cache(tmp_path):
    """JAX's persistent compilation cache in a directory of this test's,
    every entry written whatever its size or compile time."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    old = [getattr(jax.config, n) for n in names]
    for n, v in zip(names, (str(tmp_path / "cache"), 0.0, -1)):
        jax.config.update(n, v)
    cc.reset_cache()
    yield
    for n, v in zip(names, old):
        jax.config.update(n, v)
    cc.reset_cache()


def _program(tag):
    """A jitted function nobody has compiled: the tag is in its HLO."""
    def ledger_probe(x):
        return jnp.tanh(x * tag).sum()
    return jax.jit(ledger_probe)


def _of(det, name):
    return [e for e in det.events if e.get("fun_name") == name]


# ------------------------------------------------------------ the ledger ---

def test_first_call_is_a_trace_a_lowering_and_a_named_miss(dark,
                                                           disk_cache):
    _program(3.25)(jnp.ones((5,)))
    kinds = {e["kind"]: e for e in _of(dark, "ledger_probe")
             + _of(dark, "jit(ledger_probe)")}
    assert set(kinds) == {"trace", "lower", "backend_compile"}
    assert kinds["trace"]["fun_name"] == "ledger_probe"
    assert kinds["lower"]["fun_name"] == "jit(ledger_probe)"
    built = kinds["backend_compile"]
    assert built["cache"] == "miss" and built["retrieval_s"] == 0.0
    assert all(e["duration_s"] > 0 and e["t_ns"] > 0
               for e in kinds.values())
    # ... with nothing else switched on by it
    assert core.records() == []
    assert not any(n.startswith("recompile.") for n in core.counters())
    assert dark.misses == 0 and not dark.flagged


def test_after_clear_caches_the_same_call_is_a_hit(dark, disk_cache):
    fn, x = _program(4.5), jnp.ones((6,))
    fn(x)
    t = core.now_ns()
    jax.clear_caches()
    fn(x)
    first, again = [e for e in _of(dark, "jit(ledger_probe)")
                    if e["kind"] == "backend_compile"]
    assert (first["cache"], again["cache"]) == ("miss", "hit")
    assert again["retrieval_s"] > 0
    # compile seconds = backend less retrieval: near nothing on a hit
    assert again["duration_s"] - again["retrieval_s"] \
        < 0.5 * first["duration_s"]
    warm = recompile.summary(since=t)
    probe, = [p for p in warm["by_program"]
              if p["program"] == "jit(ledger_probe)"]
    assert (probe["builds"], probe["hits"], probe["misses"]) == (1, 1, 0)
    assert warm["hits"] >= 1 and warm["cache_load_s"] > 0
    assert warm["trace_s"] > 0 and warm["lower_s"] > 0   # paid again


def test_without_a_cache_a_build_is_uncached(dark):
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        _program(5.75)(jnp.ones((7,)))
    finally:
        jax.config.update("jax_enable_compilation_cache", old)
        cc.reset_cache()
    built = [e for e in _of(dark, "jit(ledger_probe)")
             if e["kind"] == "backend_compile"]
    assert [e["cache"] for e in built] == ["uncached"]
    assert recompile.summary()["uncached"] >= 1


def test_summary_names_programs_and_cuts_by_time(dark):
    _program(6.5)(jnp.ones((8,)))
    t = core.now_ns()
    early = recompile.summary(before=t)
    assert early["programs"] >= 1 and early["dropped"] == 0
    assert early["programs"] == (early["hits"] + early["misses"]
                                 + early["uncached"])
    top = {p["program"]: p for p in early["by_program"]}
    assert top["jit(ledger_probe)"]["builds"] == 1
    assert top["jit(ledger_probe)"]["seconds"] > 0

    def later(x):
        return jnp.cos(x) * 7.25
    jax.jit(later)(jnp.ones((9,)))
    # what came after `t` is left out before it, and alone since it
    assert recompile.summary(before=t) == early
    since = recompile.summary(since=t)
    assert "jit(later)" in {p["program"] for p in since["by_program"]}
    assert "jit(ledger_probe)" not in {p["program"]
                                       for p in since["by_program"]}
    whole = recompile.summary()
    assert whole["programs"] == early["programs"] + since["programs"]
    assert whole["entries"] == early["entries"] + since["entries"]


def test_the_uncut_summary_is_kept_until_the_ledger_moves(dark):
    _program(8.125)(jnp.ones((3,)))
    first = recompile.summary()
    assert recompile.summary() is first          # a router's every pick
    _program(8.25)(jnp.ones((3,)))
    assert recompile.summary() is not first
    assert recompile.summary()["programs"] > first["programs"]
    dark.reset()
    assert recompile.summary()["entries"] == 0


def test_a_nested_trace_is_counted_in_the_outer_one_alone(dark):
    def ledger_inner(x):
        return jnp.sin(x) * 9.5

    def ledger_outer(x):
        return jax.jit(ledger_inner)(x) + 1.0
    before = recompile.seq
    jax.jit(ledger_outer)(jnp.ones((4,)))
    outer, = [e for e in _of(dark, "ledger_outer") if e["kind"] == "trace"]
    # the inner function's entry, and those of the jnp functions inside
    # both, were replaced by the outer one
    assert _of(dark, "ledger_inner") == []
    assert outer["nested"] >= 2                      # ledger_inner, sin
    assert outer["self_s"] == outer["duration_s"]
    assert recompile.seq - before == len(dark.events) + outer["nested"]
    led = recompile.summary()
    assert led["dropped"] == 0
    assert led["trace_s"] == pytest.approx(sum(
        e["duration_s"] for e in dark.events if e["kind"] == "trace"))
    top = {p["program"]: p for p in led["by_program"]}
    assert top["jit(ledger_outer)"]["builds"] == 1
    assert "jit(ledger_inner)" not in top


def test_an_inner_entry_that_was_buried_keeps_its_own_seconds(dark):
    inner = dark._push("trace", None, None, 0.25, nested=[],
                       fun_name="buried")
    dark._push("lower", None, None, 0.5, fun_name="jit(other)")
    outer = dark._push("trace", None, None, 1.0, nested=[inner],
                       fun_name="outer")
    assert outer["nested"] == 0 and outer["self_s"] == 0.75
    assert inner in dark.events
    assert recompile.summary()["trace_s"] == pytest.approx(1.0)
    innermost = dark._push("trace", None, None, 0.125, nested=[],
                           fun_name="innermost")
    mid = dark._push("trace", None, None, 0.5, nested=[innermost],
                     fun_name="mid")
    top = dark._push("trace", None, None, 2.0, nested=[mid],
                     fun_name="top")
    assert (mid["nested"], top["nested"], top["self_s"]) == (1, 2, 2.0)
    assert innermost not in dark.events and mid not in dark.events
    assert recompile.summary()["dropped"] == 0


def test_a_trace_inside_a_lowering_is_counted_in_the_lowering(dark):
    """A lowering rule that traces (`mlir.lower_fun` over a jitted jnp
    function): jax reports the trace first, the lowering's seconds cover
    it, and `trace_s + lower_s` counts them once."""
    dark.on_event(recompile.JAXPR_TRACE_EVENT, 0.5, "ledger_program")
    dark.on_event(recompile.JAXPR_TRACE_EVENT, 0.001, "_where")
    dark.on_event(recompile.LOWER_EVENT, 0.25, "jit(ledger_program)")
    program, lowering = dark.events
    assert (program["fun_name"], lowering["kind"]) \
        == ("ledger_program", "lower")
    assert (lowering["nested"], lowering["self_s"]) == (1, 0.25)
    led = recompile.summary()
    assert (led["trace_s"], led["lower_s"]) == (0.5, 0.25)
    assert led["dropped"] == 0
    # the next program's trace does not take the lowering's for its own
    dark.on_event(recompile.JAXPR_TRACE_EVENT, 0.001, "ledger_next")
    assert len(dark.events) == 3


def test_seq_counts_every_entry(dark):
    before = recompile.seq
    _program(10.5)(jnp.ones((2,)))
    assert recompile.seq - before >= len(dark.events) >= 3
    assert recompile.seq - before == len(dark.events) + sum(
        e.get("nested", 0) for e in dark.events)


# ----------------------------------------------------------- cold calls ---

def test_a_span_that_is_off_is_charged_only_a_cold_call(dark):
    fn = _program(11.5)
    x = jnp.ones((11,))
    with core.span("serving.admit", cat="serving"):
        fn(x)                                    # traces, lowers, compiles
    cold = core.cold_totals()
    assert cold["serving.admit"]["count"] == 1
    assert cold["serving.admit"]["total_ns"] > 0
    for _ in range(3):
        with core.span("serving.admit", cat="serving"):
            fn(x)                                # warm: nothing is built
    assert core.cold_totals() == cold
    assert core.span("serving.step").start().stop() is None
    assert core.span_totals() == {} and core.records() == []


def test_nested_cold_spans_each_get_their_own_time(dark):
    fn = _program(12.5)
    with core.span("trainer.step", cat="step"):
        time.sleep(0.02)
        with core.span("forward", cat="step"):
            fn(jnp.ones((12,)))
        with core.span("backward", cat="step"):
            pass
    cold = core.cold_totals()
    assert set(cold) == {"trainer.step", "forward"}
    assert cold["trainer.step"]["total_ns"] \
        >= cold["forward"]["total_ns"] + 0.02e9


def test_a_cold_call_is_charged_while_spans_record_too(dark):
    core.set_enabled(True)
    with core.span("serving.step", cat="serving"):
        _program(13.5)(jnp.ones((13,)))
    core.set_enabled(None)
    assert core.cold_totals()["serving.step"]["count"] == 1


def test_a_gluon_forward_that_builds_a_program_is_a_cold_call(dark):
    """The outermost block call opens its `forward` span whatever the
    gates, so the calls that compiled the network are charged."""
    from mxnet_tpu import gluon, nd
    net = gluon.nn.Dense(3, in_units=5)
    net.initialize()
    net.hybridize()
    x = nd.ones((2, 5))
    net(x)
    assert core.cold_totals()["forward"]["count"] == 1
    net(x)                               # a later call may still build one
    settled = core.cold_totals()["forward"]
    for _ in range(3):
        net(x)
    assert core.cold_totals()["forward"] == settled
    assert "forward" not in core.span_totals() and core.records() == []


# ------------------------------------------------------- start-up spans ---

def _tiny_batcher():
    cfg = tf.TransformerConfig(vocab_size=97, d_model=16, n_heads=2,
                               n_layers=1, d_ff=32, max_len=48,
                               dtype=jnp.float32)
    return ContinuousBatcher(tf.init_params(cfg, seed=0), cfg, max_batch=2)


def test_a_batcher_records_its_construction_once(dark):
    srv = _tiny_batcher()
    built = core.span_totals()["startup.batcher"]
    assert built["count"] == 1 and built["total_ns"] > 0
    srv.admit([3, 4, 5], 4)
    for _ in range(3):
        srv.step()
    assert core.span_totals()["startup.batcher"] == built
    # serving's cold calls, seen from inside with every gate off
    cold = core.cold_totals()
    assert cold["serving.admit"]["count"] >= 1
    assert cold["serving.step"]["count"] >= 1
    assert core.records() == []


def test_an_admissions_row_is_one_program_and_a_warm_admission_none(
        dark, fresh_rows):
    """The zeroed row an admission starts from is ONE entry of the
    ledger, named for its maker, built by the first admission; on a
    warmed batcher an admission moves the ledger not at all."""
    srv = _tiny_batcher()
    rows = [p for p in recompile.summary()["by_program"]
            if "fresh_row" in p["program"]]
    assert rows == []                    # the constructor builds none
    srv.admit([3, 4, 5], 4)
    row, = [p for p in recompile.summary()["by_program"]
            if "fresh_row" in p["program"]]
    assert (row["program"], row["builds"]) == ("jit(fresh_row)", 1)
    assert [e["kind"] for e in _of(dark, "fresh_row")] == ["trace"]
    while srv.active_count:
        srv.step()
    before, entries = recompile.seq, len(dark.events)
    cold = core.cold_totals()["serving.admit"]
    srv.admit([6, 7, 8], 4)              # the same bucket, a lane reused
    srv.admit([9, 10], 4)
    assert (recompile.seq, len(dark.events)) == (before, entries)
    assert core.cold_totals()["serving.admit"] == cold
    assert len(fresh_rows.made) == 3


def test_health_snapshot_carries_the_ledger(dark):
    srv = _tiny_batcher()
    snap = srv.health_snapshot()["startup"]
    assert snap == recompile.summary()
    assert {"trace_s", "lower_s", "compile_s", "cache_load_s", "hits",
            "misses", "programs", "by_program"} <= set(snap)


def test_the_packages_import_is_a_startup_span():
    import mxnet_tpu
    assert mxnet_tpu._import_t0 > 0
    # (core.reset() clears the totals with everything else, so the span
    # is closed again here as the last line of the package does)
    core.record_startup("startup.import", time.perf_counter_ns() - 1000)
    got = core.span_totals()["startup.import"]
    assert got["count"] >= 1 and got["total_ns"] >= 1000


def test_the_first_device_query_is_spanned_and_installs_the_ledger(
        dark, monkeypatch):
    monkeypatch.setattr(chip, "_asked", False)
    assert chip.describe()["count"] == len(jax.devices())
    assert core.span_totals()["startup.backend"]["count"] == 1
    assert recompile._listener_installed
    chip.describe()
    chip.peaks()
    assert core.span_totals()["startup.backend"]["count"] == 1


def test_a_startup_span_is_in_the_ring_when_telemetry_is_on(dark):
    t0 = time.perf_counter_ns()
    assert core.record_startup("startup.batcher", t0) >= 0
    assert core.records() == []
    core.set_enabled(True)
    core.record_startup("startup.batcher", t0)
    core.set_enabled(None)
    rec, = [r for r in core.records() if r[0] == "X"]
    assert rec[1:3] == ("startup.batcher", "startup")
    assert core.span_totals()["startup.batcher"]["count"] == 2


def test_the_first_session_is_noted_once(dark, tmp_path, monkeypatch):
    monkeypatch.setattr(core, "_first_session_ns", None)
    with core.span("forward", cat="step"):
        pass
    assert core.first_session_ns() is None       # no session yet
    t = core.now_ns()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        with core.span("forward", cat="step"):
            pass
        first = core.first_session_ns()
        with core.span("forward", cat="step"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert t <= first <= core.now_ns()
    assert core.first_session_ns() == first
    # set-up is what the ledger holds from before it
    assert recompile.summary(before=first)["entries"] \
        == len([e for e in dark.events if e["t_ns"] < first])
