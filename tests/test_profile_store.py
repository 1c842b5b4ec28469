"""Performance archive (observability/profile_store.py +
costmodel.py + tools/perf_timeline.py, ISSUE 18): CRC-framed record
round-trip, merge-across-runs, corruption evidence, retention caps,
signature stability under re-jit, calibration fit vs a numpy
least-squares reference, the ``--history`` rolling-window sentinel's
boundary cases, and off-path silence with MXNET_OBS_PROFILE_DIR
unset."""

import contextlib
import importlib.util
import io
import json
import os
import time

import numpy as np
import pytest

from mxnet_tpu.observability import (core, costmodel, membudget,
                                     profile_store)

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        "%s_for_test" % name, os.path.join(ROOT, "tools",
                                           "%s.py" % name))
    mod = importlib.util.module_from_spec(spec)
    # the tools switch telemetry on for their own process when imported
    # (os.environ.setdefault("MXNET_OBS", "1")): here that would leave it
    # on for every later test of this worker
    before = os.environ.get("MXNET_OBS")
    try:
        spec.loader.exec_module(mod)
    finally:
        if before is None:
            os.environ.pop("MXNET_OBS", None)
        else:
            os.environ["MXNET_OBS"] = before
    return mod


@pytest.fixture
def store(tmp_path, monkeypatch):
    """An enabled, empty archive directory for one test."""
    d = str(tmp_path / "perf")
    monkeypatch.setenv("MXNET_OBS_PROFILE_DIR", d)
    monkeypatch.delenv("MXNET_OBS_PROFILE_RUN", raising=False)
    profile_store.reset()
    yield d
    profile_store.reset()


def _scope_rec(scope, run, p50, ts, flops=0, hbm=0, sig=None,
               block_k=None):
    cfg = {"env": {}}
    if block_k is not None:
        cfg["env"]["MXNET_PAGED_BLOCK_K"] = str(block_k)
    return {"schema": 1, "kind": "scope", "run": run, "ts": ts,
            "scope": scope,
            "sig": sig or profile_store.signature_key(scope, "", "fid"),
            "fingerprint": "fid", "config": cfg,
            "stats": {"count": 3, "total_ms": 3 * p50, "p50_ms": p50,
                      "p99_ms": p50 * 1.2},
            "flops": flops, "hbm_bytes": hbm}


# ------------------------------------------------ framing/round-trip ---

def test_record_round_trip(store):
    recs = [_scope_rec("decode", "run1", 5.0, 10.0),
            _scope_rec("prefill", "run1", 7.0, 11.0)]
    for r in recs:
        assert profile_store.append(r) is not None
    loaded, evidence = profile_store.load(store)
    assert evidence == []
    assert loaded == sorted(recs, key=lambda r: r["ts"])


def test_merge_across_runs(store):
    for runi in range(3):
        profile_store.append(_scope_rec("decode", "run%d" % runi,
                                        5.0 + runi, 10.0 + runi))
    loaded, _ = profile_store.load(store)
    groups = profile_store.merge_by_signature(loaded)
    assert len(groups) == 1
    g = next(iter(groups.values()))
    assert g["runs"] == ["run0", "run1", "run2"]
    series = profile_store.run_series(g, metric="p50_ms")
    assert [v for _r, _t, v in series] == [5.0, 6.0, 7.0]


def test_corruption_evidence_names_file_and_offset(store):
    for i in range(3):
        profile_store.append(_scope_rec("decode", "run1", 5.0, 10.0 + i))
    path = profile_store.host_file(store)
    data = open(path, "rb").read()
    # flip one byte inside the SECOND frame's json body
    frames = data.split(profile_store.MAGIC)
    second_off = len(frames[0]) + len(profile_store.MAGIC) \
        + len(frames[1])
    body_at = data.find(b'"schema"', second_off)
    corrupt = bytearray(data)
    corrupt[body_at] ^= 0xFF
    open(path, "wb").write(bytes(corrupt))
    loaded, evidence = profile_store.load(store)
    assert len(loaded) == 2                     # bad frame skipped
    assert len(evidence) == 1
    assert evidence[0]["evidence"] == "crc-mismatch"
    assert evidence[0]["file"] == path
    assert evidence[0]["offset"] == second_off


def test_torn_tail_evidence(store):
    for i in range(2):
        profile_store.append(_scope_rec("decode", "run1", 5.0, 10.0 + i))
    path = profile_store.host_file(store)
    data = open(path, "rb").read()
    open(path, "wb").write(data[:-20])          # crash mid-write
    loaded, evidence = profile_store.load(store)
    assert len(loaded) == 1
    assert len(evidence) == 1
    assert evidence[0]["evidence"] == "torn-payload"
    assert evidence[0]["offset"] > 0


def test_retention_cap(store, monkeypatch):
    monkeypatch.setenv("MXNET_OBS_PROFILE_KEEP", "2")
    for i in range(5):
        profile_store.append(_scope_rec("decode", "run%d" % i, 5.0,
                                        10.0 + i))
    profile_store.append(_scope_rec("other", "run0", 1.0, 1.0))
    dropped = profile_store.prune(store)
    assert dropped == 3
    loaded, _ = profile_store.load(store)
    decode = [r for r in loaded if r["scope"] == "decode"]
    assert len(decode) == 2                     # newest kept
    assert sorted(r["run"] for r in decode) == ["run3", "run4"]
    assert any(r["scope"] == "other" for r in loaded)   # untouched


# --------------------------------------------------- signatures -------

def test_signature_stable_under_rejit():
    # a re-jit with a widened batch axis: same key
    a = profile_store.normalize_signature("f32[8,128],f32[128] flags=1")
    b = profile_store.normalize_signature("f32[16,128],f32[128] flags=1")
    assert a == b == "f32[*,128],f32[128] flags=1"
    # rank-1 shapes stay exact (their size IS the workload)
    assert profile_store.normalize_signature("f32[128]") == "f32[128]"
    # rename counters strip; real names survive
    assert profile_store.normalize_scope("dense_1") == "dense"
    assert profile_store.normalize_scope("paged_decode_kernel_2") \
        == "paged_decode_kernel"
    assert profile_store.normalize_scope("conv2d") == "conv2d"
    assert profile_store.signature_key("dense_1", "f32[8,4]", "fid") \
        == profile_store.signature_key("dense", "f32[8,4]", "fid")


def test_fingerprint_tracks_env_knobs(store, monkeypatch):
    fid1, cfg = profile_store.config_fingerprint()
    assert "MXNET_PAGED_BLOCK_K" not in cfg["env"]
    monkeypatch.setenv("MXNET_PAGED_BLOCK_K", "256")
    fid2, cfg2 = profile_store.config_fingerprint()
    assert fid1 != fid2
    assert cfg2["env"]["MXNET_PAGED_BLOCK_K"] == "256"


def test_fingerprint_no_discovery_reads_archived_device_doc(store):
    # the orchestrator mode (a parent that must stay off jax so its
    # child can hold the chip): discover=False must not initialize a
    # backend — the device doc comes from the archive
    fid, cfg = profile_store.config_fingerprint(discover=False)
    assert cfg["device_kind"] == "?"        # empty archive: placeholder
    rec = _scope_rec("decode", "run0", 5.0, 10.0)
    rec["config"] = {"device_kind": "testchip-v1", "backend": "testchip",
                     "n_devices": 1, "n_processes": 1, "env": {}}
    profile_store.append(rec)
    # the placeholder was NOT cached: the next call upgrades to the
    # leg-archived doc and fingerprints diverge accordingly
    fid2, cfg2 = profile_store.config_fingerprint(discover=False)
    assert cfg2["device_kind"] == "testchip-v1"
    assert fid2 != fid
    # append_bench with an explicit fingerprint recomputes nothing
    path = profile_store.append_bench("leg", value=1.0, unit="x",
                                      fingerprint=fid2, config=cfg2)
    assert path is not None
    loaded, _ = profile_store.load(store)
    bench = [r for r in loaded if r.get("kind") == "bench"]
    assert bench and bench[0]["fingerprint"] == fid2


def test_record_run_spans(store, monkeypatch):
    monkeypatch.setenv("MXNET_OBS", "1")
    core.set_enabled(True)
    core.reset()
    # programs an earlier test of this process registered for attribution
    # are scopes too: the one span recorded here is to be the only record
    from mxnet_tpu.observability import attribution
    attribution.reset()
    try:
        t0 = time.perf_counter_ns()
        core.record_span("phase.step", "phase", t0, t0 + 4_000_000)
        monkeypatch.setenv("MXNET_OBS_PROFILE_RUN", "runA")
        assert profile_store.record_run() == 1
    finally:
        core.set_enabled(None)
        core.reset()
    loaded, evidence = profile_store.load(store)
    assert evidence == []
    (rec,) = loaded
    assert rec["scope"] == "phase.step"
    assert rec["run"] == "runA"
    assert rec["stats"]["count"] == 1
    assert rec["stats"]["p50_ms"] == pytest.approx(4.0)
    assert rec["fingerprint"]


# ---------------------------------------------------- cost model ------

def _roofline_archive(store, slope_f=2.0, slope_b=1.0, const=0.5):
    """Archive 4 scope families x 3 runs whose measured ms is an exact
    linear function of the roofline terms."""
    from mxnet_tpu.observability import attribution
    pf, bw = attribution.peak_flops(), attribution.hbm_bw()
    pts = []
    i = 0
    for scope, flops, hbm in [("conv", 1e12, 1e9), ("dense", 5e11, 5e9),
                              ("norm", 1e10, 2e10), ("attn", 2e12, 8e9)]:
        for runi in range(3):
            f, h = flops * (1 + 0.1 * runi), hbm * (1 + 0.1 * runi)
            ms = slope_f * 1e3 * f / pf + slope_b * 1e3 * h / bw + const
            profile_store.append(_scope_rec(scope, "run%d" % runi, ms,
                                            10.0 + i, flops=f, hbm=h))
            pts.append((f / pf * 1e3, h / bw * 1e3, ms))
            i += 1
    return pts


def test_calibration_fit_matches_numpy_lstsq(store):
    pts = _roofline_archive(store)
    model = costmodel.fit()
    X = np.array([[f, b, 1.0] for f, b, _ in pts])
    y = np.array([ms for _f, _b, ms in pts])
    ref, _res, _rank, _sv = np.linalg.lstsq(X, y, rcond=None)
    assert model["global"]["kind"] == "lsq"
    assert model["global"]["coef"] == pytest.approx(list(ref), rel=1e-6)
    assert model["global"]["calib_err"] < 0.01


def test_predict_heldout_within_calibration_error(store):
    _roofline_archive(store)
    # hold attn out of the fit entirely; predict it from the others
    model = costmodel.fit(exclude_scope="attn")
    assert "attn" not in model["families"]
    pred = costmodel.predict(scope="attn", model=model)
    records, _ = profile_store.load(store)
    measured = max(r["stats"]["p50_ms"] for r in records
                   if r["scope"] == "attn")     # newest = largest here
    err_bound = max(model["global"]["calib_err"], 0.01)
    assert pred == pytest.approx(measured, rel=err_bound)


def test_calibration_report_and_table(store):
    _roofline_archive(store)
    rows = costmodel.calibration_report()
    assert {r["scope"] for r in rows} == {"conv", "dense", "norm",
                                          "attn"}
    for r in rows:
        assert r["predicted_ms"] == pytest.approx(r["measured_ms"],
                                                  rel=0.05)
    table = costmodel.format_calibration_table()
    assert any("Cost model calibration" in ln for ln in table)
    assert any("conv" in ln for ln in table)


def test_costmodel_off_without_store(monkeypatch):
    monkeypatch.delenv("MXNET_OBS_PROFILE_DIR", raising=False)
    assert costmodel.format_calibration_table() == []
    model = costmodel.fit()
    assert model["n"] == 0 and model["global"] is None
    assert costmodel.predict(scope="anything") is None
    assert membudget.predicted_step_ms(scope="anything") is None


def test_membudget_predicted_step_ms(store):
    _roofline_archive(store)
    costmodel.reset_cache()
    pred = membudget.predicted_step_ms(scope="conv")
    assert pred is not None and pred > 0


def test_cached_fit_memoizes_until_archive_changes(store, monkeypatch):
    _roofline_archive(store)
    costmodel.reset_cache()
    records, model = costmodel.cached_fit()
    assert model["n"] > 0
    # unchanged archive: the memo hits — no reload, no refit
    calls = []
    real_load = profile_store.load
    monkeypatch.setattr(profile_store, "load",
                        lambda *a, **k: calls.append(1) or real_load(
                            *a, **k))
    records2, model2 = costmodel.cached_fit()
    assert not calls
    assert model2 is model and records2 is records
    # an append changes the stamp -> reload + refit
    profile_store.append(_scope_rec("conv", "runN", 99.0, 99.0,
                                    flops=1e12, hbm=1e9))
    _r3, model3 = costmodel.cached_fit()
    assert calls
    assert model3 is not model
    costmodel.reset_cache()


def test_archived_block_k_beats_heuristic(store):
    # measured: block_k=128 fastest among tiling candidates
    i = 0
    for bk, ms in ((512, 9.0), (256, 7.0), (128, 3.0), (48, 1.0)):
        for runi in range(2):
            profile_store.append(_scope_rec(
                "paged_decode_kernel", "r%d" % runi, ms, 10.0 + i,
                flops=1e9, hbm=1e9,
                sig="paged_decode_kernel||bk%d" % bk, block_k=bk))
            i += 1
    # 48 is fastest but does not divide 1024 with multiple=16 -> 128
    assert costmodel.archived_block_k(1024, multiple=16) == 128
    from mxnet_tpu.kernels import common as kcommon
    kcommon._BLOCK_CHOICE.clear()
    try:
        # the archive consult is scoped to the paged knob's callers...
        assert kcommon.choose_block_k(1024, shape_key=("test_arch",),
                                      multiple=16,
                                      env="MXNET_PAGED_BLOCK_K") == 128
        # ...a caller not keyed on it (flash_decode) keeps its static
        # heuristic — paged winners must not leak into its grid
        assert kcommon.choose_block_k(1024, shape_key=("test_arch2",),
                                      multiple=16) == 512
    finally:
        kcommon._BLOCK_CHOICE.clear()


def test_archived_block_k_needs_comparable_measurements(store):
    # a single measured candidate is not a comparison: keep the
    # heuristic rather than crowning an un-raced block_k
    profile_store.append(_scope_rec("paged_decode_kernel", "r0", 3.0,
                                    10.0, sig="paged_decode_kernel||a",
                                    block_k=128))
    assert costmodel.archived_block_k(1024, multiple=16) is None
    # flash_decode records don't honor MXNET_PAGED_BLOCK_K: excluded
    profile_store.append(_scope_rec("flash_decode", "r0", 1.0, 11.0,
                                    sig="flash_decode||a", block_k=256))
    assert costmodel.archived_block_k(1024, multiple=16) is None
    # a second candidate on the SAME workload signature makes the A/B
    profile_store.append(_scope_rec("paged_decode_kernel", "r1", 7.0,
                                    12.0, sig="paged_decode_kernel||b",
                                    block_k=256))
    assert costmodel.archived_block_k(1024, multiple=16) == 128


def test_choose_block_k_heuristic_unchanged_without_store(monkeypatch):
    monkeypatch.delenv("MXNET_OBS_PROFILE_DIR", raising=False)
    from mxnet_tpu.kernels import common as kcommon
    kcommon._BLOCK_CHOICE.clear()
    try:
        assert kcommon.choose_block_k(1024, shape_key=("test_off",)) \
            == 512
    finally:
        kcommon._BLOCK_CHOICE.clear()


# ------------------------------------------------- --history ----------

def _history_rc(store_dir, *extra):
    obs_regression = _load_tool("obs_regression")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = obs_regression.main(["--history", "--profile-dir",
                                  store_dir] + list(extra))
    return rc, buf.getvalue()


def test_history_flags_2x_slowdown_naming_scope(store):
    for runi, p50 in ((0, 5.0), (1, 5.2), (2, 10.4)):
        profile_store.append(_scope_rec("decode", "run%d" % runi, p50,
                                        10.0 + runi))
        profile_store.append(_scope_rec("steady", "run%d" % runi, 8.0,
                                        10.0 + runi))
    rc, out = _history_rc(store)
    assert rc == 1
    assert "decode" in out
    assert "steady" not in [ln.split()[0] for ln in out.splitlines()
                            if ln.startswith("  ")]


def test_history_boundary_exactly_at_tolerance_passes(store):
    # 50% default tolerance and a STRICT boundary: exactly 1.5x passes
    for runi, p50 in ((0, 4.0), (1, 6.0)):  # 6.0 == median(4.0) * 1.5
        profile_store.append(_scope_rec("decode", "run%d" % runi, p50,
                                        10.0 + runi))
    rc, out = _history_rc(store)
    assert rc == 0, out
    profile_store.append(_scope_rec("decode", "run2", 9.0, 12.5))
    rc, out = _history_rc(store)        # median(4, 6) = 6; 9.0 == 1.5x
    assert rc == 0, out
    # just past the boundary -> flagged
    profile_store.append(_scope_rec("decode", "run3", 9.02, 13.0))
    rc, out = _history_rc(store)        # median(4, 6, 9) = 6
    assert rc == 1
    assert "decode" in out
    # and a tighter CLI tolerance moves the boundary
    rc, _ = _history_rc(store, "--tol", "p50_ms=2.0")
    assert rc == 0


def test_history_single_run_is_not_an_error(store):
    profile_store.append(_scope_rec("decode", "run0", 5.0, 10.0))
    rc, out = _history_rc(store)
    assert rc == 0
    assert "need >= 2" in out


def test_history_without_archive_fails_loud(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_OBS_PROFILE_DIR", raising=False)
    obs_regression = _load_tool("obs_regression")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = obs_regression.main(["--history"])
    assert rc == 2


def test_history_respects_window(store, monkeypatch):
    # old slow epoch, then fast runs; window=2 must forget the slow era
    for runi, p50 in ((0, 20.0), (1, 4.0), (2, 4.0), (3, 8.5)):
        profile_store.append(_scope_rec("decode", "run%d" % runi, p50,
                                        10.0 + runi))
    rc, _ = _history_rc(store, "--window", "2")     # median(4,4)=4
    assert rc == 1                                  # 8.5 > 6.0
    rc, _ = _history_rc(store, "--window", "3")     # median(20,4,4)=4
    assert rc == 1


# ----------------------------------------- kernels-scope renames ------

def test_kernels_normalization_merges_renamed_scope():
    obs_regression = _load_tool("obs_regression")
    summ = {"totals": {"flops": 10}, "scopes": {
        "paged_decode_kernel_1": {"flops": 5, "hbm_bytes": 7},
        "other": {"flops": 5, "hbm_bytes": 1}}}
    norm, notes = obs_regression._normalize_scopes(summ)
    assert "paged_decode_kernel" in norm["scopes"]
    assert "paged_decode_kernel_1" not in norm["scopes"]
    assert any("normalized" in n for n in notes)
    # collision merges (two renamed copies sum onto one key)
    summ["scopes"]["paged_decode_kernel"] = {"flops": 2, "hbm_bytes": 1}
    norm, _ = obs_regression._normalize_scopes(summ)
    assert norm["scopes"]["paged_decode_kernel"]["flops"] == 7


# -------------------------------------------------- perf_timeline -----

def test_perf_timeline_renders_and_writes_json(store, tmp_path):
    for runi in range(3):
        profile_store.append(_scope_rec("decode", "run%d" % runi,
                                        5.0 + runi, 10.0 + runi))
        profile_store.append_bench("serving", value=100.0 + runi,
                                   unit="tok/s",
                                   metric="serving_goodput",
                                   run="run%d" % runi)
    out_json = str(tmp_path / "timeline.json")
    perf_timeline = _load_tool("perf_timeline")
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = perf_timeline.main(["--dir", store, "--json", out_json])
    out = buf.getvalue()
    assert rc == 0
    assert "3 run(s)" in out
    assert "decode" in out and "serving_goodput" in out
    doc = json.load(open(out_json))
    assert doc["runs"] == ["run0", "run1", "run2"]
    assert len(doc["scopes"][0]["points"]) == 3
    assert len(doc["bench"][0]["points"]) == 3


def test_perf_timeline_empty_and_missing_dir(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_OBS_PROFILE_DIR", raising=False)
    perf_timeline = _load_tool("perf_timeline")
    with contextlib.redirect_stdout(io.StringIO()):
        assert perf_timeline.main([]) == 2
        d = str(tmp_path / "empty")
        os.makedirs(d)
        assert perf_timeline.main(["--dir", d]) == 1


# ------------------------------------------------- off-path silence ---

def test_off_path_no_store_io(tmp_path, monkeypatch):
    monkeypatch.delenv("MXNET_OBS_PROFILE_DIR", raising=False)
    profile_store.reset()
    assert not profile_store.enabled()
    assert profile_store.store_dir() is None
    assert profile_store.append({"kind": "scope"}) is None
    assert profile_store.append_bench("leg", value=1.0) is None
    assert profile_store.record_run() == 0
    assert profile_store.prune() == 0
    # the bench helper is the same single guarded branch
    import sys
    sys.path.insert(0, ROOT)
    from benchmark.common import record_bench_profile
    assert record_bench_profile("leg", value=1.0) is None
    # and nothing appeared on disk anywhere under tmp
    assert list(tmp_path.iterdir()) == []


def test_dump_writes_store_only_when_enabled(store, monkeypatch,
                                             tmp_path):
    import mxnet_tpu as mx
    monkeypatch.setenv("MXNET_OBS", "1")
    core.set_enabled(True)
    core.reset()
    try:
        t0 = time.perf_counter_ns()
        core.record_span("phase.step", "phase", t0, t0 + 1_000_000)
        mx.profiler.set_config(filename=str(tmp_path / "t.json"),
                               xla_trace=False)
        mx.profiler.dump()
    finally:
        core.set_enabled(None)
        core.reset()
    loaded, _ = profile_store.load(store)
    assert any(r["scope"] == "phase.step" for r in loaded)
