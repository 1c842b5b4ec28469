"""Observability smoke: one instrumented train step, validated trace.

Run by the opt-in tier-1 lane (``TIER1_OBS=1 ci/tier1.sh``) and usable
standalone. With MXNET_OBS=1 it trains a 2-layer model for a couple of
steps, dumps the chrome-trace JSON through ``profiler.dump()``,
validates that the JSON parses and carries the four step-phase spans +
per-bucket collective counters, and prints the aggregate-stats table —
the ISSUE 2 acceptance path, exercised as a console one-liner:

    MXNET_OBS=1 JAX_PLATFORMS=cpu python tools/obs_smoke.py

``--ops`` runs the per-operator attribution half (ISSUE 4) instead:
the two-block conv+dense workload from ``tools/obs_ops.py`` trains a
couple of steps, and the emitted chrome trace must carry ``ops.*``
per-scope gauges naming the conv AND dense block scopes, with >=90% of
flops and HBM bytes attributed:

    MXNET_OBS=1 JAX_PLATFORMS=cpu python tools/obs_smoke.py --ops

``--nproc 2`` adds the distributed half (ISSUE 3): two gloo processes
each train against a ``dist_tpu_sync`` kvstore (which takes the
barrier-handshake clock anchor at creation), dump rank-local traces,
and the parent merges them with ``observability.merge_traces`` and
validates that the merged chrome trace carries BOTH rank lanes:

    MXNET_OBS=1 JAX_PLATFORMS=cpu python tools/obs_smoke.py --nproc 2

``--serving`` runs the serving half (ISSUEs 5 + 7 + 8): a pipelined
PAGED ContinuousBatcher serves a few requests while a live HTTP
endpoint is scraped mid-run, and the emitted trace must carry the full
request lifecycle — dispatch/sync/patch/prefill/queue-wait spans,
per-request flow chains, the TTFT/ITL/e2e/queue histograms (bucket
states included), the occupancy/goodput gauges AND the paged-pool
block gauges (kv_free_blocks / kv_block_utilization, which must also
appear in the mid-run /healthz snapshot — the router's load signal):

    MXNET_OBS=1 JAX_PLATFORMS=cpu python tools/obs_smoke.py --serving
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_OBS", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _train_steps(kvstore, steps=2):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1}, kvstore=kvstore)
    loss_fn = gluon.loss.L2Loss()
    x = mx.nd.random.uniform(shape=(8, 10))
    y = mx.nd.random.uniform(shape=(8, 4))
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)
    return mx


def single_process():
    import mxnet_tpu as mx
    # a store instance: the "device" string is no store on one worker
    _train_steps(kvstore=mx.kvstore.create("device"))
    fname = os.path.join(tempfile.mkdtemp(prefix="obs_smoke_"),
                         "trace.json")
    mx.profiler.set_config(filename=fname, xla_trace=False)
    path = mx.profiler.dump()
    with open(path) as f:
        trace = json.load(f)           # must PARSE — the lane's gate
    names = {e["name"] for e in trace["traceEvents"]}
    required = {"forward", "backward", "allreduce", "update",
                "kvstore.bucket", "kvstore.collectives"}
    missing = required - names
    if missing:
        print("[obs_smoke] FAIL: trace missing spans/counters: %s"
              % sorted(missing))
        return 1
    print("[obs_smoke] trace OK: %d events, %d distinct names -> %s"
          % (len(trace["traceEvents"]), len(names), path))
    print(mx.profiler.dumps(aggregate=True))
    return 0


def ops_smoke():
    """--ops: block-level scopes must survive jit into the emitted
    trace (ops.* per-scope gauges) and attribution must cover >=90%
    of the compiled step's flops and HBM bytes."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "obs_ops", os.path.join(ROOT, "tools", "obs_ops.py"))
    obs_ops = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(obs_ops)

    summ = obs_ops.run_workload()
    t = summ["totals"]
    if not t.get("programs"):
        print("[obs_smoke] FAIL: no compiled program registered")
        return 1
    for metric, attr in (("flops", "attributed_flops"),
                         ("hbm_bytes", "attributed_hbm_bytes")):
        if t[attr] < 0.9 * t[metric]:
            print("[obs_smoke] FAIL: only %.1f%% of %s attributed"
                  % (100.0 * t[attr] / max(t[metric], 1e-9), metric))
            return 1

    import mxnet_tpu as mx
    fname = os.path.join(tempfile.mkdtemp(prefix="obs_smoke_ops_"),
                         "trace.json")
    mx.profiler.set_config(filename=fname, xla_trace=False)
    path = mx.profiler.dump()
    with open(path) as f:
        trace = json.load(f)
    ops_names = {e["name"] for e in trace["traceEvents"]
                 if e["name"].startswith("ops.")}
    for block in ("conv", "dense"):
        if not any(block in n for n in ops_names):
            print("[obs_smoke] FAIL: no ops.* gauge names the %s "
                  "block; ops names: %s" % (block, sorted(ops_names)))
            return 1
    table = mx.profiler.dumps(aggregate=True)
    if "Per-operator attribution" not in table:
        print("[obs_smoke] FAIL: aggregate table lacks the "
              "attribution section")
        return 1
    print("[obs_smoke] ops OK: %d ops.* gauges, %.1f%% flops / %.1f%% "
          "bytes attributed -> %s"
          % (len(ops_names), 100.0 * t["attributed_flops"] / t["flops"],
             100.0 * t["attributed_hbm_bytes"] / t["hbm_bytes"], path))
    print(table)
    return 0


def serving_smoke():
    """--serving: a pipelined, SPECULATIVE ContinuousBatcher run under
    churn must land the request lifecycle in the emitted chrome trace —
    dispatch/sync/patch/prefill/queue-wait spans, serving.request flow
    events tying admit->syncs->finish per rid, the bounded-memory
    TTFT/ITL/e2e/queue histograms (events + mergeable bucket states),
    the occupancy/goodput gauges, the spec acceptance histogram/gauge —
    and the MXNET_OBS_HTTP-style live endpoint must answer a /metrics +
    /healthz scrape MID-RUN (acceptance ratio included)."""
    import urllib.request

    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.observability import http as obs_http

    cfg = tf.TransformerConfig(vocab_size=97, d_model=16, n_heads=2,
                               n_layers=1, d_ff=32, max_len=48,
                               dtype=jnp.float32)
    params = tf.init_params(cfg, seed=0)
    rng = np.random.RandomState(0)
    jobs = [(list(rng.randint(1, 97, 5)), 6) for _ in range(4)]
    srv = ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=2,
                            paged=True, block_size=8, spec_k=2)

    port = obs_http.start(0)       # ephemeral port; env-free smoke
    scraped = {"metrics": None, "healthz": None}
    results = {}
    try:
        for n_done, (rid, tok, done) in enumerate(srv.stream(jobs)):
            if done:
                results[rid] = True
            if n_done == 8 and scraped["metrics"] is None:
                # mid-run: lanes busy, chunks in flight
                base = "http://127.0.0.1:%d" % port
                scraped["metrics"] = urllib.request.urlopen(
                    base + "/metrics", timeout=10).read().decode()
                scraped["healthz"] = json.loads(urllib.request.urlopen(
                    base + "/healthz", timeout=10).read().decode())
    finally:
        obs_http.stop()
    if len(results) != len(jobs):
        print("[obs_smoke] FAIL: serving pool lost requests")
        return 1
    if not scraped["metrics"] \
            or "mxnet_obs_hist" not in scraped["metrics"] \
            or 'name="serving_ttft_ms"' not in scraped["metrics"]:
        print("[obs_smoke] FAIL: live /metrics scrape lacks serving "
              "histograms")
        return 1
    hz = scraped["healthz"]
    needed_hz = ("serving.lane_occupancy", "serving.kv_free_blocks",
                 "serving.kv_block_utilization",
                 "serving.spec_draft_ratio")
    if not hz or hz.get("status") != "ok" \
            or any(k not in hz.get("counters", {}) for k in needed_hz):
        print("[obs_smoke] FAIL: /healthz snapshot incomplete (need "
              "%s): %s" % (list(needed_hz),
                           sorted((hz or {}).get("counters", {}))))
        return 1
    if not 0.0 < hz["counters"]["serving.kv_block_utilization"] <= 1.0:
        print("[obs_smoke] FAIL: mid-run block utilization %s not in "
              "(0, 1]" % hz["counters"]["serving.kv_block_utilization"])
        return 1

    # ---- overload telemetry (ISSUE 12): a tiny priority storm on a
    # 2-replica fleet must export the brownout rung, the per-replica
    # breaker state, the preemption counter + stall histogram, and
    # the shed-vs-expired split — on /healthz AND in the trace
    from mxnet_tpu.models.router import ReplicaRouter
    from mxnet_tpu.observability import core as obs_core
    from mxnet_tpu.observability import events as obs_events
    from mxnet_tpu.observability import timeseries as obs_ts

    pre0 = obs_core.counter("serving.preemptions").value
    rng2 = np.random.RandomState(3)
    rr = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=3,
                             breaker=True, paged=True, block_size=8,
                             num_blocks=5, brownout=True,
                             brownout_trip=1)
    for _ in range(4):                 # pin every usable block
        rr.submit(list(rng2.randint(1, 97, 4)), 10, priority=0)
    for _ in range(6):
        rr.step()
    rr.submit(list(rng2.randint(1, 97, 4)), 6, priority=1)  # preempts
    rr.submit(list(rng2.randint(1, 97, 4)), 6, priority=0,
              deadline_ms=0)                                # expires
    hz2, steps = None, 0
    port = obs_http.start(0)
    try:
        while (rr._queue or rr._live) and steps < 200:
            rr.step()
            obs_ts.tick()      # deterministic mid-run sample points
            if steps == 1:
                hz2 = json.loads(urllib.request.urlopen(
                    "http://127.0.0.1:%d/healthz" % port,
                    timeout=10).read().decode())
            steps += 1
    finally:
        obs_http.stop()
    if steps >= 200:
        print("[obs_smoke] FAIL: overload act did not quiesce")
        return 1
    if obs_core.counter("serving.preemptions").value - pre0 < 1 \
            or not rr.expired_rids:
        print("[obs_smoke] FAIL: overload act drove no preemption "
              "or no deadline expiry")
        return 1
    needed_hz2 = ("serving.preemptions", "serving.brownout_rung",
                  "serving.slo_violation.expired",
                  "router.replica_state.r0",
                  "router.replica_state.r1")
    missing_hz2 = [k for k in needed_hz2
                   if k not in (hz2 or {}).get("counters", {})]
    if missing_hz2:
        print("[obs_smoke] FAIL: /healthz lacks the overload gauges "
              "%s" % missing_hz2)
        return 1
    for k in ("serving.slo_violation.shed",
              "serving.slo_violation.expired",
              "router.replica_state.r0"):
        if k not in rr.health_snapshot():
            print("[obs_smoke] FAIL: router health_snapshot() lacks "
                  "%s" % k)
            return 1

    # ---- flight-recorder telemetry (ISSUE 17): the sampler must have
    # a mid-run window with the serving counters in it, and every
    # admission must have left a decision event in the ring
    win = obs_ts.last_window()
    if win["ticks"] < 1 \
            or "serving.preemptions" not in win["series"] \
            or "rate_per_s" not in win["series"]["serving.preemptions"]:
        print("[obs_smoke] FAIL: no mid-run time-series window "
              "(ticks=%d, series=%d)"
              % (win["ticks"], len(win["series"])))
        return 1
    if not obs_ts.running():
        print("[obs_smoke] FAIL: time-series sampler daemon not "
              "running under a live batcher")
        return 1
    admitted_ev = {f.get("rid")
                   for _t, kind, f in obs_events.recent(10000)
                   if kind == "admit"}
    # 6 submissions in the act; each one either got an admit event,
    # was shed, or expired — the decision ring narrates all of them
    expected = 6 - len(rr.shed_rids) - len(rr.expired_rids)
    if len(admitted_ev) < expected:
        print("[obs_smoke] FAIL: %d admissions but only %d admit "
              "decision events" % (expected, len(admitted_ev)))
        return 1
    ev_counts = obs_events.counts()
    for kind in ("admit", "preempt", "expire"):
        if not ev_counts.get(kind):
            print("[obs_smoke] FAIL: no '%s' decision event recorded "
                  "(kinds: %s)" % (kind, sorted(ev_counts)))
            return 1

    fname = os.path.join(tempfile.mkdtemp(prefix="obs_smoke_srv_"),
                         "trace.json")
    mx.profiler.set_config(filename=fname, xla_trace=False)
    path = mx.profiler.dump()
    with open(path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    required = {"serving.dispatch", "serving.sync", "serving.patch",
                "serving.prefill", "serving.queue_wait",
                "serving.finish", "serving.request",
                "serving.inflight_depth", "serving.lane_occupancy",
                "serving.kv_utilization", "serving.goodput_tok_s",
                "serving.kv_free_blocks",
                "serving.kv_block_utilization",
                "serving.spec_accept_len", "serving.spec_draft_ratio",
                "serving.ttft_ms", "serving.itl_ms", "serving.e2e_ms",
                "serving.preempt", "serving.preempt_stall_ms",
                "serving.brownout_rung", "router.queue_depth",
                "router.replica_state.r0", "router.replica_state.r1"}
    missing = required - names
    if missing:
        print("[obs_smoke] FAIL: serving trace missing: %s"
              % sorted(missing))
        return 1
    # every request's flow chain must be complete: one start, >=1
    # step, one finish per rid
    flows = {}
    for e in trace["traceEvents"]:
        if e["name"] == "serving.request" and e["ph"] in "stf":
            flows.setdefault(e["id"], set()).add(e["ph"])
    bad = [rid for rid, phs in flows.items() if phs != {"s", "t", "f"}]
    if len(flows) != len(jobs) or bad:
        print("[obs_smoke] FAIL: request flow chains incomplete "
              "(%d chains, broken: %s)" % (len(flows), bad))
        return 1
    hists = trace["otherData"].get("histograms", {})
    for hname in ("serving.ttft_ms", "serving.itl_ms",
                  "serving.e2e_ms", "serving.queue_ms",
                  "serving.spec_accept_len",
                  "serving.preempt_stall_ms"):
        if not hists.get(hname, {}).get("count"):
            print("[obs_smoke] FAIL: histogram %s missing/empty in "
                  "trace otherData" % hname)
            return 1
    table = mx.profiler.dumps(aggregate=True)
    if "Histograms" not in table or "serving.ttft_ms" not in table:
        print("[obs_smoke] FAIL: aggregate table lacks the serving "
              "histogram section")
        return 1
    print("[obs_smoke] serving trace OK: %d events, %d request flow "
          "chains, %d histograms, live scrape on :%d -> %s"
          % (len(trace["traceEvents"]), len(flows), len(hists), port,
             path))
    return 0


def goodput_smoke():
    """--goodput: the whole-run wall-clock ledger (ISSUE 19). A
    deterministic single-rank run with one injected stall per badput
    class — a chaos ``delay`` at io.read inside a real DataIter
    io.next, a detector-narrated recompile, committed step work and a
    checkpoint span — must come back from ``compute_ledger`` with
    >=95% of the wall attributed and every injected category within
    20% of its injected duration, and ``tools/obs_goodput.py --check``
    must pass on the dumped chrome trace."""
    import time as _time

    from mxnet_tpu import io as mio
    from mxnet_tpu.observability import chaos, core, export, goodput
    from mxnet_tpu.observability import recompile

    core.set_enabled(True)
    core.reset()
    chaos.reset()
    goodput.reset()
    try:
        # a compile the detector narrates: its [ts - duration, ts]
        # interval extends the window backwards, before the first span
        recompile.get_detector()._push("trace", "goodput_smoke",
                                       "sig(smoke)", 0.04)

        class OneBatch(mio.DataIter):
            def __init__(self):
                super().__init__(batch_size=1)
                self._left = 1

            def iter_next(self):
                self._left -= 1
                return self._left >= 0

            def getdata(self):
                chaos.fire("io.read", path="goodput_smoke")
                return []

            def getlabel(self):
                return []

            def getpad(self):
                return 0

        # the sleep can overshoot badly on a loaded 1-core host, so
        # the tolerance is against the MEASURED stall (what the ledger
        # must reproduce), floored by the injected 50 ms
        chaos.inject("io.read", "delay", ms=50)
        t0 = _time.perf_counter()
        OneBatch().next()
        stall_ms = (_time.perf_counter() - t0) * 1e3
        chaos.reset()

        # committed work + a checkpoint, deterministic durations
        t = _time.perf_counter_ns()
        core.record_span("trainer.step", "step", t, t + 100 * 10**6)
        core.record_span("checkpoint.save", "checkpoint",
                         t + 100 * 10**6, t + 130 * 10**6)

        led = goodput.compute_ledger()
        for line in goodput.format_table(led):
            print(line)
        coverage = 1.0 - led["untracked_fraction"]
        if coverage < 0.95:
            print("[obs_smoke] FAIL: ledger attributes only %.1f%% of "
                  "the wall" % (100.0 * coverage))
            return 1
        if stall_ms < 50.0:
            print("[obs_smoke] FAIL: injected 50 ms delay measured "
                  "only %.1f ms" % stall_ms)
            return 1
        expect = (("recompile", 40.0), ("data_stall", stall_ms),
                  ("checkpoint", 30.0))
        for cat, want in expect:
            got = led["badput_ms"][cat]
            if abs(got - want) > 0.20 * want:
                print("[obs_smoke] FAIL: %s %.1f ms not within 20%% "
                      "of the injected %.1f ms" % (cat, got, want))
                return 1
        if abs(led["goodput_ms"] - 100.0) > 20.0 \
                or led["steps"]["committed"] != 1:
            print("[obs_smoke] FAIL: goodput %.1f ms / %d committed "
                  "steps (expected 100 ms / 1)"
                  % (led["goodput_ms"], led["steps"]["committed"]))
            return 1
        text = export.prometheus_text()
        if "mxnet_obs_goodput_fraction" not in text \
                or 'mxnet_obs_badput_ms{category="data_stall"}' \
                not in text:
            print("[obs_smoke] FAIL: prometheus export lacks the "
                  "goodput series")
            return 1

        # the CLI gate on the dumped trace (what CI runs on artifacts)
        import importlib.util
        fname = os.path.join(tempfile.mkdtemp(prefix="obs_goodput_"),
                             "trace.json")
        export.dump_chrome_trace(fname)
        spec = importlib.util.spec_from_file_location(
            "obs_goodput", os.path.join(ROOT, "tools",
                                        "obs_goodput.py"))
        obs_goodput = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(obs_goodput)
        rc = obs_goodput.main([fname, "--check"])
        if rc != 0:
            print("[obs_smoke] FAIL: obs_goodput --check rc=%d on the "
                  "dumped trace" % rc)
            return 1
        print("[obs_smoke] goodput OK: %.1f%% of %.1f ms wall "
              "attributed, all injected categories within 20%% -> %s"
              % (100.0 * coverage, led["wall_ms"], fname))
        return 0
    finally:
        chaos.reset()
        core.reset()
        core.set_enabled(None)


def worker():
    """One rank of the --nproc job (re-entered via tools/launch.py)."""
    from mxnet_tpu import parallel
    parallel.init_distributed()
    import jax
    mx = _train_steps(kvstore="dist_tpu_sync")
    out = os.path.join(os.environ["OBS_SMOKE_DIR"], "trace.json")
    mx.profiler.set_config(filename=out, xla_trace=False)
    path = mx.profiler.dump()
    print("OBS-SMOKE-RANK-OK", jax.process_index(), path)
    return 0


def orchestrate(nproc, goodput_check=False):
    """Launch the gloo workers, then merge + validate the rank lanes.
    With ``goodput_check`` the merged trace must also yield a
    cross-rank critical-path table naming a real rank+phase (ISSUE
    19)."""
    outdir = tempfile.mkdtemp(prefix="obs_smoke_mp_")
    env = dict(os.environ)
    env.update({"OBS_SMOKE_WORKER": "1", "OBS_SMOKE_DIR": outdir,
                "MXNET_OBS": "1", "MXNET_OBS_SKEW_EVERY": "1",
                "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", str(nproc), "--launcher", "local",
         sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    sys.stdout.write(r.stdout)
    sys.stderr.write(r.stderr)
    if r.returncode != 0:
        print("[obs_smoke] FAIL: worker launch rc=%d" % r.returncode)
        return 1
    if r.stdout.count("OBS-SMOKE-RANK-OK") != nproc:
        print("[obs_smoke] FAIL: expected %d rank markers" % nproc)
        return 1

    from mxnet_tpu.observability import dist
    base = os.path.join(outdir, "trace.json")
    inputs = dist.find_rank_traces(base)
    if len(inputs) != nproc:
        print("[obs_smoke] FAIL: expected %d rank-local traces, found "
              "%s" % (nproc, inputs))
        return 1
    merged = dist.merge_traces(base, out=os.path.join(outdir,
                                                      "merged.json"))
    lanes = {e.get("pid") for e in merged["traceEvents"]
             if e.get("ph") == "X"}
    if lanes != set(range(nproc)):
        print("[obs_smoke] FAIL: merged trace lanes %s != ranks 0..%d"
              % (sorted(lanes), nproc - 1))
        return 1
    unaligned = merged["otherData"]["unaligned_ranks"]
    if unaligned:
        print("[obs_smoke] FAIL: ranks %s merged without a clock "
              "anchor" % unaligned)
        return 1
    # the merged trace must carry BUCKET-WISE merged histograms: each
    # rank's trainer.step_ms counts sum into the fleet distribution
    rank_counts = []
    for p in inputs:
        with open(p) as f:
            other = json.load(f).get("otherData", {})
        rank_counts.append(other.get("histograms", {})
                           .get("trainer.step_ms", {}).get("count", 0))
    merged_hist = merged["otherData"].get("histograms", {}) \
        .get("trainer.step_ms", {})
    if not all(rank_counts) \
            or merged_hist.get("count") != sum(rank_counts):
        print("[obs_smoke] FAIL: merged trainer.step_ms histogram "
              "count %s != per-rank counts %s summed"
              % (merged_hist.get("count"), rank_counts))
        return 1
    print("[obs_smoke] merged trace OK: %d ranks, %d events, clock "
          "offsets %s, trainer.step_ms histogram %s=%d -> %s"
          % (nproc, len(merged["traceEvents"]),
             merged["otherData"]["clock_offsets_us"],
             "+".join(str(c) for c in rank_counts),
             merged_hist.get("count", 0),
             os.path.join(outdir, "merged.json")))
    if goodput_check:
        from mxnet_tpu.observability import goodput as _goodput
        events = _goodput.events_from_trace(merged)
        cp = _goodput.critical_path(events)
        if not cp or not cp.get("bound"):
            print("[obs_smoke] FAIL: merged %d-rank trace yields no "
                  "critical-path attribution" % nproc)
            return 1
        top = cp["bound"][0]
        if top["rank"] not in range(nproc) \
                or top["phase"] not in ("forward", "backward",
                                        "allreduce", "update"):
            print("[obs_smoke] FAIL: critical path names rank=%r "
                  "phase=%r" % (top["rank"], top["phase"]))
            return 1
        for line in _goodput.format_table(
                _goodput.compute_ledger(events), cp):
            print(line)
        print("[obs_smoke] critical path OK: step bound by rank %d "
              "%s (%.1f%%) across %d steps"
              % (top["rank"], top["phase"], 100.0 * top["fraction"],
                 cp["steps"]))
    return 0


def store_smoke():
    """--store: the performance-archive smoke (ISSUE 18). Two synthetic
    runs of the same workload — deterministic injected span durations,
    the second run 2x slower on one scope — must land in ONE merged
    timeline (``tools/perf_timeline.py`` renders both runs), and
    ``obs_regression --history`` must flag the slowed scope by name
    while leaving the steady scope alone."""
    import contextlib
    import importlib.util
    import io
    import shutil
    import time as _time

    from mxnet_tpu.observability import core, profile_store

    def load_tool(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(os.path.dirname(
                os.path.abspath(__file__)), "%s.py" % name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    d = tempfile.mkdtemp(prefix="obs_store_smoke_")
    saved = {k: os.environ.get(k) for k in
             ("MXNET_OBS_PROFILE_DIR", "MXNET_OBS_PROFILE_RUN")}
    os.environ["MXNET_OBS_PROFILE_DIR"] = d
    try:
        t0 = _time.perf_counter_ns()
        # run1: decode 5ms, steady 8ms; run2: decode 10ms (the
        # injected 2x slowdown), steady 8ms — synthetic spans through
        # the REAL ring + record_run() write path
        for run, decode_ms in (("run1", 5.0), ("run2", 10.0)):
            os.environ["MXNET_OBS_PROFILE_RUN"] = run
            core.set_enabled(True)
            core.reset()
            for _ in range(3):
                core.record_span("smoke.decode", "phase", t0,
                                 t0 + int(decode_ms * 1e6))
                core.record_span("smoke.steady", "phase", t0,
                                 t0 + int(8.0 * 1e6))
            if not profile_store.record_run():
                print("[obs_smoke] FAIL: record_run wrote nothing")
                return 1
        records, evidence = profile_store.load(d)
        if evidence:
            print("[obs_smoke] FAIL: fresh archive has corruption "
                  "evidence: %s" % evidence)
            return 1
        groups = profile_store.merge_by_signature(records)
        decode = next((g for g in groups.values()
                       if g["scope"] == "smoke.decode"), None)
        if decode is None or decode["runs"] != ["run1", "run2"]:
            print("[obs_smoke] FAIL: two runs did not merge into one "
                  "timeline: %s" % (decode and decode["runs"]))
            return 1

        perf_timeline = load_tool("perf_timeline")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = perf_timeline.main(["--dir", d, "--json",
                                     os.path.join(d, "timeline.json")])
        out = buf.getvalue()
        if rc != 0 or "2 run(s)" not in out \
                or "smoke.decode" not in out:
            print(out)
            print("[obs_smoke] FAIL: perf_timeline did not render "
                  "both runs (rc=%d)" % rc)
            return 1

        obs_regression = load_tool("obs_regression")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = obs_regression.main(["--history", "--profile-dir", d])
        out = buf.getvalue()
        if rc != 1 or "smoke.decode" not in out:
            print(out)
            print("[obs_smoke] FAIL: --history missed the injected 2x "
                  "slowdown (rc=%d)" % rc)
            return 1
        if "smoke.steady" in out:
            print(out)
            print("[obs_smoke] FAIL: --history flagged the steady "
                  "scope")
            return 1
        print("[obs_smoke] store OK: %d records, 2 runs merged, "
              "perf_timeline rendered, --history flagged smoke.decode "
              "2x drift" % len(records))
        return 0
    finally:
        core.set_enabled(None)
        core.reset()
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        profile_store.reset()
        shutil.rmtree(d, ignore_errors=True)


def main():
    print("[obs_smoke] CPU structure check by design: JAX_PLATFORMS=%s "
          "(pinned by this script when unset); counts, bytes and "
          "orderings only — no time or rate below is a device number"
          % os.environ["JAX_PLATFORMS"], flush=True)
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--nproc", type=int, default=1,
                   help="launch N gloo processes and validate the "
                        "merged per-rank trace (default: single "
                        "process)")
    p.add_argument("--ops", action="store_true",
                   help="run the per-operator attribution smoke "
                        "instead: block scopes must appear in the "
                        "emitted trace with >=90%% cost attribution")
    p.add_argument("--serving", action="store_true",
                   help="run the serving smoke instead: a pipelined "
                        "ContinuousBatcher step's dispatch/sync/patch "
                        "spans and depth/occupancy gauges must reach "
                        "the emitted trace")
    p.add_argument("--store", action="store_true",
                   help="run the performance-archive smoke instead: "
                        "two synthetic runs must merge into one "
                        "timeline and --history must flag an injected "
                        "2x slowdown")
    p.add_argument("--goodput", action="store_true",
                   help="run the goodput-ledger smoke instead: a "
                        "deterministic injected-stall run must have "
                        ">=95%% of its wall attributed with every "
                        "category within 20%%; with --nproc 2 the "
                        "merged trace's critical path must name a "
                        "rank+phase")
    args = p.parse_args()
    if os.environ.get("OBS_SMOKE_WORKER"):
        return worker()
    if args.goodput:
        if args.nproc > 1:
            return orchestrate(args.nproc, goodput_check=True)
        return goodput_smoke()
    if args.store:
        return store_smoke()
    if args.serving:
        return serving_smoke()
    if args.ops:
        return ops_smoke()
    if args.nproc > 1:
        return orchestrate(args.nproc)
    return single_process()


if __name__ == "__main__":
    sys.exit(main())
