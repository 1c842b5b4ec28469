"""Chaos smoke: one injected fault per class, recovery asserted.

Run by the opt-in tier-1 lane (``TIER1_CHAOS=1 ci/tier1.sh``) and
usable standalone:

    MXNET_OBS=1 JAX_PLATFORMS=cpu python tools/chaos_smoke.py

Every fault class from docs/ROBUSTNESS.md gets one scenario, and each
scenario asserts BOTH halves of the loop — the fault fired (chaos
stats / post-mortem artifact) and the system recovered (weights
intact, stream bit-exact, checkpoint loadable, resume bit-exact):

  nan      trainer step guard skips the poisoned update; weights
           bit-identical, chaos.skipped_steps counted
  ioerror  record iterator retries two injected read failures and
           still delivers every batch
  serving  an injected dispatch failure frees the lanes and requeues;
           greedy streams match solo generate() bit-exactly
  hang     (subprocess) a hung collective under
           MXNET_OBS_WATCHDOG_ACTION=checkpoint dumps a post-mortem,
           commits an emergency checkpoint, aborts with exit 43 — and
           that checkpoint restores
  sigterm  (subprocess) an injected preemption triggers the emergency
           SIGTERM save; exit 143, checkpoint at the preempted step
  crash    (subprocesses) an injected hard crash mid-run, then a
           relaunch via resume_from_latest: the concatenated loss
           trajectory is bit-exact (float hex) vs an uninterrupted run

Five scenarios run as their own tier-1 lane invocations:
``--elastic`` (the 2-process shrink/regrow chain), ``--overload``
(the ISSUE 12 serving overload storm: mixed-priority burst at ~4x
block capacity, one replica chaos-killed mid-storm, recovery through
the circuit breaker's HALF_OPEN canary), ``--integrity`` (the
silent-corruption defense: one injected flip per corruption class —
gradient bucket, replicated weight on one rank, checkpoint byte,
recordio record — each detected with named evidence AND recovered
from a verified state), and ``--oom`` (the ISSUE 14 memory-pressure
closure: one injected RESOURCE_EXHAUSTED per recovery path —
trainer accum re-lower with the global-batch trajectory preserved,
serving pool shrink-and-retry with bit-exact streams, pool-grow
degradation, checkpoint snapshot serial retry — no process death),
and ``--durable`` (the ISSUE 15 durable-serving closure: a kill -9 at
a journal commit point replayed bit-exactly by ``recover()``, torn and
CRC-corrupt records skipped with named evidence, a chaos-failed canary
rolling the fleet back to the prior verified fingerprint with zero
dropped requests, and a lineage-gated hot-swap refusing unverified
weights).
"""

import argparse
import os
import subprocess
import sys
import tempfile

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_OBS", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _tiny_cfg():
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    return T.TransformerConfig(vocab_size=41, d_model=16, n_heads=2,
                               n_layers=1, d_ff=32, max_len=32,
                               dtype=jnp.float32)


def _flight_dir(label):
    """Point the flight recorder's incident sideband at a fresh
    per-leg directory (inherited by subprocess workers through the
    environment) so the leg can assert on exactly its own bundles."""
    d = tempfile.mkdtemp(prefix="chaos_flight_%s_" % label)
    os.environ["MXNET_OBS_FLIGHT_DIR"] = d
    return d


def _assert_incident(d, cause_prefix, label):
    """Every fault class must leave a PARSEABLE incident bundle whose
    cause names the injected fault (ISSUE 17). Returns 1 (leg FAIL)
    when no bundle under ``d`` matches ``cause_prefix``; a no-op when
    telemetry is off (standalone runs without MXNET_OBS)."""
    from mxnet_tpu.observability import core as obs_core
    from mxnet_tpu.observability import flight
    if not obs_core.enabled():
        return 0
    causes = []
    for p in flight.list_bundles(d):
        try:
            doc = flight.read_bundle(p)
        except flight.BundleError as e:
            print("[chaos_smoke] FAIL(%s): unreadable incident "
                  "bundle %s (%s)" % (label, p, e.evidence))
            return 1
        causes.append(doc.get("cause", ""))
        if causes[-1].startswith(cause_prefix):
            print("[chaos_smoke] %s incident bundle OK: cause=%s "
                  "taxonomy=%s (%s)"
                  % (label, doc["cause"], doc.get("taxonomy"),
                     os.path.basename(p)))
            return 0
    print("[chaos_smoke] FAIL(%s): no incident bundle with cause "
          "%s* under %s (saw: %s)" % (label, cause_prefix, d, causes))
    return 1


# ------------------------------------------------------------ scenarios --

def nan_guard():
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.observability import chaos

    os.environ["MXNET_STEP_GUARD"] = "1"
    chaos.reset()
    fdir = _flight_dir("nan")
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(2))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=mx.kvstore.create("device"))
    loss_fn = gluon.loss.L2Loss()
    x = mx.nd.random.uniform(shape=(4, 6))
    y = mx.nd.random.uniform(shape=(4, 2))

    def step():
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)

    step()
    before = {k: v.data().asnumpy().copy()
              for k, v in net.collect_params().items()}
    chaos.inject("trainer.grads", "nan", at=0)
    step()                                    # poisoned -> skipped
    after = {k: v.data().asnumpy().copy()
             for k, v in net.collect_params().items()}
    for k in before:
        if not np.array_equal(before[k], after[k]):
            print("[chaos_smoke] FAIL(nan): weights moved on a "
                  "poisoned step (%s)" % k)
            return 1
    if chaos.stats["skipped_steps"] != 1:
        print("[chaos_smoke] FAIL(nan): skipped_steps=%r"
              % chaos.stats["skipped_steps"])
        return 1
    step()                                    # rule exhausted: resumes
    resumed = {k: v.data().asnumpy().copy()
               for k, v in net.collect_params().items()}
    if all(np.array_equal(before[k], resumed[k]) for k in before):
        print("[chaos_smoke] FAIL(nan): training did not resume")
        return 1
    chaos.reset()
    if _assert_incident(fdir, "chaos.nan", "nan"):
        return 1
    print("[chaos_smoke] nan OK: poisoned step skipped, weights "
          "bit-identical, training resumed")
    return 0


def ioerror():
    import numpy as np
    from mxnet_tpu import io as mx_io, recordio
    from mxnet_tpu.observability import chaos

    chaos.reset()
    fdir = _flight_dir("ioerror")
    os.environ["MXNET_IO_BACKOFF_MS"] = "1"
    d = tempfile.mkdtemp(prefix="chaos_smoke_io_")
    path, idx = os.path.join(d, "img.rec"), os.path.join(d, "img.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    rng = np.random.RandomState(0)
    for i in range(8):
        img = rng.randint(0, 255, (8, 8, 3)).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".npy"))
    w.close()
    chaos.inject("io.read", "error", count=2)
    it = mx_io.ImageRecordIter(path_imgrec=path, path_imgidx=idx,
                               data_shape=(3, 8, 8), batch_size=4)
    batches = list(it)
    if len(batches) != 2 or chaos.stats["error"] != 2:
        print("[chaos_smoke] FAIL(ioerror): batches=%d injected=%d"
              % (len(batches), chaos.stats["error"]))
        return 1
    chaos.reset()
    if _assert_incident(fdir, "chaos.error", "ioerror"):
        return 1
    print("[chaos_smoke] ioerror OK: 2 injected read failures retried, "
          "full epoch delivered")
    return 0


def serving():
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.observability import chaos

    chaos.reset()
    fdir = _flight_dir("serving")
    cfg = _tiny_cfg()
    params = T.init_params(cfg, seed=0)
    rng = np.random.RandomState(0)
    jobs = [(list(rng.randint(1, 41, 4)), 6) for _ in range(3)]
    solo = [np.asarray(T.generate(params,
                                  jnp.asarray([p], jnp.int32), n, cfg,
                                  greedy=True))[0].tolist()
            for p, n in jobs]
    chaos.inject("serving.dispatch", "error", at=1)
    srv = ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=2)
    results, order = srv.run(jobs)
    if len(results) != len(jobs) or chaos.stats["error"] != 1:
        print("[chaos_smoke] FAIL(serving): results=%d injected=%d"
              % (len(results), chaos.stats["error"]))
        return 1
    for j, rid in enumerate(order):
        if results[rid] != solo[j]:
            print("[chaos_smoke] FAIL(serving): stream %d diverged "
                  "after requeue" % j)
            return 1
    chaos.reset()
    if _assert_incident(fdir, "chaos.error", "serving"):
        return 1
    print("[chaos_smoke] serving OK: dispatch failure requeued, all "
          "streams bit-exact vs solo generate()")
    return 0


def hang_worker(ckdir):
    """Subprocess body: one collective hangs; the watchdog must
    post-mortem, emergency-checkpoint, and abort(43)."""
    import mxnet_tpu as mx
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models.checkpoint import install_emergency_checkpoint

    cfg = _tiny_cfg()
    params = T.init_params(cfg, seed=0)
    install_emergency_checkpoint(
        ckdir, lambda: {"cfg": cfg, "params": params, "step": 7},
        on_sigterm=False)
    kv = mx.kvstore.create("device")
    kv.init(0, mx.nd.ones((8,)))
    kv.push(0, mx.nd.ones((8,)))     # chaos hangs HERE; watchdog fires
    print("UNREACHABLE", flush=True)
    return 1


def hang():
    from mxnet_tpu.observability import watchdog as wd
    from mxnet_tpu.models.checkpoint import load_checkpoint

    d = tempfile.mkdtemp(prefix="chaos_smoke_hang_")
    ckdir = os.path.join(d, "ck")
    sideband = os.path.join(d, "wd")
    fdir = _flight_dir("hang")
    env = dict(os.environ)
    env.update({
        "MXNET_OBS": "1",
        "MXNET_OBS_COLLECTIVE_TIMEOUT": "0.5",
        "MXNET_OBS_WATCHDOG_ACTION": "checkpoint",
        "MXNET_OBS_WATCHDOG_DIR": sideband,
        "MXNET_CHAOS": "kvstore.push:hang:ms=60000",
        "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
        "CHAOS_SMOKE_WORKER": "hang",
    })
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), ckdir],
        capture_output=True, text=True, timeout=300, env=env)
    if r.returncode != wd.ABORT_EXIT_CODE or "UNREACHABLE" in r.stdout:
        print("[chaos_smoke] FAIL(hang): rc=%d\n%s\n%s"
              % (r.returncode, r.stdout, r.stderr))
        return 1
    pm = os.path.join(sideband, "postmortem.rank0.txt")
    if not os.path.exists(pm):
        print("[chaos_smoke] FAIL(hang): no post-mortem artifact at %s"
              % pm)
        return 1
    with open(pm) as f:
        report = f.read()
    if "kvstore.push" not in report:
        print("[chaos_smoke] FAIL(hang): post-mortem does not name "
              "the collective:\n%s" % report)
        return 1
    _, _, _, step, meta = load_checkpoint(ckdir)
    if step != 7 or not str(meta.get("emergency", "")).startswith(
            "watchdog:"):
        print("[chaos_smoke] FAIL(hang): emergency checkpoint "
              "step=%r meta=%r" % (step, meta))
        return 1
    if _assert_incident(fdir, "watchdog.hang", "hang"):
        return 1
    print("[chaos_smoke] hang OK: post-mortem names kvstore.push, "
          "emergency checkpoint loadable at step 7, abort rc=%d"
          % wd.ABORT_EXIT_CODE)
    return 0


def train_worker(ckdir, steps):
    """Subprocess body for sigterm/crash scenarios: a restartable
    training loop — resume_from_latest, per-step checkpoint, a
    chaos site at every step boundary for the injected faults."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models.checkpoint import (
        save_checkpoint, resume_from_latest,
        install_emergency_checkpoint)
    from mxnet_tpu.observability import chaos

    cfg = _tiny_cfg()
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 41, (4, 32)), jnp.int32)

    def fresh():
        p = T.init_params(cfg, seed=0)
        return cfg, p, T.init_momentum(p), 0

    _, params, mom, start = resume_from_latest(ckdir, init=fresh)
    state = {"params": params, "mom": mom, "step": start}
    install_emergency_checkpoint(
        ckdir, lambda: {"cfg": cfg, "params": state["params"],
                        "momentum": state["mom"],
                        "step": state["step"]})
    step_fn = T.make_train_step(cfg, lr=0.1)
    for step in range(start + 1, steps + 1):
        params, mom, loss = step_fn(params, mom, tokens)
        state.update(params=params, mom=mom, step=step)
        print("LOSS %d %s" % (step, float(loss).hex()), flush=True)
        save_checkpoint(ckdir, cfg, params, momentum=mom, step=step,
                        keep=2)
        chaos.fire("train.step", step=step)   # sigterm/crash land here
    return 0


def sigterm():
    from mxnet_tpu.models.checkpoint import load_checkpoint
    d = tempfile.mkdtemp(prefix="chaos_smoke_sigterm_")
    fdir = _flight_dir("sigterm")
    ckdir = os.path.join(d, "ck")
    env = dict(os.environ)
    env.update({"MXNET_CHAOS": "train.step:sigterm:at=1",
                "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
                "CHAOS_SMOKE_WORKER": "train"})
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), ckdir, "5"],
        capture_output=True, text=True, timeout=300, env=env)
    if r.returncode != 143:
        print("[chaos_smoke] FAIL(sigterm): rc=%d\n%s\n%s"
              % (r.returncode, r.stdout, r.stderr))
        return 1
    _, _, _, step, meta = load_checkpoint(ckdir)
    if step != 2 or meta.get("emergency") != "sigterm":
        print("[chaos_smoke] FAIL(sigterm): step=%r meta=%r"
              % (step, meta))
        return 1
    if _assert_incident(fdir, "sigterm", "sigterm"):
        return 1
    print("[chaos_smoke] sigterm OK: preemption at step 2 committed "
          "an emergency checkpoint, exit 143")
    return 0


def crash():
    d = tempfile.mkdtemp(prefix="chaos_smoke_crash_")
    fdir = _flight_dir("crash")
    env_base = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
                "CHAOS_SMOKE_WORKER": "train"}

    def run(ckdir, chaos_spec=None):
        env = dict(os.environ, **env_base)
        env.pop("MXNET_CHAOS", None)
        if chaos_spec:
            env["MXNET_CHAOS"] = chaos_spec
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), ckdir, "5"],
            capture_output=True, text=True, timeout=300, env=env)

    base = run(os.path.join(d, "a"))
    if base.returncode != 0:
        print("[chaos_smoke] FAIL(crash): baseline rc=%d\n%s"
              % (base.returncode, base.stderr))
        return 1
    want = [l for l in base.stdout.splitlines() if l.startswith("LOSS")]

    crashed = run(os.path.join(d, "b"),
                  "train.step:crash:at=2:code=21")
    if crashed.returncode != 21:
        print("[chaos_smoke] FAIL(crash): injected run rc=%d"
              % crashed.returncode)
        return 1
    resumed = run(os.path.join(d, "b"))
    if resumed.returncode != 0:
        print("[chaos_smoke] FAIL(crash): resume rc=%d\n%s"
              % (resumed.returncode, resumed.stderr))
        return 1
    got = [l for l in (crashed.stdout + resumed.stdout).splitlines()
           if l.startswith("LOSS")]
    if got != want:
        print("[chaos_smoke] FAIL(crash): resumed loss trajectory "
              "diverged:\n  want %s\n  got  %s" % (want, got))
        return 1
    if _assert_incident(fdir, "chaos.crash", "crash"):
        return 1
    print("[chaos_smoke] crash OK: crash at step 3, "
          "resume-from-latest; %d-step loss trajectory bit-exact"
          % len(want))
    return 0


def overload():
    """The serving overload storm end to end, in process: steady
    priority-0 streams pin every usable KV block on a 2-replica fleet,
    then a seeded mixed-priority burst at ~4x block capacity lands
    while an injected fault kills replica r1 mid-storm. Asserts the
    whole degradation story from ISSUE 12: no deadlock (bounded
    rounds), zero leaked blocks at quiesce, high-priority work
    preempting and completing first, ONLY priority-0 work shed or
    expired, the killed replica returning to rotation through
    HALF_OPEN, and every completed stream bit-exact vs solo
    generate()."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models.router import ReplicaRouter
    from mxnet_tpu.observability import chaos
    from mxnet_tpu.observability import core as obs

    chaos.reset()
    fdir = _flight_dir("overload")
    cfg = _tiny_cfg()
    params = T.init_params(cfg, seed=0)
    rng = np.random.RandomState(12)

    def prompt():
        return list(rng.randint(1, 41, 4))

    # steady phase: four priority-0 streams sized to pin all four
    # usable blocks on each replica (2 lifetime blocks per stream)
    # while leaving one lane free — the preemption precondition
    steady = [(prompt(), 10, 0, None) for _ in range(4)]
    # storm phase: mixed priorities at ~4x the fleet's block capacity;
    # two low-priority jobs carry an already-lapsed deadline
    storm = ([(prompt(), 8, 2, None) for _ in range(3)]
             + [(prompt(), 8, 1, None) for _ in range(3)]
             + [(prompt(), 8, 0, None) for _ in range(4)]
             + [(prompt(), 8, 0, 0) for _ in range(2)])
    solo = {}

    pre0 = obs.counter("serving.preemptions").value
    r = ReplicaRouter.build(params, cfg, n_replicas=2, max_batch=3,
                            shed_queue=8, breaker=True, paged=True,
                            block_size=8, num_blocks=5, brownout=True)
    prio, results, done_at, rung_max = {}, {}, {}, 0

    def submit(batch):
        for p, n, pr, ddl in batch:
            rid = r.submit(p, n, priority=pr, deadline_ms=ddl)
            prio[rid] = pr
            solo[rid] = np.asarray(T.generate(
                params, jnp.asarray([p], jnp.int32), n, cfg,
                greedy=True))[0].tolist()

    submit(steady)
    rounds = 0
    for _ in range(2):                    # let the steady load settle
        results.update(r.step())
        rounds += 1
    chaos.install("serving.dispatch.r1:error:at=1;"
                  "serving.dispatch.r1:error:at=2;"
                  "serving.dispatch.r1:error:at=3;"
                  "serving.dispatch.r1:error:at=4")
    submit(storm)
    try:
        while (r._queue or r._live) and rounds < 400:
            done = r.step()
            results.update(done)
            for rid in done:
                done_at.setdefault(rid, rounds)
            rung_max = max([rung_max] + [rep._bo_rung
                                         for rep in r.replicas])
            rounds += 1
    finally:
        chaos.reset()
    if r._queue or r._live:
        print("[chaos_smoke] FAIL(overload): DEADLOCK — %d queued, %d "
              "live after %d rounds" % (len(r._queue), len(r._live),
                                        rounds))
        return 1

    preemptions = obs.counter("serving.preemptions").value - pre0
    if preemptions < 1:
        print("[chaos_smoke] FAIL(overload): the burst never preempted "
              "a low-priority lane")
        return 1
    if rung_max < 1:
        print("[chaos_smoke] FAIL(overload): brownout ladder never "
              "left rung 0 under block exhaustion")
        return 1
    dropped = set(r.shed_rids) | set(r.expired_rids)
    if not r.shed_rids or len(r.expired_rids) < 2:
        print("[chaos_smoke] FAIL(overload): shed=%d expired=%d — "
              "expected both paths exercised"
              % (len(r.shed_rids), len(r.expired_rids)))
        return 1
    if any(prio[rid] != 0 for rid in dropped):
        print("[chaos_smoke] FAIL(overload): non-priority-0 work was "
              "shed/expired: %s"
              % sorted((rid, prio[rid]) for rid in dropped))
        return 1
    for name in ("shed", "expired"):
        key = "serving.slo_violation." + name
        if r.health_snapshot()[key] != len(getattr(r, name + "_rids")):
            print("[chaos_smoke] FAIL(overload): %s miscounted in "
                  "health_snapshot()" % key)
            return 1

    # every non-dropped request completed, bit-exact vs solo
    for rid, pr in prio.items():
        if rid in dropped:
            continue
        if results.get(rid) != solo[rid]:
            print("[chaos_smoke] FAIL(overload): stream rid=%d "
                  "(priority %d) diverged from solo generate()"
                  % (rid, pr))
            return 1
    # priority-ordered completion: higher classes finish earlier on
    # average than the priority-0 survivors (the steady streams all
    # get preempted or drained and resume at the tail of the storm)
    by_p = {p: [done_at[rid] for rid in prio
                if prio[rid] == p and rid in done_at
                and rid not in dropped]
            for p in (0, 1, 2)}
    mean = lambda xs: sum(xs) / float(len(xs))  # noqa: E731
    if not by_p[2] or not by_p[1] or not by_p[0] \
            or mean(by_p[2]) >= mean(by_p[0]) \
            or mean(by_p[1]) >= mean(by_p[0]):
        print("[chaos_smoke] FAIL(overload): completion order ignored "
              "priority: %s" % by_p)
        return 1

    want = [("r1", "closed", "open"), ("r1", "open", "half_open"),
            ("r1", "half_open", "closed")]
    if any(ev not in r.breaker_events for ev in want):
        print("[chaos_smoke] FAIL(overload): breaker never completed "
              "open -> half_open -> closed for r1: %s"
              % r.breaker_events)
        return 1
    if r._alive != [True, True] or r._brk_state != ["closed", "closed"]:
        print("[chaos_smoke] FAIL(overload): fleet did not fully "
              "recover: alive=%s state=%s" % (r._alive, r._brk_state))
        return 1
    for rep in r.replicas:
        rep.check_invariants(quiesce=True)   # zero leaked blocks
        if "serving.brownout_rung" not in rep.health_snapshot():
            print("[chaos_smoke] FAIL(overload): %s health snapshot "
                  "lacks serving.brownout_rung" % rep.name)
            return 1
    if _assert_incident(fdir, "chaos.error", "overload") \
            or _assert_incident(fdir, "breaker.open", "overload"):
        return 1
    print("[chaos_smoke] overload OK: %d-job storm over 2 replicas — "
          "%d preempted-and-resumed, %d shed + %d expired (all "
          "priority 0), brownout peaked at rung %d, r1 killed and "
          "recovered via HALF_OPEN, all %d completed streams bit-exact"
          % (len(prio), preemptions, len(r.shed_rids),
             len(r.expired_rids), rung_max,
             sum(1 for rid in prio if rid not in dropped)))
    return 0


def elastic():
    """The elastic shrink-relaunch-resume chain, end to end on the CPU
    mesh: a 2-process gloo job with one injected rank kill must (1)
    shrink to world 1 and regrow to 2 under tools/elastic_launch.py,
    (2) consume every sample exactly once across all generations
    (cursor-exact), (3) produce a post-shrink loss trajectory
    BIT-identical to a clean world-1 run resumed from the same shard
    set, and (4) export the elastic.time_to_recovery_ms histogram on
    the merged trace."""
    import json
    import re
    import shutil

    d = tempfile.mkdtemp(prefix="chaos_smoke_elastic_")
    fdir = _flight_dir("elastic")
    sb, ck = os.path.join(d, "sb"), os.path.join(d, "ck")
    steps, rows = 6, 8
    env = dict(os.environ)
    env.update({
        "MXNET_ELASTIC_DIR": sb,
        "MXNET_ELASTIC_HEARTBEAT_S": "0.2",
        "MXNET_ELASTIC_MISS": "3",
        "MXNET_ELASTIC_KEEP_GLOBAL_BATCH": "1",
        "MXNET_ELASTIC_KEEP_GENERATIONS": "8",
        "MXNET_OBS": "1", "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
    })
    env.pop("MXNET_CHAOS", None)
    worker_py = os.path.join(ROOT, "examples", "elastic_training.py")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools",
                                      "elastic_launch.py"),
         "-n", "2", "--max-restarts", "4", "--backoff-ms", "100",
         "--chaos-spec", "train.step:crash:at=1:rank=1:code=31",
         "--", sys.executable, worker_py, "--elastic-worker",
         "--steps", str(steps), "--gen-steps", "2",
         "--ckpt-dir", ck],
        capture_output=True, text=True, timeout=540, env=env)
    sys.stdout.write(r.stdout)
    if r.returncode != 0:
        print("[chaos_smoke] FAIL(elastic): supervisor rc=%d\n%s"
              % (r.returncode, r.stderr[-2000:]))
        return 1
    out = r.stdout
    if "-> shrink" not in out or "regrow: world 1 -> 2" not in out:
        print("[chaos_smoke] FAIL(elastic): no shrink/regrow in the "
              "supervisor log")
        return 1

    # (2) cursor-exact: the union of per-step DATA ranges must tile
    # [0, steps*rows) exactly — zero skipped, zero replayed
    ranges = {}
    for m in re.finditer(r"^DATA g(\d+) r0 (\d+) (\d+) (\d+)$", out,
                         re.M):
        step, lo, hi = int(m.group(2)), int(m.group(3)), int(m.group(4))
        if step in ranges and ranges[step] != (lo, hi):
            print("[chaos_smoke] FAIL(elastic): step %d consumed both "
                  "%s and %s" % (step, ranges[step], (lo, hi)))
            return 1
        ranges[step] = (lo, hi)
    want = {s: ((s - 1) * rows, s * rows) for s in range(1, steps + 1)}
    if ranges != want:
        print("[chaos_smoke] FAIL(elastic): data ranges %s != %s"
              % (ranges, want))
        return 1

    # (3) post-shrink bit-exactness: a clean world-1 run resumed from
    # the SAME generation-1 shard set must reproduce g1's losses digit
    # for digit
    g1 = {int(m.group(1)): m.group(2) for m in re.finditer(
        r"^LOSS g1 r0 (\d+) (\S+)$", out, re.M)}
    if not g1:
        print("[chaos_smoke] FAIL(elastic): no post-shrink LOSS lines")
        return 1
    clean_ck = os.path.join(d, "ck_clean")
    shutil.copytree(ck, clean_ck)
    env_clean = dict(env)
    env_clean.update({
        "MXNET_ELASTIC_DIR": os.path.join(d, "sb_clean"),
        "MXNET_ELASTIC_GENERATION": "1",
        "MXNET_ELASTIC_RESUME_GEN": "1",
        "MXNET_ELASTIC_BASE_WORLD": "2",
        "MXNET_TPU_NUM_PROC": "1", "MXNET_TPU_PROC_ID": "0",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    })
    rc = subprocess.run(
        [sys.executable, worker_py, "--elastic-worker",
         "--steps", str(max(g1)), "--gen-steps", "0",
         "--ckpt-dir", clean_ck],
        capture_output=True, text=True, timeout=300, env=env_clean)
    if rc.returncode != 0:
        print("[chaos_smoke] FAIL(elastic): clean comparison run "
              "rc=%d\n%s" % (rc.returncode, rc.stderr[-2000:]))
        return 1
    clean = {int(m.group(1)): m.group(2) for m in re.finditer(
        r"^LOSS g1 r0 (\d+) (\S+)$", rc.stdout, re.M)}
    if any(clean.get(s) != g1[s] for s in g1):
        print("[chaos_smoke] FAIL(elastic): post-shrink trajectory "
              "diverged from the clean same-step run:\n  elastic %s\n"
              "  clean   %s" % (g1, clean))
        return 1

    # (4) recovery-time histogram on the merged trace of the recovered
    # generation
    from mxnet_tpu.observability import dist
    base = os.path.join(sb, "trace-g1.json")
    if not os.path.exists(base):
        print("[chaos_smoke] FAIL(elastic): no generation-1 trace at "
              "%s" % base)
        return 1
    merged = dist.merge_traces(base, out=os.path.join(d, "merged.json"))
    hist = merged.get("otherData", {}).get("histograms", {}).get(
        "elastic.time_to_recovery_ms", {})
    if not hist.get("count"):
        print("[chaos_smoke] FAIL(elastic): merged trace lacks the "
              "elastic.time_to_recovery_ms histogram (%s)"
              % json.dumps(list(merged.get("otherData", {})
                                .get("histograms", {}))))
        return 1
    if _assert_incident(fdir, "elastic.shrink", "elastic"):
        return 1
    print("[chaos_smoke] elastic OK: kill -> shrink(44) -> bit-exact "
          "world-1 resume -> regrow(45) -> done; %d/%d samples "
          "cursor-exact, time_to_recovery_ms count=%d mean=%.0fms"
          % (steps * rows, steps * rows, hist["count"],
             hist.get("sum", 0.0) / max(hist["count"], 1)))
    return 0


def integrity_train_worker(ckdir, steps):
    """Subprocess body for the --integrity grad-flip leg: a gluon
    training loop through the fused kvstore path, one verified
    checkpoint per step, restartable via load_checkpoint. A replay-
    audit verdict quarantines INSIDE trainer.step (exit 46), before
    the corrupted step's checkpoint is ever written."""
    import numpy as np
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.models.checkpoint import (save_checkpoint,
                                             load_checkpoint)

    cfg = _tiny_cfg()               # carrier config for the manifest
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05},
                            kvstore=mx.kvstore.create("device"))
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.uniform(size=(8, 10)).astype(np.float32))
    y = mx.nd.array(rng.uniform(size=(8, 4)).astype(np.float32))
    params = net.collect_params()
    start = 0
    if os.path.exists(os.path.join(ckdir, "manifest.json")):
        net(x)                      # materialize deferred-init shapes
        _, saved, _, start, _ = load_checkpoint(ckdir)
        for k, p in params.items():
            p.data()._data = jnp.asarray(saved[k])
    for step in range(start + 1, steps + 1):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)             # a detected flip exits 46 HERE
        print("LOSS %d %s" % (step,
                              float(loss.asnumpy().sum()).hex()),
              flush=True)
        save_checkpoint(ckdir, cfg,
                        {k: p.data()._data for k, p in params.items()},
                        step=step, keep=3)
    return 0


def vote_worker():
    """Subprocess body for the --integrity weight-drift leg: one of
    three gloo ranks trains with a chaos-flipped replicated weight;
    the per-step fingerprint vote must name it."""
    from mxnet_tpu import parallel
    parallel.init_distributed()
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.observability import integrity

    rank = jax.process_index()
    assert jax.process_count() == 3
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05},
                            kvstore="dist_tpu_sync")
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)          # same data on every rank
    x = mx.nd.array(rng.uniform(size=(8, 10)).astype(np.float32))
    y = mx.nd.array(rng.uniform(size=(8, 4)).astype(np.float32))
    for _ in range(2):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)
    if integrity.stats["votes"] < 1:
        print("[chaos_smoke] FAIL(vote): rank %d never voted" % rank)
        return 1
    if rank == 1 and integrity.stats["detected"] < 1:
        print("[chaos_smoke] FAIL(vote): the flipped rank saw no "
              "verdict")
        return 1
    print("VOTE-RANK-OK %d" % rank, flush=True)
    return 0


def integrity_scenario():
    """One injected flip per silent-corruption class, each asserting
    BOTH detection (evidence naming rank/bucket/file/record) and
    verified recovery (docs/ROBUSTNESS.md "Silent corruption")."""
    import json

    # ---- gradient-bucket flip -> replay audit -> quarantine(46) ----
    # -> relaunch resumes BIT-exact from the last verified checkpoint
    d = tempfile.mkdtemp(prefix="chaos_smoke_integrity_")
    fdir = _flight_dir("integrity")
    sb = os.path.join(d, "sb")
    env_base = {"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
                "CHAOS_SMOKE_WORKER": "integrity_train"}

    def run(ckdir, extra=None):
        env = dict(os.environ, **env_base)
        for k in ("MXNET_CHAOS", "MXNET_INTEGRITY",
                  "MXNET_INTEGRITY_REPLAY_EVERY",
                  "MXNET_INTEGRITY_ACTION", "MXNET_INTEGRITY_EVERY"):
            env.pop(k, None)
        env.update(extra or {})
        return subprocess.run(
            [sys.executable, os.path.abspath(__file__), ckdir, "5"],
            capture_output=True, text=True, timeout=300, env=env)

    base = run(os.path.join(d, "a"))
    if base.returncode != 0:
        print("[chaos_smoke] FAIL(grad): baseline rc=%d\n%s"
              % (base.returncode, base.stderr[-2000:]))
        return 1
    want = [l for l in base.stdout.splitlines() if l.startswith("LOSS")]

    armed = {"MXNET_INTEGRITY": "1", "MXNET_INTEGRITY_EVERY": "0",
             "MXNET_INTEGRITY_REPLAY_EVERY": "1",
             "MXNET_INTEGRITY_ACTION": "quarantine",
             "MXNET_ELASTIC_DIR": sb}
    flipped = run(os.path.join(d, "b"),
                  dict(armed,
                       MXNET_CHAOS="kvstore.bucket.pack:bitflip:"
                                   "at=2:bit=30:elem=5"))
    if flipped.returncode != 46:
        print("[chaos_smoke] FAIL(grad): flipped run rc=%d (want "
              "quarantine 46)\n%s" % (flipped.returncode,
                                      flipped.stderr[-2000:]))
        return 1
    rec_path = os.path.join(sb, "quarantine.g0.rank0.json")
    if not os.path.exists(rec_path):
        print("[chaos_smoke] FAIL(grad): no quarantine evidence at %s"
              % rec_path)
        return 1
    with open(rec_path) as f:
        ev = json.load(f).get("evidence", {})
    if ev.get("kind") != "replay_mismatch" or "bucket" not in ev:
        print("[chaos_smoke] FAIL(grad): evidence lacks bucket-level "
              "replay verdict: %s" % ev)
        return 1
    resumed = run(os.path.join(d, "b"), armed)   # detectors stay armed
    if resumed.returncode != 0:
        print("[chaos_smoke] FAIL(grad): resume rc=%d\n%s"
              % (resumed.returncode, resumed.stderr[-2000:]))
        return 1
    got = [l for l in (flipped.stdout + resumed.stdout).splitlines()
           if l.startswith("LOSS")]
    if got != want:
        print("[chaos_smoke] FAIL(grad): post-quarantine trajectory "
              "diverged:\n  want %s\n  got  %s" % (want, got))
        return 1
    print("[chaos_smoke] grad OK: bucket flip caught by the replay "
          "audit (bucket %s), quarantine(46) with evidence, %d-step "
          "loss trajectory bit-exact after verified-checkpoint resume"
          % (ev.get("bucket"), len(want)))

    # ---- replicated-weight flip on one rank -> 3-way vote ----
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
                "CHAOS_SMOKE_WORKER": "vote",
                "MXNET_INTEGRITY": "1", "MXNET_INTEGRITY_EVERY": "1",
                "MXNET_INTEGRITY_REPLAY_EVERY": "0",
                "MXNET_INTEGRITY_ACTION": "warn",
                "MXNET_CHAOS":
                    "trainer.weights:bitflip:rank=1:at=0:bit=30"})
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "launch.py"),
         "-n", "3", "--launcher", "local",
         sys.executable, os.path.abspath(__file__)],
        capture_output=True, text=True, timeout=420, env=env)
    if r.returncode != 0 or r.stdout.count("VOTE-RANK-OK") != 3:
        print("[chaos_smoke] FAIL(vote): rc=%d\n%s\n%s"
              % (r.returncode, r.stdout[-2000:], r.stderr[-2000:]))
        return 1
    if "replica_drift" not in r.stderr \
            or "'drifted': [1]" not in r.stderr:
        print("[chaos_smoke] FAIL(vote): no replica_drift verdict "
              "naming rank 1 in stderr:\n%s" % r.stderr[-2000:])
        return 1
    print("[chaos_smoke] vote OK: weight flip on rank 1 of 3 named by "
          "the fingerprint majority vote on every rank")

    # ---- checkpoint-byte flip -> refuse by name -> verified fallback --
    import warnings

    import numpy as np
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models import checkpoint as ckpt
    from mxnet_tpu.observability import chaos

    cfg = _tiny_cfg()
    ck = os.path.join(d, "ck")
    p1 = T.init_params(cfg, seed=1)
    ckpt.save_checkpoint(ck, cfg, p1, step=1, keep=2)
    chaos.install("checkpoint.bytes:bitflip:at=0:elem=4096:bit=6")
    try:
        ckpt.save_checkpoint(ck, cfg, T.init_params(cfg, seed=2),
                             step=2, keep=2)
    finally:
        chaos.reset()
    try:
        ckpt.load_checkpoint(ck, fallback=False)
    except ckpt.CheckpointCorrupt as e:
        if "arrays-2" not in str(e):
            print("[chaos_smoke] FAIL(checkpoint): corruption error "
                  "does not name the data file: %s" % e)
            return 1
    else:
        print("[chaos_smoke] FAIL(checkpoint): flipped byte loaded "
              "without complaint")
        return 1
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)
        _, got_p, _, step, _ = ckpt.load_checkpoint(ck)
    if step != 1:
        print("[chaos_smoke] FAIL(checkpoint): fell back to step %r, "
              "want 1" % step)
        return 1
    a, b = {}, {}
    ckpt._flatten(p1, "p", a)
    ckpt._flatten(got_p, "p", b)
    if any(np.asarray(b[k]).tobytes() != np.asarray(a[k]).tobytes()
           for k in a):
        print("[chaos_smoke] FAIL(checkpoint): fallback weights are "
              "not bit-identical to the verified step-1 save")
        return 1
    print("[chaos_smoke] checkpoint OK: flipped byte refused naming "
          "the data file, recovery fell back to the verified step-1 "
          "checkpoint bit-exactly")

    # ---- recordio record flip: transient retried, persistent fatal --
    from mxnet_tpu import io as mx_io, recordio

    chaos.reset()
    rec_file = os.path.join(d, "data.rec")
    payload = bytes(range(48))
    w = recordio.MXRecordIO(rec_file, "w")
    w.write(payload)
    w.close()
    r0 = recordio.MXRecordIO(rec_file, "r")
    chaos.install("recordio.read:bitflip:at=0:bit=2:elem=5")
    try:
        r0.read()
        print("[chaos_smoke] FAIL(recordio): transient flip read "
              "without complaint")
        return 1
    except recordio.RecordCorrupt as e:
        if e.path != rec_file or e.record_index != 0:
            print("[chaos_smoke] FAIL(recordio): evidence names %r "
                  "record %r" % (e.path, e.record_index))
            return 1
    if r0.read() != payload:           # rule exhausted: retry is clean
        print("[chaos_smoke] FAIL(recordio): retry after a transient "
              "flip did not deliver the clean record")
        return 1
    r0.close()
    chaos.reset()
    with open(rec_file, "r+b") as f:   # at-rest flip: every read fails
        f.seek(11)
        byte = f.read(1)
        f.seek(11)
        f.write(bytes([byte[0] ^ 4]))
    os.environ["MXNET_IO_BACKOFF_MS"] = "1"
    r1 = recordio.MXRecordIO(rec_file, "r")
    try:
        mx_io._retry_read(r1.read, "recordio.read", path=rec_file)
        print("[chaos_smoke] FAIL(recordio): on-disk flip read "
              "without complaint")
        return 1
    except IOError as e:
        if "corrupt record 0" not in str(e) or rec_file not in str(e):
            print("[chaos_smoke] FAIL(recordio): exhausted error "
                  "lacks path/record evidence: %s" % e)
            return 1
    r1.close()
    if _assert_incident(fdir, "integrity.quarantine", "integrity"):
        return 1
    print("[chaos_smoke] recordio OK: transient flip named "
          "(path, record 0) and recovered on retry; at-rest flip "
          "exhausted retries into the enriched IOError")
    return 0


def mem_pressure():
    """The ISSUE 14 memory-pressure closure: one deterministic
    injected RESOURCE_EXHAUSTED per recovery path — every site listed
    in docs/ROBUSTNESS.md "Memory pressure" must recover WITHOUT
    process death, on the CPU mesh, replayably:

      trainer.step         accum re-lower at 2x: the recovered loss
                           trajectory is deterministic (bit-identical
                           across reruns) and matches the
                           uninterrupted global-batch run
      serving.dispatch     pool shrink-and-retry: blocks park, lanes
                           survive, every stream bit-exact vs solo
      kv.pool.grow         a grow that OOMs leaves the pool shrunk
                           (capacity loss, never a crash); the next
                           clean grow restores it
      checkpoint.snapshot  the D2H gather retries serially and the
                           committed checkpoint loads bit-exact
    """
    import tempfile

    fdir = _flight_dir("oom")
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models import checkpoint as ck
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.observability import chaos, membudget
    from mxnet_tpu.parallel import elastic as el

    chaos.reset()
    membudget.reset()
    os.environ["MXNET_MEM_OOM_ACTION"] = "accum"
    cfg = _tiny_cfg()
    try:
        # ---- trainer.step: OOM -> accum re-lower, trajectory kept --
        rng = np.random.RandomState(0)
        batches = [rng.randint(0, 41, (4, cfg.max_len))
                   for _ in range(4)]

        def train(inject):
            chaos.reset()
            if inject:
                chaos.inject("trainer.step", "oom", at=2)
            params = T.init_params(cfg, seed=1)
            mom = T.init_momentum(params)
            accum = membudget.sticky_accum_factor()
            step = el.make_accum_train_step(cfg, lr=0.1, accum=accum)
            losses = []
            for b in batches:
                while True:
                    try:
                        if chaos.enabled():
                            chaos.fire("trainer.step")
                        toks = jnp.asarray(
                            b.reshape(accum, b.shape[0] // accum,
                                      cfg.max_len), jnp.int32)
                        params, mom, loss = step(params, mom, toks)
                        break
                    except Exception as exc:
                        if not membudget.is_resource_exhausted(exc):
                            raise
                        membudget.note_oom("trainer.step", exc)
                        accum = membudget.escalate_accum(
                            accum, b.shape[0])
                        step = el.make_accum_train_step(cfg, lr=0.1,
                                                        accum=accum)
                losses.append(float(loss))
            fired = chaos.stats["oom"]
            chaos.reset()
            return losses, accum, fired

        plain, accum0, _ = train(inject=False)
        rec1, accum1, fired1 = train(inject=True)
        rec2, accum2, _ = train(inject=True)
        if accum0 != 1 or accum1 != 2 or fired1 != 1:
            print("[chaos_smoke] FAIL(oom/trainer): accum %d -> %d, "
                  "%d faults fired" % (accum0, accum1, fired1))
            return 1
        if [x.hex() for x in rec1] != [x.hex() for x in rec2]:
            print("[chaos_smoke] FAIL(oom/trainer): recovered "
                  "trajectory is not deterministic")
            return 1
        if not np.allclose(rec1, plain, rtol=1e-5):
            print("[chaos_smoke] FAIL(oom/trainer): recovered "
                  "trajectory diverged from the global batch: %s vs %s"
                  % (rec1, plain))
            return 1

        # ---- serving.dispatch: OOM -> shrink-and-retry ----
        params = T.init_params(cfg, seed=0)
        jobs = [([3, 5, 7, 5], 6), ([11, 2, 9, 4], 6)]
        solo = [np.asarray(T.generate(
            params, jnp.asarray([p], jnp.int32), n, cfg,
            greedy=True))[0].tolist() for p, n in jobs]
        chaos.inject("serving.dispatch", "oom", at=1)
        srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                block_size=8, num_blocks=12)
        results, order = srv.run(jobs)
        if chaos.stats["oom"] != 1 or srv._alloc.parked_blocks < 1:
            print("[chaos_smoke] FAIL(oom/serving): fired=%d parked=%d"
                  % (chaos.stats["oom"], srv._alloc.parked_blocks))
            return 1
        for j, rid in enumerate(order):
            if results[rid] != solo[j]:
                print("[chaos_smoke] FAIL(oom/serving): stream %d "
                      "diverged after shrink-and-retry" % j)
                return 1
        srv.check_invariants(quiesce=True)
        chaos.reset()

        # ---- kv.pool.grow: OOM stays shrunk, clean grow restores ----
        srv2 = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                 block_size=8, num_blocks=10,
                                 brownout=True)
        srv2._set_rung(4)                  # kv_shrink rung parks
        parked = srv2._bo_parked
        chaos.inject("kv.pool.grow", "oom", at=0)
        srv2._set_rung(0)                  # grow-back OOMs: stay shrunk
        if parked < 1 or srv2._bo_parked != parked \
                or srv2._alloc.parked_blocks != parked:
            print("[chaos_smoke] FAIL(oom/grow): parked=%d bo=%d "
                  "ledger=%d" % (parked, srv2._bo_parked,
                                 srv2._alloc.parked_blocks))
            return 1
        chaos.reset()
        if srv2.grow_pool(parked) != parked \
                or srv2._alloc.parked_blocks != 0:
            print("[chaos_smoke] FAIL(oom/grow): clean grow did not "
                  "restore the pool")
            return 1
        r = srv2.admit([3, 5, 7], 6)       # shrunk-then-grown pool serves
        done = {}
        while r not in done:
            done.update(srv2.step())
        want = np.asarray(T.generate(
            params, jnp.asarray([[3, 5, 7]], jnp.int32), 6, cfg,
            greedy=True))[0].tolist()
        if done[r] != want:
            print("[chaos_smoke] FAIL(oom/grow): post-grow stream "
                  "diverged")
            return 1
        srv2.check_invariants(quiesce=True)

        # ---- checkpoint.snapshot: OOM retries serial + commits ----
        chaos.inject("checkpoint.snapshot", "oom", at=0)
        params2 = T.init_params(cfg, seed=5)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "oomck")
            ck.save_checkpoint(path, cfg, params2)
            if chaos.stats["oom"] != 1:
                print("[chaos_smoke] FAIL(oom/ckpt): fault never fired")
                return 1
            if membudget.snapshot_bytes_in_flight() != 0:
                print("[chaos_smoke] FAIL(oom/ckpt): snapshot ledger "
                      "left open")
                return 1
            cfg2, p2 = ck.load_checkpoint(path)[:2]
            for a, b in zip(jax.tree.leaves(params2),
                            jax.tree.leaves(p2)):
                if not np.array_equal(np.asarray(a), np.asarray(b)):
                    print("[chaos_smoke] FAIL(oom/ckpt): reloaded "
                          "params diverged")
                    return 1
        chaos.reset()
    finally:
        os.environ.pop("MXNET_MEM_OOM_ACTION", None)
        membudget.reset()
        chaos.reset()
    if _assert_incident(fdir, "chaos.oom", "oom"):
        return 1
    print("[chaos_smoke] oom OK: trainer re-lowered at accum=2 with a "
          "deterministic global-batch trajectory, serving shrank and "
          "retried bit-exact, a failed pool grow degraded to reduced "
          "capacity, and the checkpoint snapshot retried serially and "
          "reloaded bit-exact — no process died")
    return 0


_DURABLE_JOBS = [([1, 2, 3], 6, 0), ([4, 5], 6, 1), ([7, 8, 9], 6, 2)]
_DURABLE_MODES = {
    # paged x spec x pipeline greedy, and paged x pipeline sampled —
    # the ISSUE 15 recovery matrix's two hardest columns
    "spec_greedy": dict(paged=True, block_size=4, num_blocks=24,
                        pipeline_depth=2, spec_k=2, spec_ngram=2,
                        greedy=True),
    "pipe_sampled": dict(paged=True, block_size=4, num_blocks=24,
                         pipeline_depth=2, greedy=False),
}


def durable_worker(jdir, mode):
    """Subprocess body for the kill-9 leg: serve the fixed job set with
    the journal attached; the parent's MXNET_CHAOS spec hard-kills us
    mid-emission at a journal commit point (exit code 9)."""
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models.serving import ContinuousBatcher
    cfg = _tiny_cfg()
    params = T.init_params(cfg, seed=0)
    srv = ContinuousBatcher(params, cfg, max_batch=4, journal=jdir,
                            **_DURABLE_MODES[mode])
    for prompt, n_new, seed in _DURABLE_JOBS:
        srv.admit(prompt, n_new, seed=seed)
    done = {}
    for _ in range(300):
        done.update(srv.step())
        if len(done) == len(_DURABLE_JOBS):
            return 0               # chaos never fired — parent fails rc
    return 0


def durable():
    """The ISSUE 15 durable-serving closure, four legs:

      kill-9 replay   (subprocess x2) a journal-commit-point hard kill
                      (exit 9, no cleanup) under paged x spec x
                      pipeline greedy AND paged x pipeline sampled; a
                      fresh batcher's recover() replays every stream
                      BIT-exactly vs an uninterrupted run
      torn/corrupt    a torn tail and a CRC-flipped record are skipped
                      with named evidence; the records behind them
                      still replay
      canary rollback (fleet) an injected ``router.rollout`` fault at
                      the canary phase rolls every replica back to the
                      prior verified fingerprint with ZERO dropped
                      in-flight requests
      lineage gate    a hot-swap whose manifest fingerprint does not
                      match the incoming weights is refused before any
                      replica is touched
    """
    import tempfile

    fdir = _flight_dir("durable")
    from mxnet_tpu.models import transformer as T
    from mxnet_tpu.models import checkpoint as ck
    from mxnet_tpu.models.journal import RequestJournal
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.models.router import ReplicaRouter
    from mxnet_tpu.observability import chaos

    chaos.reset()
    cfg = _tiny_cfg()
    params = T.init_params(cfg, seed=0)

    # ---- kill-9 replay, both matrix columns ----
    for mode in ("spec_greedy", "pipe_sampled"):
        ref_srv = ContinuousBatcher(params, cfg, max_batch=4,
                                    journal=False,
                                    **_DURABLE_MODES[mode])
        ref, order = ref_srv.run(
            [(p, n, s) for p, n, s in _DURABLE_JOBS])
        ref = {rid: ref[rid] for rid in order}
        with tempfile.TemporaryDirectory() as td:
            env = dict(os.environ)
            env.pop("MXNET_SERVING_JOURNAL_DIR", None)
            env.update({
                "CHAOS_SMOKE_WORKER": "durable_serve",
                # each record is TWO rule matches (the pre-write fire
                # + the at-rest corrupt_file hook): at=8 is the
                # pre-write fire of the 5th record — after all three
                # submits and one emission checkpoint landed
                "MXNET_CHAOS": "journal.append:crash:at=8:code=9",
                "JAX_PLATFORMS": "cpu", "MXNET_OBS": "1"})
            proc = subprocess.run(
                [sys.executable, os.path.abspath(__file__), td, mode],
                env=env, cwd=ROOT, capture_output=True, text=True,
                timeout=600)
            if proc.returncode != 9:
                print("[chaos_smoke] FAIL(durable/%s): worker exited "
                      "%d, wanted the injected kill (9)\n%s" % (
                          mode, proc.returncode, proc.stderr[-2000:]))
                return 1
            srv = ContinuousBatcher(params, cfg, max_batch=4,
                                    journal=td,
                                    **_DURABLE_MODES[mode])
            resumed, rdone, skipped = srv.recover()
            if skipped:
                print("[chaos_smoke] FAIL(durable/%s): clean journal "
                      "replay skipped records: %s" % (mode, skipped))
                return 1
            if not resumed and len(rdone) != len(_DURABLE_JOBS):
                print("[chaos_smoke] FAIL(durable/%s): nothing to "
                      "recover — the kill landed too late" % mode)
                return 1
            got = dict(rdone)
            new2old = {v: k for k, v in resumed.items()
                       if v is not None}
            parked = [k for k, v in resumed.items() if v is None]
            for _ in range(400):
                while srv.preempted and parked:
                    req, _t = srv.preempted.pop(0)
                    new = srv.admit_continuation(
                        req.tokens, req.n_new - req.emitted,
                        seed=req.seed, emitted=req.emitted,
                        stop_token=req.stop_token, resumes=req.rid,
                        key=req.key)
                    if new is None:
                        srv.preempted.insert(0, (req, _t))
                        break
                    new2old[new] = req.rid
                    parked.remove(req.rid)
                if not parked and all(
                        n in got or o in got
                        for n, o in new2old.items()):
                    break
                for rid, toks in srv.step().items():
                    got[new2old.get(rid, rid)] = toks
            for i, rid in enumerate(sorted(ref)):
                if got.get(rid) != ref[rid]:
                    print("[chaos_smoke] FAIL(durable/%s): stream %d "
                          "diverged after kill-9 replay: %s vs %s"
                          % (mode, i, got.get(rid), ref[rid]))
                    return 1
            srv.check_invariants(quiesce=True)

    # ---- torn tail + CRC flip: skipped with evidence, rest replay --
    with tempfile.TemporaryDirectory() as td:
        j = RequestJournal(td)
        j.append_submit(0, [1, 2, 3, 9], 6, seed=0, emitted=1)
        j.append_submit(1, [4, 5, 8], 6, seed=1, emitted=1)
        j.append_emit(0, [7], 2)
        j.close()
        seg = sorted(n for n in os.listdir(td)
                     if n.endswith(".wal"))[0]
        path = os.path.join(td, seg)
        with open(path, "rb") as f:
            lines = f.read().split(b"\n")
        # flip one payload byte of record 1 (rid 1's submit): CRC
        # mismatch; then a torn tail with no record terminator
        bad = bytearray(lines[1])
        bad[-1] ^= 0x01
        lines[1] = bytes(bad)
        with open(path, "wb") as f:
            f.write(b"\n".join(lines[:3]) + b"\n")
            f.write(b"deadbeef {\"t\": \"submit\", \"rid\": 2")
        live, fin, skipped = RequestJournal(td).replay()
        reasons = sorted(s["reason"].split(" ")[0] for s in skipped)
        if reasons != ["crc", "torn"]:
            print("[chaos_smoke] FAIL(durable/torn): wanted crc+torn "
                  "evidence, got %s" % skipped)
            return 1
        if sorted(live) != [0] or live[0]["tokens"] != [1, 2, 3, 9, 7]:
            print("[chaos_smoke] FAIL(durable/torn): surviving "
                  "records did not replay: %s" % live)
            return 1

    # ---- chaos-failed canary -> fleet rollback, zero dropped ----
    import warnings
    p1 = T.init_params(cfg, seed=1)
    reps = [ContinuousBatcher(params, cfg, max_batch=4, journal=False)
            for _ in range(2)]
    router = ReplicaRouter(reps, journal=False)
    fp0 = reps[0].weight_fingerprint
    order = [router.submit([1, 2, 3], 6, seed=s) for s in range(5)]
    router.step()
    chaos.inject("router.rollout", "error", at=1)   # the canary fire
    router.start_rollout(p1)
    results = {}
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        for _ in range(500):
            if not (router._queue or router._live
                    or router.rollout_phase in ("draining", "canary")):
                break
            results.update(router.step())
    chaos.reset()
    if router.rollout_phase != "rolled_back":
        print("[chaos_smoke] FAIL(durable/rollback): phase %s after "
              "a chaos-failed canary" % router.rollout_phase)
        return 1
    if any(r.weight_fingerprint != fp0 for r in reps):
        print("[chaos_smoke] FAIL(durable/rollback): fleet not "
              "restored to the prior fingerprint %s: %s"
              % (fp0, [r.weight_fingerprint for r in reps]))
        return 1
    dropped = [r for r in order
               if r not in results or results[r] is None]
    if dropped:
        print("[chaos_smoke] FAIL(durable/rollback): %d in-flight "
              "request(s) dropped across the rollback" % len(dropped))
        return 1

    # ---- lineage gate: a mismatched manifest refuses the swap ----
    srv = ContinuousBatcher(params, cfg, max_batch=2, journal=False)
    fp = srv.weight_fingerprint
    try:
        srv.swap_weights(p1, manifest={"param_fingerprint": "0" * 8})
        print("[chaos_smoke] FAIL(durable/lineage): unverified swap "
              "was accepted")
        return 1
    except ck.CheckpointCorrupt:
        pass
    if srv.weight_fingerprint != fp:
        print("[chaos_smoke] FAIL(durable/lineage): refused swap "
              "still changed the weights")
        return 1

    if _assert_incident(fdir, "rollout.rollback", "durable") \
            or _assert_incident(fdir, "chaos.crash", "durable"):
        return 1
    print("[chaos_smoke] durable OK: kill-9 at a journal commit point "
          "replayed bit-exact (paged x spec x pipeline greedy, paged "
          "x pipeline sampled), torn/CRC-corrupt records skipped "
          "with named evidence, a chaos-failed canary rolled the "
          "fleet back to the prior verified fingerprint with zero "
          "dropped requests, and an unverified hot-swap was refused")
    return 0


SCENARIOS = [("nan", nan_guard), ("ioerror", ioerror),
             ("serving", serving), ("hang", hang),
             ("sigterm", sigterm), ("crash", crash)]


def main():
    print("[chaos_smoke] CPU structure check by design: JAX_PLATFORMS=%s "
          "(pinned by this script when unset); counts, bytes and "
          "orderings only — no time or rate below is a device number"
          % os.environ["JAX_PLATFORMS"], flush=True)
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("args", nargs="*")
    p.add_argument("--only", help="run one scenario (%s)"
                   % "/".join(n for n, _ in SCENARIOS))
    p.add_argument("--elastic", action="store_true",
                   help="run the elastic shrink/regrow e2e (2-process "
                        "gloo; its own tier-1 lane invocation)")
    p.add_argument("--overload", action="store_true",
                   help="run the serving overload storm e2e (priority "
                        "burst + replica kill; its own tier-1 lane "
                        "invocation)")
    p.add_argument("--integrity", action="store_true",
                   help="run the silent-corruption defense e2e (one "
                        "injected flip per corruption class; its own "
                        "tier-1 lane invocation)")
    p.add_argument("--oom", action="store_true",
                   help="run the memory-pressure e2e (one injected "
                        "RESOURCE_EXHAUSTED per recovery path: trainer "
                        "accum re-lower, serving shrink-and-retry, "
                        "pool-grow degradation, checkpoint snapshot "
                        "retry; its own tier-1 lane invocation)")
    p.add_argument("--durable", action="store_true",
                   help="run the durable-serving e2e (kill-9 journal "
                        "replay bit-exact, torn/CRC records skipped "
                        "with evidence, chaos-failed canary fleet "
                        "rollback with zero drops, lineage-gated "
                        "hot-swap; its own tier-1 lane invocation)")
    args = p.parse_args()
    worker = os.environ.get("CHAOS_SMOKE_WORKER")
    if worker == "durable_serve":
        return durable_worker(args.args[0], args.args[1])
    if worker == "hang":
        return hang_worker(args.args[0])
    if worker == "train":
        return train_worker(args.args[0], int(args.args[1]))
    if worker == "integrity_train":
        return integrity_train_worker(args.args[0], int(args.args[1]))
    if worker == "vote":
        return vote_worker()
    if args.integrity:
        if integrity_scenario():
            print("[chaos_smoke] integrity scenario FAILED")
            return 1
        return 0
    if args.oom:
        if mem_pressure():
            print("[chaos_smoke] oom scenario FAILED")
            return 1
        return 0
    if args.durable:
        if durable():
            print("[chaos_smoke] durable scenario FAILED")
            return 1
        return 0
    if args.elastic:
        if elastic():
            print("[chaos_smoke] elastic scenario FAILED")
            return 1
        return 0
    if args.overload:
        if overload():
            print("[chaos_smoke] overload scenario FAILED")
            return 1
        return 0
    failures = 0
    for name, fn in SCENARIOS:
        if args.only and name != args.only:
            continue
        failures += fn()
    if failures:
        print("[chaos_smoke] %d scenario(s) FAILED" % failures)
        return 1
    print("[chaos_smoke] all fault classes recovered")
    return 0


if __name__ == "__main__":
    sys.exit(main())
