"""Fault injection (mxnet_tpu/observability/chaos.py) and the recovery
machinery it proves out: deterministic rule firing, NaN step guards
that leave weights bit-identical, io retry-with-backoff, serving
dispatch-failure requeue, and the watchdog escalation policy.

Every scenario here is the in-process half of the robustness story;
the subprocess legs (kill -9 mid-save, SIGTERM preemption, crash +
resume-from-latest) live in tests/test_checkpoint.py and
tools/chaos_smoke.py.
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import io as mx_io
from mxnet_tpu import recordio
from mxnet_tpu.observability import chaos, watchdog
from mxnet_tpu.models import transformer as T


@pytest.fixture(autouse=True)
def _clean_chaos():
    chaos.reset()
    yield
    chaos.reset()


# ------------------------------------------------------------ the layer --

def test_off_by_default_and_no_op():
    assert not chaos.enabled()
    assert chaos.fire("kvstore.push") == ()
    assert chaos.stats["fired"] == 0


def test_spec_grammar():
    rules = chaos.parse_spec(
        "kvstore.*:delay:ms=250:at=3;io.read:error:count=2;"
        "trainer.grads:nan:every=4:count=0")
    assert [r.fault for r in rules] == ["delay", "error", "nan"]
    assert rules[0].ms == 250.0 and rules[0].at == 3
    assert rules[1].count == 2
    assert rules[2].every == 4 and rules[2].count == 0
    with pytest.raises(ValueError, match="unknown chaos fault"):
        chaos.parse_spec("site:explode")
    with pytest.raises(ValueError, match="key=value"):
        chaos.parse_spec("site:delay:ms")
    with pytest.raises(ValueError, match="unknown key"):
        chaos.parse_spec("site:delay:volume=11")


def test_occurrence_at_is_deterministic():
    r = chaos.inject("s", "nan", at=2)
    fired = [chaos.fire("s") for _ in range(5)]
    assert fired == [(), (), ("nan",), (), ()]
    assert r.fired == 1 and r.seen == 5
    assert chaos.stats["fired"] == 1 and chaos.stats["nan"] == 1


def test_every_with_unlimited_count():
    chaos.inject("s", "nan", every=2, count=0)
    fired = [bool(chaos.fire("s")) for _ in range(6)]
    assert fired == [True, False, True, False, True, False]


def test_glob_pattern_and_other_sites_untouched():
    chaos.inject("kvstore.*", "nan", count=0)
    assert chaos.fire("kvstore.pushpull_fused") == ("nan",)
    assert chaos.fire("serving.dispatch") == ()


def test_env_spec_fires_and_cache_tracks_changes(monkeypatch):
    monkeypatch.setenv("MXNET_CHAOS", "boom:error")
    assert chaos.enabled()
    with pytest.raises(chaos.ChaosError, match="injected fault"):
        chaos.fire("boom")
    monkeypatch.delenv("MXNET_CHAOS")
    assert not chaos.enabled()
    assert chaos.fire("boom") == ()


def test_rank_filter_skips_other_ranks():
    chaos.inject("s", "error", rank=7)        # this process is rank 0
    assert chaos.fire("s") == ()


def test_delay_and_hang_release():
    chaos.inject("slow", "delay", ms=60)
    t0 = time.perf_counter()
    assert chaos.fire("slow") == ("delay",)
    assert time.perf_counter() - t0 >= 0.05
    chaos.inject("stuck", "hang", ms=30000)
    threading.Timer(0.1, chaos.release).start()
    t0 = time.perf_counter()
    assert chaos.fire("stuck") == ("hang",)
    assert time.perf_counter() - t0 < 10.0


def test_chaos_error_is_oserror():
    assert issubclass(chaos.ChaosError, OSError)


# ------------------------------------------------------- the step guard --

def _tiny_gluon():
    """A net and its step through a store INSTANCE (the string spec
    resolves to no store on one worker), so the guard is held on the path
    where the allreduce and the update may live inside the store."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn
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

    def one_step():
        from mxnet_tpu import autograd
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(4)

    return net, one_step


def _weights(net):
    return {k: v.data().asnumpy().copy()
            for k, v in net.collect_params().items()}


def test_trainer_guard_nan_step_leaves_weights_bit_identical(monkeypatch):
    monkeypatch.setenv("MXNET_STEP_GUARD", "1")
    net, one_step = _tiny_gluon()
    one_step()                       # clean warmup step updates weights
    before = _weights(net)
    chaos.inject("trainer.grads", "nan", at=0)
    one_step()                       # poisoned: guard must skip
    after = _weights(net)
    for k in before:
        np.testing.assert_array_equal(before[k], after[k])
        assert np.isfinite(after[k]).all()
    assert chaos.stats["skipped_steps"] == 1
    one_step()                       # rule exhausted: training resumes
    resumed = _weights(net)
    assert any(not np.array_equal(before[k], resumed[k])
               for k in before)
    assert chaos.stats["skipped_steps"] == 1


def test_trainer_without_guard_is_poisoned(monkeypatch):
    """The counterfactual: the same injection without MXNET_STEP_GUARD
    corrupts the weights — proving the guard is what saves them."""
    monkeypatch.delenv("MXNET_STEP_GUARD", raising=False)
    net, one_step = _tiny_gluon()
    one_step()
    chaos.inject("trainer.grads", "nan", at=0)
    one_step()
    assert any(not np.isfinite(w).all()
               for w in _weights(net).values())


def test_module_guard_skips_nan_update(monkeypatch):
    monkeypatch.setenv("MXNET_STEP_GUARD", "1")
    from mxnet_tpu.module import Module
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, name="fc", num_hidden=2)
    sym = mx.sym.SoftmaxOutput(fc, name="softmax")
    mod = Module(sym, data_names=["data"], label_names=["softmax_label"])
    mod.bind(data_shapes=[("data", (4, 6))],
             label_shapes=[("softmax_label", (4,))])
    mod.init_params()
    mod.init_optimizer(kvstore="local",
                       optimizer_params=(("learning_rate", 0.1),))
    batch = mx_io.DataBatch(data=[mx.nd.random.uniform(shape=(4, 6))],
                            label=[mx.nd.zeros((4,))])

    def one_step():
        mod.forward(batch, is_train=True)
        mod.backward()
        mod.update()

    one_step()
    before = {k: v.asnumpy().copy()
              for k, v in mod.get_params()[0].items()}
    chaos.inject("module.grads", "nan", at=0)
    one_step()
    after = {k: v.asnumpy().copy()
             for k, v in mod.get_params()[0].items()}
    for k in before:
        np.testing.assert_array_equal(before[k], after[k])
    assert chaos.stats["skipped_steps"] == 1


def _tiny_cfg(**kw):
    kw.setdefault("vocab_size", 32)
    kw.setdefault("d_model", 16)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 1)
    kw.setdefault("d_ff", 32)
    kw.setdefault("max_len", 12)
    kw.setdefault("dtype", jnp.float32)
    return T.TransformerConfig(**kw)


def test_guarded_train_step_device_side():
    """make_train_step(guard=True): non-finite grads pass params AND
    momentum through bit-identically (device-side select, no host
    branch); finite steps match the unguarded trajectory exactly."""
    cfg = _tiny_cfg()
    tokens = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 12)),
        jnp.int32)
    params = T.init_params(cfg, seed=0)
    mom = T.init_momentum(params)
    plain = T.make_train_step(cfg, lr=0.1)
    guarded = T.make_train_step(cfg, lr=0.1, guard=True)

    p1, m1, l1 = plain(jax.tree.map(jnp.copy, params),
                       jax.tree.map(jnp.copy, mom), tokens)
    p2, m2, l2, skipped = guarded(jax.tree.map(jnp.copy, params),
                                  jax.tree.map(jnp.copy, mom), tokens)
    assert not bool(skipped)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # poison one leaf: loss goes non-finite, the whole update is a
    # pass-through (the NaN leaf included — nothing else may move)
    bad = jax.tree.map(jnp.copy, params)
    bad["embed"] = bad["embed"].at[0, 0].set(jnp.nan)
    bad_in = jax.tree.map(jnp.copy, bad)
    p3, m3, _l3, skipped = guarded(bad_in, jax.tree.map(jnp.copy, mom),
                                   tokens)
    assert bool(skipped)
    for a, b in zip(jax.tree.leaves(p3), jax.tree.leaves(bad)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for m in jax.tree.leaves(m3):
        assert float(jnp.abs(m).sum()) == 0.0


# ------------------------------------------------------------- io retry --

def _small_rec(tmp_path, n=6):
    path = str(tmp_path / "img.rec")
    idx = str(tmp_path / "img.idx")
    w = recordio.MXIndexedRecordIO(idx, path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, (8, 8, 3)).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i), i, 0), img, img_fmt=".npy"))
    w.close()
    return path, idx


def test_io_retry_recovers_from_transient_errors(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_IO_BACKOFF_MS", "1")
    path, idx = _small_rec(tmp_path)
    chaos.inject("io.read", "error", count=2)   # two transient failures
    it = mx_io.ImageRecordIter(path_imgrec=path, path_imgidx=idx,
                               data_shape=(3, 8, 8), batch_size=3)
    b = next(it)
    assert b.data[0].shape == (3, 3, 8, 8)
    assert chaos.stats["error"] == 2


def test_io_retry_exhaustion_names_path_and_batch(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_IO_BACKOFF_MS", "1")
    monkeypatch.setenv("MXNET_IO_RETRIES", "1")
    path, idx = _small_rec(tmp_path)
    it = mx_io.ImageRecordIter(path_imgrec=path, path_imgidx=idx,
                               data_shape=(3, 8, 8), batch_size=3)
    chaos.inject("io.read", "error", count=0)   # permanent failure
    with pytest.raises(IOError, match="after 2 attempt"):
        next(it)
    try:
        chaos.reset()
        chaos.inject("io.read", "error", count=0)
        next(it)
    except IOError as e:
        assert "img.rec" in str(e) and "batch=1" in str(e)


def test_io_retries_zero_disables_retry(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_IO_RETRIES", "0")
    path, idx = _small_rec(tmp_path)
    it = mx_io.ImageRecordIter(path_imgrec=path, path_imgidx=idx,
                               data_shape=(3, 8, 8), batch_size=3)
    chaos.inject("io.read", "error")
    with pytest.raises(IOError, match="after 1 attempt"):
        next(it)


# ------------------------------------------------------ serving requeue --

def _serving_setup(seed=0):
    cfg = _tiny_cfg(vocab_size=41, max_len=32)
    params = T.init_params(cfg, seed=seed)
    rng = np.random.RandomState(seed)
    jobs = [(list(rng.randint(1, 41, 4)), 6) for _ in range(3)]
    solo = {}
    for j, (prompt, n_new) in enumerate(jobs):
        out = T.generate(params, jnp.asarray([prompt], jnp.int32),
                         n_new, cfg, greedy=True)
        solo[j] = np.asarray(out)[0].tolist()
    return cfg, params, jobs, solo


@pytest.mark.parametrize("depth", [1, 2])
def test_serving_dispatch_failure_requeues(depth):
    """An injected dispatch failure frees the lanes and requeues the
    live requests; every greedy stream still matches solo generate()
    bit-exactly — the batcher recovers instead of wedging."""
    from mxnet_tpu.models.serving import ContinuousBatcher
    cfg, params, jobs, solo = _serving_setup()
    chaos.inject("serving.dispatch", "error", at=1)
    srv = ContinuousBatcher(params, cfg, max_batch=2,
                            pipeline_depth=depth)
    results, order = srv.run(jobs)
    assert len(results) == len(jobs)
    for j, rid in enumerate(order):
        assert results[rid] == solo[j], \
            "stream diverged after requeue (job %d)" % j
    assert chaos.stats["error"] == 1


def test_serving_repeated_failure_reraises():
    from mxnet_tpu.models.serving import ContinuousBatcher
    cfg, params, jobs, _ = _serving_setup()
    chaos.inject("serving.dispatch", "error", count=0)  # permanent
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    with pytest.raises(chaos.ChaosError):
        srv.run(jobs[:1])


# ------------------------------------------------- watchdog escalation --

def test_watchdog_action_env(monkeypatch):
    monkeypatch.delenv("MXNET_OBS_WATCHDOG_ACTION", raising=False)
    assert watchdog.action() == "report"
    monkeypatch.setenv("MXNET_OBS_WATCHDOG_ACTION", "checkpoint")
    assert watchdog.action() == "checkpoint"
    monkeypatch.setenv("MXNET_OBS_WATCHDOG_ACTION", "nonsense")
    assert watchdog.action() == "report"


def _expired_watchdog(action, hook=None, abort=None):
    clock = [0.0]
    wd = watchdog.CollectiveWatchdog(
        timeout=5.0, clock=lambda: clock[0], rank=0, nprocs=1,
        thread=False, emit=lambda s: None, action=action, abort=abort,
        emergency_hook=hook)
    wd.arm("kvstore.pushpull_fused", {"bucket": 0, "lane": "float32"})
    clock[0] = 10.0
    return wd


def test_watchdog_report_action_never_aborts():
    aborts = []
    wd = _expired_watchdog("report", abort=lambda c: aborts.append(c))
    with pytest.warns(RuntimeWarning):
        reports = wd.check()
    assert len(reports) == 1 and aborts == []


def test_watchdog_abort_action_exits_after_postmortem():
    aborts = []
    wd = _expired_watchdog("abort", abort=lambda c: aborts.append(c))
    with pytest.warns(RuntimeWarning):
        wd.check()
    assert aborts == [watchdog.ABORT_EXIT_CODE]
    assert len(wd.reports) == 1          # post-mortem dumped FIRST


def test_watchdog_checkpoint_action_runs_hook_then_aborts():
    calls, aborts = [], []
    wd = _expired_watchdog(
        "checkpoint",
        hook=lambda reason: calls.append(reason) or "/ck",
        abort=lambda c: aborts.append(c))
    with pytest.warns(RuntimeWarning):
        wd.check()
    assert calls == ["watchdog:kvstore.pushpull_fused"]
    assert aborts == [watchdog.ABORT_EXIT_CODE]


def test_watchdog_checkpoint_action_produces_loadable_resume_point(
        tmp_path, monkeypatch):
    """The satellite scenario, in process: a hung collective under
    action=checkpoint commits a real emergency checkpoint through the
    installed provider, and that checkpoint resumes training."""
    from mxnet_tpu.models import checkpoint as ck
    cfg = _tiny_cfg()
    params = T.init_params(cfg, seed=3)
    mom = T.init_momentum(params)
    ckdir = str(tmp_path / "hangck")
    ck.install_emergency_checkpoint(
        ckdir, lambda: {"cfg": cfg, "params": params, "momentum": mom,
                        "step": 9},
        on_sigterm=False, on_watchdog=True)
    try:
        aborts = []
        wd = _expired_watchdog("checkpoint",
                               abort=lambda c: aborts.append(c))
        with pytest.warns(RuntimeWarning):
            wd.check()
        assert aborts == [watchdog.ABORT_EXIT_CODE]
        cfg2, p2, m2, step = ck.restore_train_state(ckdir, mesh=None)
        assert step == 9 and cfg2 == cfg
        step_fn = T.make_train_step(cfg2, lr=0.1)
        tokens = jnp.zeros((2, cfg.max_len), jnp.int32)
        _, _, loss = step_fn(p2, m2, tokens)
        assert np.isfinite(float(loss))
        meta = ck.load_checkpoint(ckdir)[4]
        assert meta["emergency"].startswith("watchdog:")
    finally:
        ck.uninstall_emergency_checkpoint()


def test_watchdog_escalates_once():
    aborts = []
    wd = _expired_watchdog("abort", abort=lambda c: aborts.append(c))
    with pytest.warns(RuntimeWarning):
        wd.check()
    wd.arm("kvstore.push", {})
    # second expiry: post-mortem yes, second abort no
    with pytest.warns(RuntimeWarning):
        wd.check(now=99.0)
    assert aborts == [watchdog.ABORT_EXIT_CODE]


def test_watchdog_hang_under_injected_delay(monkeypatch):
    """End to end on the real singleton path: an injected collective
    delay longer than the timeout produces a post-mortem naming the
    site (action stays report — nothing aborts)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    monkeypatch.setenv("MXNET_OBS_COLLECTIVE_TIMEOUT", "0.15")
    monkeypatch.delenv("MXNET_OBS_WATCHDOG_ACTION", raising=False)
    reports = []
    wd = watchdog.CollectiveWatchdog(emit=reports.append)
    monkeypatch.setattr(watchdog, "_WD", wd)
    chaos.inject("kvstore.push", "delay", ms=600)
    kv = mx.kvstore.create("device")
    kv.init(0, mx.nd.ones((4,)))
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("ignore", RuntimeWarning)
        kv.push(0, mx.nd.ones((4,)))
    assert any("post-mortem" in r for r in reports), reports
    assert any("kvstore.push" in r for r in reports)
