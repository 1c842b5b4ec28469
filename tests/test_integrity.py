"""Silent-corruption defense (mxnet_tpu/observability/integrity.py):
fingerprint determinism across dtypes and shardings, the cross-rank
divergence vote (injected all-gather + a 3-process gloo e2e marked
``slow``), the replay audit catching an injected gradient-bucket flip,
checkpoint lineage verify/refuse/fallback, the taxonomy-46 supervisor
leg, and the off-path identity contract (MXNET_INTEGRITY unset: one
guarded branch, dispatch count and step numerics bit-identical)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.models import transformer as T
from mxnet_tpu.models import checkpoint as ckpt
from mxnet_tpu.models.checkpoint import (
    save_checkpoint, load_checkpoint, verify_lineage, resume_from_latest,
    resume_elastic, save_shard_checkpoint, CheckpointCorrupt)
from mxnet_tpu.observability import chaos, integrity
from mxnet_tpu.parallel import make_mesh, elastic

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))


@pytest.fixture(autouse=True)
def _clean():
    chaos.reset()
    integrity._reset_for_tests()
    ckpt._lineage[0] = None
    yield
    chaos.reset()
    integrity._reset_for_tests()
    ckpt._lineage[0] = None


@pytest.fixture
def integrity_on(monkeypatch):
    monkeypatch.setenv("MXNET_INTEGRITY", "1")
    monkeypatch.setenv("MXNET_INTEGRITY_ACTION", "warn")
    yield monkeypatch


def _cfg(**kw):
    kw.setdefault("vocab_size", 41)
    kw.setdefault("d_model", 16)
    kw.setdefault("n_heads", 2)
    kw.setdefault("n_layers", 1)
    kw.setdefault("d_ff", 32)
    kw.setdefault("max_len", 16)
    kw.setdefault("dtype", jnp.float32)
    return T.TransformerConfig(**kw)


# ------------------------------------------------------- the digest --

def test_off_by_default():
    assert not integrity.enabled()
    integrity.step_boundary([("w", jnp.zeros(4))])    # guarded no-op
    assert integrity.stats == {"votes": 0, "audits": 0, "detected": 0,
                               "quarantines": 0}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16",
                                   "int32", "uint8"])
def test_digest_deterministic_and_flip_sensitive(dtype):
    x = jnp.asarray(
        np.random.RandomState(7).uniform(-3, 3, (5, 9)) * 10).astype(dtype)
    d1 = integrity.digest(x)
    d2 = integrity.digest(x)
    assert d1.dtype == np.float32 and d1.shape == (4,)
    assert d1.tobytes() == d2.tobytes()
    # ANY single-bit flip must change the fingerprint (the xor lanes
    # catch flips the sum can't see)
    flipped = chaos._flip_in_array(x, bit=3, elem=11)
    assert integrity.digest(flipped).tobytes() != d1.tobytes()


def test_digest_sharding_invariant():
    """The fingerprint is a property of the VALUE, not the layout:
    replicated and dp-sharded copies of one array digest identically —
    two ranks holding equal weights always vote together."""
    mesh = make_mesh({"dp": 8})
    x = jnp.asarray(np.random.RandomState(3).rand(8, 16), jnp.float32)
    import jax
    sharded = jax.device_put(x, NamedSharding(mesh, P("dp", None)))
    replicated = jax.device_put(x, NamedSharding(mesh, P(None, None)))
    d0 = integrity.digest(x)
    assert integrity.digest(sharded).tobytes() == d0.tobytes()
    assert integrity.digest(replicated).tobytes() == d0.tobytes()


def test_combine_is_exact_for_xor_lanes():
    a = integrity.digest(jnp.asarray([1.5, -2.25], jnp.float32))
    b = integrity.digest(jnp.asarray([np.pi], jnp.float32))
    c = integrity.combine([a, b])
    assert int(c[2]) == int(a[2]) ^ int(b[2])
    assert int(c[3]) == int(a[3]) ^ int(b[3])
    # xor halves stay < 2^16: exactly representable as float32
    assert 0 <= int(c[2]) < 1 << 16 and 0 <= int(c[3]) < 1 << 16


def test_tree_fingerprint_stable_and_sensitive():
    rng = np.random.RandomState(0)
    w, b = rng.rand(3, 4).astype(np.float32), rng.rand(4).astype(np.float32)
    fp = integrity.tree_fingerprint({"w": w, "b": b})
    assert fp == integrity.tree_fingerprint({"b": b, "w": w})  # sorted
    assert len(fp) == 8 and int(fp, 16) >= 0
    w2 = w.copy()
    w2[1, 2] = np.float32(w2[1, 2] + 1e-3)
    assert integrity.tree_fingerprint({"w": w2, "b": b}) != fp
    assert integrity.tree_fingerprint({"v": w, "b": b}) != fp  # renamed


def _items(seed=0):
    rng = np.random.RandomState(seed)
    return [("p0", jnp.asarray(rng.rand(6, 4), jnp.float32)),
            ("p1", jnp.asarray(rng.rand(8), jnp.float32))]


def test_param_fingerprints_lane_evidence():
    vec, lanes = integrity.param_fingerprints(_items())
    assert vec.shape == (4 * len(lanes),) and vec.dtype == np.float32
    keys = [k for _b, _d, ks in lanes for k in ks]
    assert sorted(keys) == ["p0", "p1"]
    # deterministic across calls (cached plan included)
    vec2, _ = integrity.param_fingerprints(_items())
    assert vec.tobytes() == vec2.tobytes()


# ------------------------------------------------------- the vote --

def _gather_rows(rows):
    """Fake ``dist._allgather_vec``: this 'rank' contributes vec, the
    others are injected rows."""
    def allgather(vec):
        return np.stack([np.asarray(r, np.float32) if r is not None
                         else vec for r in rows])
    return allgather


def _tampered_vec():
    items = _items()
    bad = [(k, chaos._flip_in_array(v, bit=30, elem=2) if k == "p0"
            else v) for k, v in items]
    vec, _ = integrity.param_fingerprints(bad)
    return vec


def test_vote_majority_flags_minority():
    bad = _tampered_vec()
    out = integrity.exchange_and_vote(
        _items(), allgather=_gather_rows([None, bad, None]), rank=0)
    assert out["indeterminate"] == []
    assert len(out["drift"]) == 1
    ev = out["drift"][0]
    assert ev["kind"] == "replica_drift" and ev["drifted"] == [1]
    assert "p0" in ev["keys"] and "bucket" in ev and "lane" in ev
    assert set(ev["fingerprints"]) == {"0", "1"}


def test_vote_two_rank_tie_is_indeterminate():
    out = integrity.exchange_and_vote(
        _items(), allgather=_gather_rows([None, _tampered_vec()]), rank=0)
    assert out["drift"] == []
    assert len(out["indeterminate"]) == 1
    assert out["indeterminate"][0]["disagreeing"] == [0, 1]


def test_step_boundary_self_minority_quarantines(integrity_on, tmp_path,
                                                 capfd):
    integrity_on.setenv("MXNET_INTEGRITY_EVERY", "1")
    integrity_on.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "0")
    integrity_on.setenv("MXNET_INTEGRITY_ACTION", "quarantine")
    integrity_on.setenv("MXNET_ELASTIC_DIR", str(tmp_path))
    integrity_on.setenv("MXNET_TPU_PROC_ID", "1")
    integrity_on.setenv("MXNET_ELASTIC_GENERATION", "0")
    codes = []
    # THIS rank (1) is the minority: ranks 0 and 2 agree
    bad = _tampered_vec()
    items = _items()
    clean, _ = integrity.param_fingerprints(items)

    def allgather(vec):
        return np.stack([clean, bad, clean])

    tampered = [(k, chaos._flip_in_array(v, bit=30, elem=2)
                 if k == "p0" else v) for k, v in items]
    integrity.step_boundary(tampered, allgather=allgather, rank=1,
                            world=3, exit=codes.append)
    assert codes == [integrity.QUARANTINE_EXIT_CODE]
    assert integrity.stats["quarantines"] == 1
    recs = elastic.read_quarantine_records(str(tmp_path), 0)
    assert len(recs) == 1 and recs[0]["rank"] == 1
    assert recs[0]["evidence"]["kind"] == "replica_drift"
    assert recs[0]["evidence"]["drifted"] == [1]
    assert "QUARANTINE" in capfd.readouterr().err


def test_step_boundary_other_rank_drift_only_reports(integrity_on,
                                                     capfd):
    integrity_on.setenv("MXNET_INTEGRITY_EVERY", "1")
    integrity_on.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "0")
    codes = []
    integrity.step_boundary(
        _items(), allgather=_gather_rows([None, _tampered_vec(), None]),
        rank=0, world=3, exit=codes.append)
    assert codes == []                  # only the corrupt rank leaves
    assert integrity.stats["detected"] == 1
    err = capfd.readouterr().err
    assert "replica_drift" in err and "'drifted': [1]" in err


def test_vote_cadence_and_single_process_skip(integrity_on):
    integrity_on.setenv("MXNET_INTEGRITY_EVERY", "2")
    integrity_on.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "0")
    calls = []

    def allgather(vec):
        calls.append(1)
        return vec[None]

    for _ in range(4):      # steps 0..3 -> vote armed at 0 and 2
        integrity.step_boundary(_items(), allgather=allgather, rank=0,
                                world=3)
    assert len(calls) == 2
    # world 1 and no injected transport: the vote is skipped entirely
    integrity._reset_for_tests()
    for _ in range(2):
        integrity.step_boundary(_items(), world=1)
    assert integrity.stats["votes"] == 0


# ----------------------------------------------------- replay audit --

def test_replay_audit_catches_recorded_corruption(integrity_on, capfd):
    integrity_on.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "1")
    integrity_on.setenv("MXNET_INTEGRITY_EVERY", "0")
    clean = [jnp.asarray(np.random.RandomState(1).rand(32), jnp.float32)]
    corrupted = [chaos._flip_in_array(clean[0], bit=28, elem=5)]
    assert integrity.audit_armed()
    integrity.note_lane(0, "float32", corrupted, lambda: clean)
    integrity.step_boundary()
    assert integrity.stats["audits"] == 1
    assert integrity.stats["detected"] == 1
    err = capfd.readouterr().err
    assert "replay_mismatch" in err and "'bucket': 0" in err


def test_replay_audit_clean_lanes_pass(integrity_on):
    integrity_on.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "1")
    integrity_on.setenv("MXNET_INTEGRITY_EVERY", "0")
    clean = [jnp.asarray(np.random.RandomState(1).rand(32), jnp.float32)]
    integrity.note_lane(0, "float32", clean, lambda: list(clean))
    integrity.step_boundary()
    assert integrity.stats["audits"] == 1
    assert integrity.stats["detected"] == 0


def _tiny_train(steps=2, lr=0.05):
    """Two steps of a deterministic dense net through the fused kvstore
    path; returns (trainer, final weights as one flat dict)."""
    np.random.seed(0)
    mx.random.seed(0)
    net = gluon.nn.Sequential()
    with net.name_scope():
        net.add(nn.Dense(8, activation="relu"))
        net.add(nn.Dense(4))
    net.initialize()
    # a store INSTANCE: the string spec resolves to no store on one worker
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr},
                            kvstore=mx.kvstore.create("device"))
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.uniform(size=(8, 10)).astype(np.float32))
    y = mx.nd.array(rng.uniform(size=(8, 4)).astype(np.float32))
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(8)
    # strip the global block-name counter (sequentialN_...) so weights
    # from two independently built nets compare by role
    weights = {name.split("_", 1)[1]: np.asarray(p.data()._data)
               for name, p in net.collect_params().items()}
    return trainer, weights


def test_trainer_replay_audit_detects_injected_grad_flip(
        integrity_on, capfd):
    """The acceptance flip class 'gradient bucket': a bitflip injected
    into the packed flats feeding the fused all-reduce is caught by the
    replay audit within the same step, with bucket evidence."""
    integrity_on.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "1")
    integrity_on.setenv("MXNET_INTEGRITY_EVERY", "0")
    integrity_on.setenv("MXNET_CHAOS",
                        "kvstore.bucket.pack:bitflip:at=0:bit=30:elem=3")
    _tiny_train(steps=1)
    assert integrity.stats["audits"] == 1
    assert integrity.stats["detected"] >= 1
    err = capfd.readouterr().err
    assert "replay_mismatch" in err


# -------------------------------------------------- off-path identity --

def test_off_path_dispatch_count_and_numerics_identical(monkeypatch):
    """The PR 2 contract: arming the detectors (action=warn, single
    process — the audit runs, the vote is skipped) must not add or
    remove a single collective dispatch nor perturb step numerics by
    one bit relative to MXNET_INTEGRITY unset."""
    for k in ("MXNET_INTEGRITY", "MXNET_INTEGRITY_EVERY",
              "MXNET_INTEGRITY_REPLAY_EVERY", "MXNET_INTEGRITY_ACTION"):
        monkeypatch.delenv(k, raising=False)
    t_off, w_off = _tiny_train()
    stats_off = dict(t_off._kvstore.dispatch_stats)
    assert integrity.stats["audits"] == 0    # hooks truly off

    integrity._reset_for_tests()
    monkeypatch.setenv("MXNET_INTEGRITY", "1")
    monkeypatch.setenv("MXNET_INTEGRITY_ACTION", "warn")
    monkeypatch.setenv("MXNET_INTEGRITY_EVERY", "1")
    monkeypatch.setenv("MXNET_INTEGRITY_REPLAY_EVERY", "1")
    t_on, w_on = _tiny_train()
    stats_on = dict(t_on._kvstore.dispatch_stats)
    assert integrity.stats["audits"] >= 1    # detectors actually ran
    assert integrity.stats["detected"] == 0  # and found nothing

    assert stats_on == stats_off
    assert sorted(w_on) == sorted(w_off)
    for name in w_off:
        assert w_on[name].tobytes() == w_off[name].tobytes(), name


# ------------------------------------------------- checkpoint lineage --

def test_lineage_chain_verified(tmp_path):
    ck = str(tmp_path / "ck")
    cfg = _cfg()
    for step in (1, 2, 3):
        save_checkpoint(ck, cfg, T.init_params(cfg, seed=step),
                        step=step, keep=3)
    chain = verify_lineage(ck, deep=True)
    assert [e["step"] for e in chain] == [3, 2, 1]
    assert all(e["status"] == "verified" for e in chain)
    assert [e["parent"] for e in chain] == ["verified", "verified",
                                            "root"]


def test_manifest_fingerprint_tamper_refused_and_falls_back(tmp_path):
    ck = str(tmp_path / "ck")
    cfg = _cfg()
    save_checkpoint(ck, cfg, T.init_params(cfg, seed=1), step=1, keep=2)
    save_checkpoint(ck, cfg, T.init_params(cfg, seed=2), step=2, keep=2)
    # tamper the newest manifest's recorded fingerprint (pointer AND
    # its retained twin — one checkpoint, two names)
    for name in os.listdir(ck):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(ck, name)) as f:
            m = json.load(f)
        if m.get("step") == 2 and "param_fingerprint" in m:
            m["param_fingerprint"] = "deadbeef"
            with open(os.path.join(ck, name), "w") as f:
                json.dump(m, f)
    with pytest.raises(CheckpointCorrupt, match="fingerprint"):
        load_checkpoint(ck, fallback=False)
    with pytest.warns(RuntimeWarning, match="falling back"):
        _cfg_r, _p, _mom, step = resume_from_latest(ck)
    assert step == 1                     # the newest VERIFIED ancestor


def test_verify_lineage_detects_parent_splice(tmp_path):
    ck = str(tmp_path / "ck")
    cfg = _cfg()
    save_checkpoint(ck, cfg, T.init_params(cfg, seed=1), step=1, keep=2)
    save_checkpoint(ck, cfg, T.init_params(cfg, seed=2), step=2, keep=2)
    # rewrite step 1's retained manifest: same JSON, different text ->
    # its digest no longer matches what step 2 recorded at save time
    for name in os.listdir(ck):
        if name.startswith("manifest-") and name.endswith(".json"):
            with open(os.path.join(ck, name)) as f:
                m = json.load(f)
            if m.get("step") == 1:
                with open(os.path.join(ck, name), "w") as f:
                    json.dump(m, f, indent=4, sort_keys=True)
    chain = verify_lineage(ck)
    newest = chain[0]
    assert newest["step"] == 2
    assert newest["parent"] == "mismatch"
    assert newest["status"] == "parent-mismatch"


def test_checkpoint_byte_flip_detected_and_fallback(tmp_path):
    """The acceptance flip class 'checkpoint byte': the chaos
    checkpoint.bytes site flips one bit of the committed arrays file;
    the load refuses it by name and resumes from the older verified
    checkpoint."""
    ck = str(tmp_path / "ck")
    cfg = _cfg()
    save_checkpoint(ck, cfg, T.init_params(cfg, seed=1), step=1, keep=2)
    chaos.install("checkpoint.bytes:bitflip:at=0:elem=4096:bit=6")
    try:
        save_checkpoint(ck, cfg, T.init_params(cfg, seed=2), step=2,
                        keep=2)
    finally:
        chaos.reset()
    with pytest.raises(CheckpointCorrupt):
        load_checkpoint(ck, fallback=False)
    with pytest.warns(RuntimeWarning, match="recovered from"):
        _cfg_r, _p, _mom, step, _meta = load_checkpoint(ck)
    assert step == 1


def test_resume_elastic_falls_back_to_verified_full(tmp_path):
    """A corrupt newest shard set must not serve the resume: the
    elastic entry point falls back to the newest verified full
    checkpoint (the quarantine-recovery path)."""
    ck = str(tmp_path / "ck")
    cfg = _cfg()
    params = T.init_params(cfg, seed=1)
    save_checkpoint(ck, cfg, params, step=5, keep=2)
    save_shard_checkpoint(ck, cfg, T.init_params(cfg, seed=2), step=7,
                          rank=0, world=1, generation=3)
    shard = [n for n in os.listdir(ck) if n.startswith("shard-arrays-")]
    assert shard
    with open(os.path.join(ck, shard[0]), "r+b") as f:
        f.seek(0, os.SEEK_END)
        mid = f.tell() // 2          # well inside some member's bytes
        f.seek(mid)
        span = f.read(64)
        f.seek(mid)
        f.write(bytes(b ^ 0x5A for b in span))
    with pytest.warns(RuntimeWarning,
                      match="newest verified full checkpoint"):
        _cfg_r, p_r, _mom, step, extras = resume_elastic(ck)
    assert step == 5 and extras == {}
    flat_want, flat_got = {}, {}
    ckpt._flatten(params, "p", flat_want)
    ckpt._flatten(p_r, "p", flat_got)
    for k in flat_want:
        assert np.asarray(flat_got[k]).tobytes() == \
            np.asarray(flat_want[k]).tobytes()


# --------------------------------------------- the supervisor leg (46) --

def test_classify_taxonomy_precedence():
    import elastic_launch
    assert elastic_launch.classify([0, 0]) == "done"
    assert elastic_launch.classify([0, 46]) == "quarantine"
    assert elastic_launch.classify([45, 46]) == "quarantine"
    assert elastic_launch.classify([44, 46]) == "shrink"
    assert elastic_launch.classify([0, 45]) == "boundary"
    assert elastic_launch.classify([43, 46]) == "quarantine"
    assert elastic_launch.classify([1, 46]) == "quarantine"


SUPERVISOR_WORKER = r'''
import json, os, sys
gen = int(os.environ["MXNET_ELASTIC_GENERATION"])
rank = int(os.environ["MXNET_TPU_PROC_ID"])
d = os.environ["MXNET_ELASTIC_DIR"]
if gen == 0 and rank == 1:
    rec = {"rank": 1, "generation": 0, "host": "testhost:rank1",
           "wall": 0.0,
           "evidence": {"kind": "replay_mismatch", "bucket": 0,
                        "lane": "float32"}}
    with open(os.path.join(d, "quarantine.g0.rank1.json"), "w") as f:
        json.dump(rec, f)
    sys.exit(46)
if gen <= 1:
    sys.exit(45)
sys.exit(0)
'''


def test_supervisor_quarantine_and_cooldown(tmp_path, capsys):
    """Exit 46 at generation 0: the supervisor prints the sideband
    evidence, removes the rank, resumes at world 1, and holds the host
    out of the next boundary regrow (cooldown)."""
    import elastic_launch
    script = tmp_path / "worker.py"
    script.write_text(SUPERVISOR_WORKER)
    rc = elastic_launch.main([
        "-n", "2", "--max-restarts", "3",
        "--quarantine-cooldown", "2",
        "--elastic-dir", str(tmp_path / "sideband"),
        "--", sys.executable, str(script)])
    out = capsys.readouterr().out
    assert rc == 0
    assert "-> quarantine" in out
    assert "quarantine evidence: rank 1 (testhost:rank1)" in out
    assert "replay_mismatch" in out
    assert "host testhost:rank1 on cooldown until generation 3" in out
    assert "relaunching at world 1 from the last verified checkpoint" \
        in out
    assert "regrow held back by cooldown" in out
    assert "job complete" in out


# ----------------------------------------- 3-process gloo vote (slow) --

VOTE_WORKER = r'''
import os, sys
sys.path.insert(0, %(root)r)
os.environ["MXNET_INTEGRITY"] = "1"
os.environ["MXNET_INTEGRITY_EVERY"] = "1"
os.environ["MXNET_INTEGRITY_REPLAY_EVERY"] = "0"
os.environ["MXNET_INTEGRITY_ACTION"] = "warn"
os.environ["MXNET_CHAOS"] = "trainer.weights:bitflip:rank=1:at=0:bit=30"
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
net = gluon.nn.Sequential()
with net.name_scope():
    net.add(nn.Dense(8, activation="relu"))
    net.add(nn.Dense(4))
net.initialize()
trainer = gluon.Trainer(net.collect_params(), "sgd",
                        {"learning_rate": 0.05},
                        kvstore="dist_tpu_sync")
loss_fn = gluon.loss.L2Loss()
rng = np.random.RandomState(0)            # same data on every rank
x = mx.nd.array(rng.uniform(size=(8, 10)).astype(np.float32))
y = mx.nd.array(rng.uniform(size=(8, 4)).astype(np.float32))
for step in range(2):
    with autograd.record():
        loss = loss_fn(net(x), y)
    loss.backward()
    trainer.step(8)
assert integrity.stats["votes"] >= 1
if rank == 1:
    assert integrity.stats["detected"] >= 1, "flipped rank saw no verdict"
print("VOTE-RANK-OK", rank)
'''


@pytest.mark.slow
def test_three_process_vote_names_flipped_rank(tmp_path):
    """A replicated weight flipped on exactly one of three gloo ranks:
    the fingerprint vote's majority names rank 1 as replica drift with
    bucket/lane evidence, on every rank's stderr."""
    script = tmp_path / "worker.py"
    script.write_text(VOTE_WORKER % {"root": ROOT})
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT})
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools/launch.py"), "-n",
         "3", "--launcher", "local", sys.executable, str(script)],
        capture_output=True, text=True, timeout=420, env=env, cwd=ROOT)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-3000:])
    assert r.stdout.count("VOTE-RANK-OK") == 3
    assert "replica_drift" in r.stderr
    assert "'drifted': [1]" in r.stderr
