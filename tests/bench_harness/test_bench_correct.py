"""How `correct` is decided, at a size a test run can hold (CPU, tiny
widths): the program passes its limits, the control (the reference computed
in float8, the step below bfloat16) fails one, and a run whose timed path
is broken underneath comes out not correct. The chip readings that set the
real cells' limits are in PERF.md section 2."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, manifest, run
from chipbench.reference import cerebras_gpt as ref
from chipbench.runners import serve_lm, train_lm

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
LM = json.load(open(os.path.join(HERE, "tiny", "lm.json")))
# tiny-size limits, set as the real ones are: above the program's largest
# over seeds 1-3 (grad 0.0023, served gap 0.0008) and below the control's
# smallest (grad 0.0080, served gap 0.0286); the delta norm and the loss
# are held against an unchanged state / a dropped part of the batch
TINY_TRAIN = {"loss_gap": 1e-3, "grad_norm_gap": 5e-3, "delta_norm_gap": 0.1}
TINY_SERVE = {"served_logit_gap": 5e-3}
TRAIN_TRAFFIC = dict(manifest.load_traffic("steps-8k"), batch=4, seq=64,
                     fetch_every=3, trace_steps=2, trace_reserve_s=1)
SERVE_TRAFFIC = dict(
    manifest.load_traffic("closed24"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 40},
    output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)


def _first_steps(seed):
    s = train_lm.Session(LM, TRAIN_TRAFFIC, seed)
    got = {"losses": []}
    for i in range(3):
        got["losses"].append(s.fetch(s.step()))
        if i == 0:
            got["grad_norms"] = s.first_grad_norms()
    got["delta_norms"] = s.delta_norms()
    return s, got


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_training_program_passes_and_float8_control_fails(seed):
    s, got = _first_steps(seed)
    reference = s.reference()
    sound = compare.training_checks(got, reference, TINY_TRAIN)
    assert all(c["ok"] for c in sound), sound
    control = compare.training_checks(s.reference("fp8"), reference,
                                      TINY_TRAIN)
    assert not all(c["ok"] for c in control), control


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_serving_float8_control_fails(seed):
    toks = np.random.RandomState(seed).randint(1, 256, (60,)).astype(np.int32)
    s = serve_lm.Session(dict(LM, runner="serve_lm"), SERVE_TRAFFIC, seed)
    rid = s.admit(toks[:20], 40)
    done = {}
    while rid not in done:
        done.update(s.step())
    served = done[rid]
    out = s.reference([(20, served)], operand="fp8")[0]
    sound = compare.serving_checks([out["gaps"]], 0, 1, TINY_SERVE)
    assert all(c["ok"] for c in sound), sound
    control = compare.serving_checks([out["control_gaps"]], 0, 1, TINY_SERVE)
    assert not control[0]["ok"], control


def _args(seed, trace=0, seconds=1.0):
    return argparse.Namespace(seed=seed, seconds=seconds, trace=trace)


def _run_train(monkeypatch=None, **kw):
    cell = MAN.cell("cerebras-gpt-1.3b-train-8k")
    return run.run_cell(MAN, cell, _args(5, **kw), config=LM,
                        traffic=TRAIN_TRAFFIC, limits=TINY_TRAIN)


def _run_serve(**kw):
    cell = MAN.cell("cerebras-gpt-1.3b-serve-closed24")
    return run.run_cell(MAN, cell, _args(5, **kw),
                        config=dict(LM, runner="serve_lm"),
                        traffic=SERVE_TRAFFIC, limits=TINY_SERVE)


def test_a_sound_training_run_is_correct():
    r = _run_train()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"train_tok_s", "setup_s"}
    assert r["metrics"]["train_tok_s"]["unit"] == "tokens/s"
    assert set(r["device"]) == {"platform", "kind", "count",
                                "memory_peak_bytes"}


def test_a_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    build = train_lm.build

    def broken_build(*a):
        s = build(*a)
        real = s._step

        def step(params, mom, tokens):
            copy = lambda t: jax.tree.map(jnp.copy, t)
            _, mom, loss = real(copy(params), copy(mom), tokens)
            return params, mom, loss
        s._step = step
        return s
    monkeypatch.setattr(train_lm, "build", broken_build)
    r = _run_train()
    assert not r["correct"]


def test_a_step_on_half_the_batch_is_not_correct(monkeypatch):
    build = train_lm.build

    def broken_build(*a):
        s = build(*a)
        s.tokens = jnp.concatenate([s.tokens[:2], s.tokens[:2]])
        return s
    monkeypatch.setattr(train_lm, "build", broken_build)
    assert not _run_train()["correct"]


def test_a_sound_serving_run_is_correct():
    r = _run_serve()
    assert r["correct"] and r["failed"] == 0
    assert set(r["metrics"]) == {"serve_tok_s", "serve_itl_p95_ms", "setup_s"}


def test_a_traced_serving_run_reports_the_layer_metrics():
    r = _run_serve(trace=1)
    assert {"dispatches_per_token.serve", "ttft_p50_ms.serve",
            "device_idle.serve"} == set(r["metrics"])
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
    assert not os.path.exists(os.path.join(
        manifest.ROOT, ".chipbench_out", "trace",
        "cerebras-gpt-1.3b-serve-closed24"))


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    build = serve_lm.build

    def broken_build(*a):
        s = build(*a)
        real = s.srv.step

        def step():
            out = real()
            for toks in out.values():
                toks[-2] = (toks[-2] + 1) % LM["vocab_size"]
            return out
        s.srv.step = step
        return s
    monkeypatch.setattr(serve_lm, "build", broken_build)
    assert not _run_serve()["correct"]


def test_worst_leaf_gap_is_measured_against_the_median_leaf():
    ref_norms = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    gap, leaf = compare.worst_leaf_gap({"a": 1.1, "b": 2.0, "tiny": 5e-9},
                                       ref_norms)
    assert leaf == "a" and gap == pytest.approx(0.1)
    gap, _ = compare.worst_leaf_gap({"a": 1.0}, ref_norms)
    assert gap == float("inf")
    gap, _ = compare.worst_leaf_gap({"a": float("nan"), "b": 2.0,
                                     "tiny": 0.0}, ref_norms)
    assert not gap <= 1e9


def test_reference_weights_are_the_seeds():
    a = ref.init_weights(LM, 2 ** 31 + 7)
    b = ref.init_weights(LM, 2 ** 31 + 7)
    c = ref.init_weights(LM, 2 ** 31 + 8)
    assert all(bool(jnp.all(a[k] == b[k])) for k in a)
    assert not bool(jnp.all(a["embed"] == c["embed"]))
    assert a["embed"].dtype == jnp.bfloat16
