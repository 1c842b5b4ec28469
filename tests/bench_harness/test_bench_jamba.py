"""The Jamba hybrid against its plain reference (chipbench/reference/
jamba.py, which imports nothing of the program), at a toy size on the CPU:
two periods of the layer pattern (attention at layers 1 and 3 of 4), the
same seeded weights on both sides. The chip readings that set the real
cell's limit are in PERF.md section 2."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, manifest, run
from chipbench.reference import jamba as ref
from chipbench.runners import serve_jamba
from mxnet_tpu.models import transformer as tf

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
CELL = "jamba2-3b-serve-chat64"
TINY = json.load(open(os.path.join(HERE, "tiny", "jamba.json")))
# tiny-size limit, set as the real one is: above the program's largest
# served gap over seeds 1-4 (0.00053) and below the float8 control's
# smallest (0.053)
TINY_SERVE = {"served_logit_gap": 5e-3}
TRAFFIC = dict(
    manifest.load_traffic("chat64"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 24},
    output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)


def _sides(seed, dtype):
    """(program params, program config, reference weights)."""
    weights = ref.init_weights(TINY, seed, dtype)
    cfg = serve_jamba.program_config(TINY)
    cfg.dtype = dtype
    return ref.as_tree(weights, TINY), cfg, weights


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


def test_the_toy_pattern_is_two_periods_and_the_real_one_is_the_models():
    assert ref.layer_kinds(TINY) == ("mamba", "attention") * 2
    real = ref.layer_kinds(MAN.config_of(MAN.cell(CELL)))
    assert [i for i, k in enumerate(real) if k == "attention"] == [7, 21]
    assert len(real) == 28


@pytest.mark.parametrize("dtype,tol,why", [
    # the same arithmetic in the same precision, summed in another order
    (jnp.float32, 2e-4, "float32 both sides"),
    # the program rounds every projection's operands and results to
    # bfloat16 (8 bits of mantissa) through 4 layers; logits are O(1)
    (jnp.bfloat16, 0.15, "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, tol, why):
    params, cfg, weights = _sides(3, dtype)
    toks = _tokens(3, 40)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks[None])[0]
    want = ref.forward_row(weights, jnp.asarray(toks), TINY)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)
    if dtype == jnp.bfloat16:
        # and is no closer than its precision allows: a float32 program
        # under this label would pass the tight tolerance instead
        assert gap > 2e-4


def test_prefill_then_decode_through_the_cache_equals_the_full_forward():
    params, cfg, weights = _sides(4, jnp.float32)
    toks = _tokens(4, 45)
    want = ref.forward_row(weights, jnp.asarray(toks), TINY)
    # the admission path: a bucket of 32 for a prompt of 19
    padded = np.zeros((1, 32), np.int32)
    padded[0, :19] = toks[:19]
    logits, cache = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg, logits_row=jnp.int32(18)))(
            params, tf.init_cache(cfg, 1), jnp.asarray(padded))
    np.testing.assert_allclose(logits[0], want[18], atol=2e-4)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for t in range(19, 45):
        logits, cache = step(params, cache, jnp.asarray(toks[t:t + 1]),
                             jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], atol=2e-4)


@pytest.mark.parametrize("seed", [1, 2])
def test_served_streams_pass_and_the_float8_control_fails(seed):
    toks = _tokens(seed, 60)
    s = serve_jamba.Session(TINY, TRAFFIC, seed)
    rid = s.admit(toks[:20], 40)
    done = {}
    while rid not in done:
        done.update(s.step())
    out = s.reference([(20, done[rid])], operand="fp8")[0]
    sound = compare.serving_checks([out["gaps"]], 0, 1, TINY_SERVE)
    assert all(c["ok"] for c in sound), sound
    control = compare.serving_checks([out["control_gaps"]], 0, 1, TINY_SERVE)
    assert not control[0]["ok"], control


def _run(trace=0, **kw):
    args = argparse.Namespace(seed=5, seconds=1.0, trace=trace)
    return run.run_cell(MAN, MAN.cell(CELL), args, config=TINY,
                        traffic=TRAFFIC, limits=TINY_SERVE, **kw)


def test_a_sound_served_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_tok_s", "serve_itl_p95_ms", "setup_s"}


def test_a_traced_run_reports_what_a_cpu_can_and_no_span_time():
    r = _run(trace=1)
    # the program_span metrics (decode_wait_ms.serve among them) are host
    # times, which a CPU run never reports
    assert {"dispatches_per_token.serve", "ttft_p50_ms.serve",
            "device_idle.serve"} == set(r["metrics"])


def test_a_state_that_folds_its_padding_in_is_not_correct(monkeypatch):
    """The fault this model adds to the world: a prefill that lets the
    bucket's padding into the recurrent state serves plausible tokens of
    another prompt."""
    real = tf.prefill_chunk

    def folded(params, cache, tokens, start, cfg, logits_row=None, **kw):
        logits, _ = real(params, cache, tokens, start, cfg,
                         logits_row=logits_row, **kw)
        _, cache = real(params, cache, tokens, start, cfg, **kw)
        return logits, cache
    monkeypatch.setattr(tf, "prefill_chunk", folded)
    tf._PREFILL_JIT_CACHE.clear()
    try:
        assert not _run()["correct"]
    finally:
        tf._PREFILL_JIT_CACHE.clear()
