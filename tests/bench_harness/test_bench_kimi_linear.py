"""The Kimi-Linear share against its plain reference (chipbench/reference/
kimi_linear.py, which imports nothing of the program), at a toy size on
the CPU: one period of the layer pattern (KDA, KDA, KDA, MLA) behind a
leading dense layer, 4 of 16 routed experts held, the same seeded weights
on both sides. The chip readings that set the real cell's limit are in
PERF.md section 2."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, manifest, run
from chipbench.readers import program_counter
from chipbench.reference import kimi_linear as ref
from chipbench.runners import serve_kimi_linear
from mxnet_tpu.models import transformer as tf

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
CELL = "kimi-linear-48b-serve-reason32"
TINY = json.load(open(os.path.join(HERE, "tiny", "kimi_linear.json")))
# tiny-size limit, set as the real one is: between the program's largest
# reading over seeds 1-10 (0.0112; the widest mean of a block of served
# tokens' gaps, here a stream's 40) and the float8 control's smallest
# (0.0311), near their geometric mean. Token by token the same seeds read
# up to 0.153 sound: at this toy share (4 of 16 experts, 4 a token) one
# pick that bfloat16 orders otherwise than float32 moves a whole expert,
# which is why this reference compares in blocks (its served_gaps)
TINY_SERVE = {"served_logit_gap": 0.02}
TRAFFIC = dict(
    manifest.load_traffic("reason32"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 24},
    output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


def test_the_toy_pattern_is_a_period_and_the_real_one_is_the_first_stage():
    assert ref.layer_kinds(TINY) == ("kda", "kda", "kda", "mla")
    real = MAN.config_of(MAN.cell(CELL))
    assert ref.layer_kinds(real) == ("kda", "kda", "kda", "mla") * 2
    assert [ref.has_experts(real, i) for i in range(8)] == [False] + [True] * 7
    # the share: 64 of the 256 routed experts, 8 a token over all 256
    cfg = serve_kimi_linear.program_config(real)
    assert tf._experts(cfg) == (256, 8, 0, 64, 1024)
    assert real["published"] == {"num_hidden_layers": 27, "num_experts": 256,
                                 "vocab_size": 163840}
    assert MAN.configs["kimi-linear-48b-a3b"]["reduced"] == real["reduced"] \
        == ["num_hidden_layers", "num_experts", "vocab_size"]


def test_the_real_configuration_weighs_what_the_issue_counted():
    """Parameter counts from the reference's own shapes: a KDA mixer
    39.5 M, an MLA mixer 29.1 M, an expert 7.08 M; 7.5 GB of bfloat16
    for this chip's share."""
    real = MAN.config_of(MAN.cell(CELL))
    size = {name: int(np.prod(shape))
            for name, shape, _ in ref.leaf_specs(real)}

    def layer(i, leaves):
        return sum(size["layers.%d.%s" % (i, k)] for k in leaves)
    assert round(layer(0, ref.KDA_LEAVES) / 1e6, 1) == 39.5
    assert round(layer(3, ref.MLA_LEAVES) / 1e6, 1) == 29.1
    assert size["layers.1.w1"] * 3 // 64 == 7077888
    assert 7.5e9 < 2 * sum(size.values()) < 7.6e9


@pytest.mark.parametrize("dtype,tol,why", [
    (jnp.float32, 1e-4, "float32 both sides, sums in another order"),
    # bfloat16 through 4 layers is 0.02; a pick ordered otherwise than
    # the reference's moves a whole expert of this toy share (0.15)
    (jnp.bfloat16, 0.3, "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, tol, why):
    weights = ref.init_weights(TINY, 3, dtype)
    cfg = serve_kimi_linear.program_config(TINY)
    cfg.dtype = dtype
    toks = _tokens(3, 64)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(
        ref.as_tree(weights, TINY), toks[None])[0]
    want = ref.forward_row(weights, jnp.asarray(toks), TINY)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_served_streams_pass_and_the_float8_control_fails(seed):
    toks = _tokens(seed, 60)
    s = serve_kimi_linear.Session(TINY, TRAFFIC, seed)
    rid = s.admit(toks[:20], 40)
    done = {}
    while rid not in done:
        done.update(s.step())
    out = s.reference([(20, done[rid])], operand="fp8")[0]
    sound = compare.serving_checks([out["gaps"]], 0, 1, TINY_SERVE)
    assert all(c["ok"] for c in sound), sound
    control = compare.serving_checks([out["control_gaps"]], 0, 1, TINY_SERVE)
    assert not control[0]["ok"], control


def test_gaps_are_means_over_blocks_with_one_entry_a_token():
    """Every served token keeps an entry and the mean is the tokens' own;
    a short tail joins the blocks before it; a stream shorter than a
    block is one block."""
    gaps = np.arange(150, dtype=np.float32)
    got = ref._block_means(gaps)
    assert got.shape == (150,) and np.isclose(got.mean(), gaps.mean())
    assert np.allclose(got[:75], gaps[:75].mean())
    assert np.allclose(got[75:], gaps[75:].mean())
    assert np.allclose(ref._block_means(gaps[:40]), gaps[:40].mean())
    one = np.zeros(ref.GAP_BLOCK * 3, np.float32)
    one[70] = 6.4                       # one token off by 6.4: 0.1 a block
    assert np.isclose(ref._block_means(one).max(), 0.1)


def test_a_stream_is_padded_to_few_widths():
    real = MAN.config_of(MAN.cell(CELL))
    assert [ref.padded_width(n, real) for n in
            (300, 1024, 1025, 4097, 8193, 11264)] \
        == [1024, 1024, 2048, 8192, 11264, 11264]
    assert ref.padded_width(45, TINY) == 64


def _run(trace=0, **kw):
    args = argparse.Namespace(seed=2, seconds=1.0, trace=trace)
    return run.run_cell(MAN, MAN.cell(CELL), args, config=TINY,
                        traffic=TRAFFIC, limits=TINY_SERVE, **kw)


def test_a_sound_served_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_tok_s", "serve_itl_p95_ms", "setup_s"}


def test_a_traced_run_reports_the_routing_counts_and_no_span_time():
    r = _run(trace=1)
    # counts are counts on any platform; the program_span metrics are
    # host times, which a CPU run never reports
    assert {"dispatches_per_token.serve", "ttft_p50_ms.serve",
            "device_idle.serve", "moe_experts_touched.serve",
            "moe_load_max_over_mean.serve"} == set(r["metrics"])
    assert 0 < r["metrics"]["moe_experts_touched.serve"]["value"] <= 100
    assert r["metrics"]["moe_load_max_over_mean.serve"]["value"] >= 1


def test_a_share_that_forgets_the_other_chips_picks_is_not_correct(
        monkeypatch):
    """The fault this deployment adds to the world: a share that
    renormalises over the picks IT holds (instead of all k chosen) serves
    plausible tokens of another model."""
    real = jax.lax.top_k

    def held_only(operand, k):
        if operand.ndim == 2 and operand.shape[-1] == 16 and k == 4:
            # only this share's experts (4..7) can be chosen
            mask = (jnp.arange(16) >= 4) & (jnp.arange(16) < 8)
            operand = jnp.where(mask, operand, -jnp.inf)
        return real(operand, k)
    monkeypatch.setattr(tf.jax.lax, "top_k", held_only)
    tf._PREFILL_JIT_CACHE.clear()
    try:
        assert not _run()["correct"]
    finally:
        monkeypatch.undo()
        tf._PREFILL_JIT_CACHE.clear()


# ---------------------------------------------- the program_counter reader

class _C(object):
    def __init__(self, value):
        self.value = value


COUNTERS = {"moe.picks": 1000 * 1792.0, "moe.picks_here": 1000 * 448.0,
            "moe.experts_touched": 1000 * 280.0, "moe.load_max": 1000 * 28.0,
            "moe.experts_held": 1000 * 448.0, "moe.layers": 1000 * 7.0}
CTX = {"trace": {"window_s": 3.0}, "device": {"platform": "tpu"}}


@pytest.fixture
def counters(monkeypatch):
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))


@pytest.mark.parametrize("metric,want", [
    ("moe_experts_touched.serve", 62.5),             # 280 of 448 slots
    ("moe_load_max_over_mean.serve", 2.5),           # 28/7 over 448/280
])
def test_each_metric_reads_its_counters(counters, metric, want):
    spec = manifest.load_layer_metric(metric, MAN.root)
    assert spec["reader"] == "program_counter"
    assert MAN.per_layer[metric]["workloads"] == [CELL]
    assert program_counter.read(CTX, spec["args"]) == pytest.approx(want)


@pytest.mark.parametrize("values", [None, {}, {"moe.picks": 5.0}],
                         ids=["no-registry", "no-counter", "others-only"])
def test_a_program_without_the_counters_reads_none(monkeypatch, values):
    monkeypatch.setattr(program_counter, "_values", lambda: values)
    for metric in ("moe_experts_touched.serve",
                   "moe_load_max_over_mean.serve"):
        spec = manifest.load_layer_metric(metric, MAN.root)
        assert program_counter.read(CTX, spec["args"]) is None


def test_an_untraced_run_and_an_idle_window_read_none(counters, monkeypatch):
    spec = manifest.load_layer_metric("moe_experts_touched.serve", MAN.root)
    assert program_counter.read({"trace": None}, spec["args"]) is None
    monkeypatch.setattr(program_counter, "_values",
                        lambda: dict(COUNTERS, **{"moe.experts_held": 0.0}))
    assert program_counter.read(CTX, spec["args"]) is None


def test_the_reader_reads_the_programs_own_registry():
    from mxnet_tpu.observability import core
    core.reset()
    assert "moe.picks" not in program_counter._values()
    core.counter("moe.picks").add(7)
    core.counter("moe.picks").add(5)
    assert program_counter._values()["moe.picks"] == 12.0
    core.reset()
