"""The Kimi-K2 share against its plain reference (chipbench/reference/
kimi_k2.py, which imports nothing of the program), at a toy size on the
CPU: a leading dense layer and two expert layers, every mixer latent
attention with a query rank and a rotated key part, 4 of 16 routed experts
held, the same seeded weights on both sides. The chip readings that set the
real cell's limit are in PERF.md section 2."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, manifest, run
from chipbench.readers import program_counter, program_span
from chipbench.reference import kimi_k2 as ref
from chipbench.reference.common import OPERANDS
from chipbench.runners import serve_kimi_k2
from chipbench.traffic import length_pool
from mxnet_tpu.models import serving, transformer as tf

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
CELL = "kimi-k2.6-serve-agent32"
REAL = MAN.config_of(MAN.cell(CELL))
TINY = json.load(open(os.path.join(HERE, "tiny", "kimi_k2.json")))
# tiny-size limit, set as the real one is: between the program's largest
# reading over seeds 1-10 (0.0033; the widest mean of a block of served
# tokens' gaps, here a stream's 40) and the float8 control's smallest
# (0.0056), near their geometric mean. Token by token the same seeds read
# up to 0.126 sound against 0.08-0.52 for the control: at this toy share
# (4 of 16 experts, 4 a token) one pick that bfloat16 orders otherwise than
# float32 moves a whole expert, which is why this reference too compares
# in blocks (its served_gaps)
TINY_SERVE = {"served_logit_gap": 0.0045}
TRAFFIC = dict(
    manifest.load_traffic("agent32"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 24},
    output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


# ---------------------------------------------------- the configuration ---

def test_the_real_configuration_is_the_first_stage_of_the_deployment():
    assert [ref.has_experts(REAL, i) for i in range(5)] \
        == [False] + [True] * 4
    cfg = serve_kimi_k2.program_config(REAL)
    assert tf._layer_kinds(cfg) == ("mla",) * 5
    # the share: 12 of the 384 routed experts, 8 a token over all 384
    assert tf._experts(cfg) == (384, 8, 0, 12, 2048)
    assert REAL["published"] == {"num_hidden_layers": 61,
                                 "n_routed_experts": 384,
                                 "vocab_size": 163840}
    assert MAN.configs["kimi-k2.6"]["reduced"] == REAL["reduced"] \
        == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert REAL["chips_per_layer"] * REAL["n_routed_experts"] == 384
    assert REAL["vocab_chips"] * REAL["vocab_size"] == 163840
    assert REAL["max_len"] == MAN.traffic_of(MAN.cell(CELL))["max_total"]
    assert REAL["assumed"] and REAL["departures"] and REAL["deployment"]


def test_every_published_width_is_in_the_file_unchanged():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    want = {"hidden_size": 7168, "num_attention_heads": 64,
            "q_lora_rank": 1536, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 18432,
            "moe_intermediate_size": 2048, "num_experts_per_tok": 8,
            "routed_scaling_factor": 2.827, "rope_theta": 50000,
            "n_shared_experts": 1, "first_k_dense_replace": 1,
            "rms_norm_eps": 1e-05, "tie_word_embeddings": False,
            "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                             "mscale": 1, "mscale_all_dim": 1,
                             "original_max_position_embeddings": 4096,
                             "type": "yarn"}}
    assert {k: REAL[k] for k in want} == want
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Kimi-K2.6"' in line)
        assert MAN.configs["kimi-k2.6"]["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if REAL.get(k) != v}
        assert differ == set(REAL["reduced"])


def test_the_real_configuration_weighs_what_the_issue_counted():
    """Parameter counts from the reference's own shapes: 101.14 M in a
    layer's attention and its two norms, 396.4 M in the dense MLP, 44.04 M an expert,
    3,496.8 M (6.99 GB of bfloat16) for this chip's share; a position of
    a lane is 5 x (512 + 64) bfloat16 = 5,760 bytes, a lane 112.1 MB."""
    size = {name: int(np.prod(shape))
            for name, shape, _ in ref.leaf_specs(REAL)}

    def layer(i, leaves):
        return sum(size["layers.%d.%s" % (i, k)] for k in leaves)
    assert round(layer(0, ref.MLA_LEAVES + ("ln1", "ln2")) / 1e6, 2) == 101.14
    assert round(layer(0, ref.DENSE_LEAVES) / 1e6, 1) == 396.4
    assert round(size["layers.1.w1"] * 3 / 12 / 1e6, 2) == 44.04
    assert round(layer(1, ref.EXPERT_LEAVES) * 2 / 1e6, 1) == 1150.6
    assert round(sum(size.values()) / 1e6, 1) == 3496.8
    assert 6.98e9 < 2 * sum(size.values()) < 7.0e9
    cfg = serve_kimi_k2.program_config(REAL)
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    lane = sum(x.size * x.dtype.itemsize
               for layer in row for x in layer.values())
    assert lane // cfg.max_len == 5760 and round(lane / 1e6, 1) == 112.1
    assert round(32 * lane / 1e9, 2) == 3.59


def test_an_admission_of_the_real_share_goes_in_chunks_of_4096():
    """2^25 stream elements a call: 4,096 tokens of a 7,168-wide stream,
    so a 16,384-token prompt is four calls and a prompt of 9,000 three
    (4,096 + 4,096 + a rest of 808 in its bucket of 1,024), not one
    padded to 16,384; the older cells' widest prompts stay one call."""
    cfg = serve_kimi_k2.program_config(REAL)
    assert serving.prefill_widths(cfg, 16384) == [4096] * 4
    assert serving.prefill_widths(cfg, 9000) == [4096, 4096, 1024]
    assert serving.prefill_widths(cfg, 4096) == [4096]
    assert serving.prefill_widths(cfg, 19000, 0) == [4096] * 4 + [3072]
    for d_model, longest in ((2048, 1536), (2560, 2048), (2304, 8192)):
        older = tf.TransformerConfig(d_model=d_model, max_len=11264)
        assert serving.prefill_widths(older, longest) \
            == [serving._bucket(longest)]


def test_the_warm_up_admits_once_for_every_width_the_pool_uses():
    traffic = MAN.traffic_of(MAN.cell(CELL))
    cfg = serve_kimi_k2.program_config(REAL)
    lengths = [p for p, _ in length_pool(traffic)]
    assert min(lengths) == 4096 and max(lengths) == 16384
    assert max(p + o for p, o in length_pool(traffic)) <= REAL["max_len"]
    used = set()
    for n in lengths:
        used |= set(serving.prefill_widths(cfg, n))

    class Recorder(serve_kimi_k2.Session):
        active_count = 0

        def __init__(self, cfg):
            self.admitted, self.cfg, self.srv = [], cfg, self

        def admit(self, prompt, n_new):
            self.admitted.append(len(prompt))

    s = Recorder(cfg)
    s.warm(lengths)
    warmed = set()
    for n in s.admitted:
        warmed |= set(serving.prefill_widths(cfg, n))
    assert warmed == used and len(s.admitted) <= len(used)


# ------------------------------------------ the program and the reference

def test_the_references_shares_add_up_to_its_uncut_layer():
    """Four shares of the toy deployment (expert_offset 0, 4, 8, 12 of 16
    routed experts), each renormalising over all four picks of a token:
    their parts add up to the part the uncut layer's experts give, and
    the shared expert is what every chip computes alike, counted once."""
    uncut = dict(TINY, n_routed_experts=16, expert_offset=0)
    weights = ref.init_weights(uncut, 7, jnp.float32)
    p = {k: weights["layers.1." + k] for k in ref.EXPERT_LEAVES}
    h = jnp.asarray(np.random.RandomState(7).randn(24, 64), jnp.float32)
    whole = ref.experts_part(h, p, ref.exact, 4, 2.827, 0)
    parts = sum(ref.experts_part(
        h, dict(p, **{k: p[k][o: o + 4] for k in ("w1", "w3", "w2")}),
        ref.exact, 4, 2.827, o) for o in (0, 4, 8, 12))
    np.testing.assert_allclose(parts, whole, atol=1e-5)
    # every token's weights over its four picks sum to the scaling factor
    w = ref.route(h, p, ref.exact, 4, 2.827)
    np.testing.assert_allclose(w.sum(axis=1), 2.827, rtol=1e-5)
    assert int((w > 0).sum()) == 24 * 4


@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_served_streams_pass_and_the_float8_control_fails(seed):
    toks = _tokens(seed, 60)
    s = serve_kimi_k2.Session(TINY, TRAFFIC, seed)
    rid = s.admit(toks[:20], 40)
    done = {}
    while rid not in done:
        done.update(s.step())
    out = s.reference([(20, done[rid])], operand="fp8")[0]
    sound = compare.serving_checks([out["gaps"]], 0, 1, TINY_SERVE)
    assert all(c["ok"] for c in sound), sound
    control = compare.serving_checks([out["control_gaps"]], 0, 1, TINY_SERVE)
    assert not control[0]["ok"], control


def test_a_stream_is_padded_to_few_widths():
    assert [ref.padded_width(n, REAL) for n in
            (5000, 8192, 8193, 16384, 16385, 19456)] \
        == [8192, 8192, 16384, 16384, 19456, 19456]
    assert ref.padded_width(45, TINY) == 64
    # the reference attends in blocks of BLOCK queries: every width is
    # whole blocks
    assert all(ref.padded_width(n, REAL) % ref.BLOCK == 0
               for n in (4097, 9000, 19000))


def _run(trace=0, **kw):
    args = argparse.Namespace(seed=2, seconds=1.0, trace=trace)
    return run.run_cell(MAN, MAN.cell(CELL), args, config=TINY,
                        traffic=TRAFFIC, limits=TINY_SERVE, **kw)


def test_a_sound_served_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    # tokens/s is not this cell's to bound: it swings with the ten or so
    # prefills of 0.3-1.1 s that fall in a window (PERF.md section 6)
    assert set(r["metrics"]) == {"serve_itl_p95_ms", "setup_s"}


def test_a_traced_run_reports_the_rows_and_no_span_time():
    r = _run(trace=1)
    # counts are counts on any platform; the program_span metrics are
    # host times, which a CPU run never reports
    assert {"ttft_p50_ms.serve", "mla_rows_live_share.serve"} \
        == set(r["metrics"])
    assert 0 < r["metrics"]["mla_rows_live_share.serve"]["value"] <= 100


def _forget(what):
    """A program that forgets one of the two things this configuration
    adds to the world."""
    if what == "the blend":
        # rotates by the base's own frequencies, as if no record were there
        return "_rope_table", lambda cfg, dim: None
    # divides the scores by sqrt(N + E) alone
    return "_latent_score_norm", lambda cfg, width: np.sqrt(width)


@pytest.mark.parametrize("what", ["the blend", "the softmax scale"])
def test_a_program_that_forgets_the_scaling_record_is_not_correct(
        monkeypatch, what):
    name, fault = _forget(what)
    monkeypatch.setattr(tf, name, fault)
    tf._PREFILL_JIT_CACHE.clear()
    try:
        assert not _run()["correct"]
    finally:
        monkeypatch.undo()
        tf._PREFILL_JIT_CACHE.clear()


def test_rows_moved_to_another_position_are_not_correct(monkeypatch):
    """The fault a row that holds its position invites: a lane write that
    lays the admission's rows one place further on. Unrotated rows would
    still be read (a zero row before them); rotated ones answer for
    another distance."""
    real = serving._jitted_slot_write

    def shifted(cfg):
        write = real(cfg)

        def wr(cache, row, slot):
            row = [{k: jnp.roll(v, 1, axis=1) for k, v in layer.items()}
                   for layer in row]
            return write(cache, row, slot)
        return wr
    monkeypatch.setattr(serving, "_jitted_slot_write", shifted)
    assert not _run()["correct"]


# ------------------------------------------------- the two new metrics ---

COUNTERS = {"mla.rows_read": 150 * 5 * 32 * 19456.0,
            "mla.rows_live": 150 * 5 * 32 * 9728.0}
CTX = {"trace": {"window_s": 3.0}, "device": {"platform": "tpu"}}


def test_rows_live_share_reads_its_two_counters(monkeypatch):
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))
    spec = manifest.load_layer_metric("mla_rows_live_share.serve", MAN.root)
    assert spec["reader"] == "program_counter"
    entry = MAN.per_layer["mla_rows_live_share.serve"]
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert (entry["layer"], entry["moves"], entry["better"]) \
        == ("model step", "serve_itl_p95_ms", "higher")
    assert program_counter.read(CTX, spec["args"]) == pytest.approx(50.0)
    assert program_counter.read({"trace": None}, spec["args"]) is None


def test_prefill_window_share_reads_the_prefill_span(monkeypatch):
    ms = 1000000
    monkeypatch.setattr(program_span, "_totals", lambda: {
        "serving.prefill": {"count": 3, "total_ns": 750 * ms},
        "serving.step": {"count": 100, "total_ns": 2000 * ms}})
    spec = manifest.load_layer_metric("prefill_window_share.serve", MAN.root)
    assert spec["reader"] == "program_span"
    entry = MAN.per_layer["prefill_window_share.serve"]
    assert entry["workloads"] == [CELL] and entry["source"] == "program_span"
    assert (entry["layer"], entry["moves"], entry["better"]) \
        == ("serving scheduler + cache", "serve_itl_p95_ms", "lower")
    assert program_span.read(CTX, spec["args"]) == pytest.approx(25.0)
    assert program_span.read(dict(CTX, device={"platform": "cpu"}),
                             spec["args"]) is None


@pytest.mark.parametrize("values", [None, {}, {"moe.picks": 5.0}],
                         ids=["no-registry", "no-counter", "others-only"])
def test_a_program_without_the_row_counters_reads_none(monkeypatch, values):
    """The parent commit, or a model without latent layers."""
    monkeypatch.setattr(program_counter, "_values", lambda: values)
    spec = manifest.load_layer_metric("mla_rows_live_share.serve", MAN.root)
    assert program_counter.read(CTX, spec["args"]) is None


def test_the_cell_reports_what_its_sibling_reports_of_what_it_can_bound():
    """The cell bounds `serve_itl_p95_ms` (here a decode round) and
    `setup_s`, not `serve_tok_s`: six runs spread it by 6.3% of the
    median against the 4% a new cell is admitted under (PERF.md section
    6). So of the metrics the Kimi-Linear cell reports, this one lists
    those that move the two it bounds, and its own two."""
    sibling = MAN.cell("kimi-linear-48b-serve-reason32")
    mine = {m["name"]: m for g in ("end_to_end", "per_layer")
            for m in MAN.metrics_of(MAN.cell(CELL), g)}
    theirs = {m["name"]: m for g in ("end_to_end", "per_layer")
              for m in MAN.metrics_of(sibling, g)}
    assert set(theirs) - set(mine) == {"serve_tok_s"} | {
        name for name, m in theirs.items() if m.get("moves") == "serve_tok_s"}
    assert set(mine) - set(theirs) == {"mla_rows_live_share.serve",
                                       "prefill_window_share.serve"}
    assert {m["moves"] for m in mine.values() if "moves" in m} \
        == {"serve_itl_p95_ms", "setup_s"}
    limits = manifest.load_limits(CELL)
    assert limits["control"] in OPERANDS
