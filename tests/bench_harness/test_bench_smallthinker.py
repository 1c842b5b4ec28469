"""The SmallThinker stage against its plain reference (chipbench/reference/
smallthinker.py, which imports nothing of the program), at a toy size on the
CPU: the pattern full, window, window, window twice, a ring of 8 K/V rows in
the window layers, all 8 ReGLU experts held and routed 3 a token from the
layer's input, the same seeded weights on both sides. The chip readings that
set the real cell's limit are in PERF.md section 2."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, manifest, run
from chipbench.readers import program_counter
from chipbench.reference import smallthinker as ref
from chipbench.reference.common import OPERANDS
from chipbench.runners import serve_smallthinker
from chipbench.traffic import length_pool
from mxnet_tpu.models import serving, transformer as tf

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
CONFIG = "smallthinker-21b-a3b"
CELL = "smallthinker-21b-serve-docchat32"
REAL = MAN.config_of(MAN.cell(CELL))
TINY = json.load(open(os.path.join(HERE, "tiny", "smallthinker.json")))
# tiny-size limit, set as the real one is: between the program's largest
# reading over seeds 1-6 (0.00060; the widest mean of a block of served
# tokens' gaps, here a stream's 40) and the float8 control's smallest
# (0.0058), near their geometric mean
TINY_SERVE = {"served_logit_gap": 0.0019}
TRAFFIC = dict(
    manifest.load_traffic("docchat32"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 24},
    output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


# ---------------------------------------------------- the configuration ---

def test_the_real_configuration_is_the_first_stage_of_the_deployment():
    cfg = serve_smallthinker.program_config(REAL)
    assert tf._layer_kinds(cfg) == ("attention", "window", "window",
                                    "window") * 2
    assert tf._layer_rope(cfg) == (False, True, True, True) * 2
    assert (cfg.d_model, cfg.n_heads, tf._kvh(cfg), tf._head_dim(cfg),
            tf._window(cfg), cfg.max_len) == (2560, 28, 4, 128, 4096, 16384)
    # no expert is cut: all 64 held, 6 a token
    assert tf._experts(cfg) == (64, 6, 0, 64, 768)
    assert (cfg.ffn, cfg.expert_scoring, cfg.router_input, cfg.tied_head,
            cfg.rope_base) == ("gated_relu", "softmax_topk", "layer", False,
                               1.5e6)
    assert REAL["published"] == {"num_hidden_layers": 52}
    assert MAN.configs[CONFIG]["reduced"] == REAL["reduced"] \
        == ["num_hidden_layers"]
    # six stages of 8 layers and the last of 4; this is the first
    assert REAL["pipeline_stages"] == 7 and REAL["ep_size"] == 1
    assert 6 * REAL["num_hidden_layers"] + 4 == 52
    assert REAL["max_len"] == REAL["max_position_embeddings"] \
        == MAN.traffic_of(MAN.cell(CELL))["max_total"]
    assert REAL["assumed"] and REAL["departures"] and REAL["deployment"]
    assert (REAL["compute_dtype"], REAL["router_dtype"]) \
        == ("bfloat16", "float32")


def test_every_published_key_is_in_the_file_unchanged():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    want = {"hidden_size": 2560, "num_attention_heads": 28,
            "num_key_value_heads": 4, "head_dim": 128,
            "moe_ffn_hidden_size": 768, "moe_num_primary_experts": 64,
            "moe_num_active_primary_experts": 6,
            "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
            "sliding_window_size": 4096, "rope_theta": 1500000,
            "rope_scaling": None, "rms_norm_eps": 1e-06,
            "max_position_embeddings": 16384, "vocab_size": 151936,
            "tie_word_embeddings": False,
            "rope_layout": [0, 1, 1, 1] * 13,
            "sliding_window_layout": [0, 1, 1, 1] * 13}
    assert {k: REAL[k] for k in want} == want
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"SmallThinker-21BA3B-Instruct"' in line)
        assert MAN.configs[CONFIG]["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if REAL.get(k) != v}
        assert differ == set(REAL["reduced"]) == {"num_hidden_layers"}
        assert {k: row["config"][k] for k in differ} == REAL["published"]


def test_the_real_configuration_weighs_what_the_issue_counted():
    """Parameter counts from the reference's own shapes: 20.97 M in a
    layer's attention, 0.16 M in the router, 5.898 M an expert and
    377.5 M in the 64, 398.6 M a layer (797.3 MB of bfloat16), 777.9 M
    in embedding and head: 3,966.9 M, 7.93 GB for this stage. A position
    of one layer is 2 x 4 x 128 bfloat16 = 2,048 bytes; a lane holds 2
    full layers x 16,384 rows and 6 rings of exactly the window's 4,096
    (a chunk reads the ring as it was, then stores: docs/SERVING.md), so
    57,344 rows, 117.4 MB, and 32 lanes 3.76 GB; with max_len rows in
    every layer they would be 8.6 GB."""
    size = {name: int(np.prod(shape))
            for name, shape, _ in ref.leaf_specs(REAL)}

    def layer(i, leaves):
        return sum(size["layers.%d.%s" % (i, k)] for k in leaves)
    assert round(layer(0, ref.ATTENTION_LEAVES) / 1e6, 2) == 20.97
    assert round(size["layers.3.gate"] / 1e6, 2) == 0.16
    assert round(size["layers.1.w1"] * 3 / 64 / 1e6, 3) == 5.898
    assert round(layer(1, ("w1", "w3", "w2")) / 1e6, 1) == 377.5
    assert round(layer(5, ref.LAYER_LEAVES) / 1e6, 1) == 398.6
    assert round(layer(5, ref.LAYER_LEAVES) * 2 / 1e6, 1) == 797.3
    assert round((size["embed"] + size["head"]) / 1e6, 1) == 777.9
    assert round(sum(size.values()) / 1e6, 1) == 3966.9
    # the program's tree holds the same parameters
    cfg = serve_smallthinker.program_config(REAL)
    flat = {n: jax.ShapeDtypeStruct(s, jnp.bfloat16)
            for n, s, _ in ref.leaf_specs(REAL)}
    tree = ref.as_tree(flat, REAL)
    assert tree["layers"][1]["wq"].shape == (2560, 28, 128)
    assert tree["layers"][1]["wo"].shape == (28, 128, 2560)
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert round(nbytes / 1e9, 2) == 7.93
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    assert [layer["k"].shape[1] for layer in row] \
        == [16384, 4096, 4096, 4096] * 2
    assert {x.size * x.dtype.itemsize // x.shape[1]
            for layer in row for x in layer.values()} == {1024}
    lane = sum(x.size * x.dtype.itemsize
               for layer in row for x in layer.values())
    assert lane == 57344 * 2048 and round(lane / 1e6, 1) == 117.4
    assert round(32 * lane / 1e9, 2) == 3.76
    assert round(32 * 8 * 16384 * 2048 / 1e9, 1) == 8.6


def test_the_programs_own_init_makes_the_runners_tree():
    """`init_params` and the runner's arrangement of the reference's
    weights agree leaf by leaf, shapes and types (at the toy size)."""
    cfg = serve_smallthinker.program_config(TINY)
    mine = tf.init_params(cfg, 0)
    theirs = ref.as_tree(ref.init_weights(TINY, 0), TINY)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), mine) \
        == jax.tree.map(lambda x: (x.shape, str(x.dtype)), theirs)


def test_a_program_that_cannot_state_the_fields_fails_before_any_weight(
        monkeypatch):
    """The parent commit's TransformerConfig has none of the fields: the
    constructor raises at once, and no weight was made."""
    import dataclasses
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(tf.TransformerConfig)
        if f.name not in ("attn_head_dim", "attn_window", "rope_layers",
                          "router_input")])
    monkeypatch.setattr(tf, "TransformerConfig", old)
    monkeypatch.setattr(ref, "init_weights", lambda *a, **k: 1 / 0)
    with pytest.raises(TypeError, match="unexpected keyword"):
        serve_smallthinker.build(REAL, MAN.traffic_of(MAN.cell(CELL)), 1)


def test_the_traffic_is_the_issues_64_pairs():
    traffic = MAN.traffic_of(MAN.cell(CELL))
    assert (traffic["kind"], traffic["clients"], traffic["pool"],
            traffic["pairing_seed"]) == ("closed-loop", 32, 64, 47)
    assert traffic["prompt"] == {"median": 4096, "sigma": 0.6, "lo": 512,
                                 "hi": 12288}
    assert traffic["output"] == {"median": 1024, "sigma": 0.5, "lo": 256,
                                 "hi": 3072}
    assert (traffic["max_total"], traffic["greedy"], traffic["check_requests"],
            traffic["trace_seconds"], traffic["warm_max_s"]) \
        == (16384, True, 6, 3, 90)
    pool = length_pool(traffic)
    assert len(pool) == 64
    prompts, outputs = zip(*pool)
    assert 512 <= min(prompts) < 1024 and max(prompts) == 12288
    assert 256 <= min(outputs) < 320 and max(outputs) == 3072
    assert 4700 < np.mean(prompts) < 4900 and 1140 < np.mean(outputs) < 1160
    assert max(p + o for p, o in pool) <= REAL["max_len"]
    # some half of the requests shorter than one window, half longer
    assert 24 <= sum(p < 4096 for p in prompts) <= 40


def test_an_admission_of_the_real_stage_goes_in_chunks_of_8192():
    """2^25 stream elements a call: 8,192 tokens of a 2,560 stream, two
    windows wide, so the longest prompt of 12,288 is a chunk and a rest
    of 4,096; the warm-up admits once for every width the pool uses."""
    traffic = MAN.traffic_of(MAN.cell(CELL))
    cfg = serve_smallthinker.program_config(REAL)
    assert serving.prefill_widths(cfg, 12288) == [8192, 4096]
    assert serving.prefill_widths(cfg, 4781) == [8192]
    lengths = [p for p, _ in length_pool(traffic)]
    used = set()
    for n in lengths:
        used |= set(serving.prefill_widths(cfg, n))
    assert used == {8192, 4096, 2048, 1024, 256}

    class Recorder(serve_smallthinker.Session):
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

@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_served_streams_pass_and_the_float8_control_fails(seed):
    toks = _tokens(seed, 60)
    s = serve_smallthinker.Session(TINY, TRAFFIC, seed)
    rid = s.admit(toks[:20], 40)
    done = {}
    while rid not in done:
        done.update(s.step())
    out = s.reference([(20, done[rid])], operand="fp8")[0]
    sound = compare.serving_checks([out["gaps"]], 0, 1, TINY_SERVE)
    assert all(c["ok"] for c in sound), sound
    control = compare.serving_checks([out["control_gaps"]], 0, 1, TINY_SERVE)
    assert not control[0]["ok"], control


def test_the_reference_heads_only_the_rows_that_chose_a_served_token():
    """A stream's gaps come from the rows [prompt - 1, len - 1), headed
    in a power of two of them: the same numbers as the whole stream's
    logits give. At the real widths a stream runs at 1,024 times a power
    of two, or max_len, in whole blocks of the reference's queries."""
    weights = ref.init_weights(TINY, 3)
    toks = _tokens(3, 45)
    served, control = ref.stream_gaps(weights, TINY, 20, toks)
    assert control is None and served.shape == (25,)
    padded = np.zeros((64,), np.int32)
    padded[:45] = toks
    logits = np.asarray(ref.forward_row(weights, jnp.asarray(padded), TINY))
    want = logits[19:44].max(-1) - logits[np.arange(19, 44), toks[20:45]]
    np.testing.assert_allclose(served, want, atol=1e-6)
    assert [ref.padded_width(n, REAL) for n in (1300, 4096, 4097, 8193,
                                                16384)] \
        == [2048, 4096, 8192, 16384, 16384]
    assert all(ref.padded_width(n, REAL) % ref.BLOCK == 0
               for n in (1300, 5000, 15000))


def _run(trace=0, **kw):
    args = argparse.Namespace(seed=2, seconds=1.0, trace=trace)
    return run.run_cell(MAN, MAN.cell(CELL), args, config=TINY,
                        traffic=TRAFFIC, limits=TINY_SERVE, **kw)


def test_a_sound_served_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    # the p95 gap is not this cell's to bound: it sits on the edge
    # between a plain round and a round behind an admission, 3.9% and
    # 6.9% from seed to seed (PERF.md section 6)
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


def test_a_traced_run_reports_the_counts_and_no_span_time():
    r = _run(trace=1)
    # counts are counts on any platform; the program_span metrics are
    # host times, which a CPU run never reports
    assert set(r["metrics"]) == {"dispatches_per_token.serve",
                                 "device_idle.serve",
                                 "kv_rows_live_share.serve",
                                 "kv_ring_share.serve"}
    # 6 rings of 8 rows beside 2 full layers of 64: 48 of 176 rows read
    assert r["metrics"]["kv_ring_share.serve"]["value"] \
        == pytest.approx(100 * 48 / 176)
    assert 0 < r["metrics"]["kv_rows_live_share.serve"]["value"] < 100


def _forget(what):
    """A program that forgets part of the architecture: the window (every
    layer sees everything), the rotation, or where the router reads."""
    def fault(cfg):
        import dataclasses
        real = serve_smallthinker.program_config(cfg)
        if what == "the window":
            # a ring as wide as the cache, and a mask as wide
            return dataclasses.replace(real, attn_window=real.max_len)
        if what == "the rotation":
            return dataclasses.replace(real, rope_layers=(False,)
                                       * real.n_layers)
        return dataclasses.replace(real, router_input="ffn")
    return fault


@pytest.mark.parametrize("what", ["the window", "the rotation",
                                  "the router's input"])
def test_a_program_that_forgets_part_of_the_layer_is_not_correct(
        monkeypatch, what):
    fault = _forget(what)
    monkeypatch.setattr(
        serve_smallthinker.Session, "__init__",
        lambda self, config, traffic, seed: _faulty(self, config, traffic,
                                                    seed, fault))
    assert not _run()["correct"]


def _faulty(self, config, traffic, seed, fault):
    from mxnet_tpu.models.serving import ContinuousBatcher
    self.config, self.seed = config, seed
    self.srv = ContinuousBatcher(
        ref.as_tree(ref.init_weights(config, seed), config), fault(config),
        max_batch=traffic["clients"])


# ------------------------------------------------- the two new metrics ---

COUNTERS = {"kv.rows_read": 57344.0 * 32 * 100, "kv.rows_ring": 24576.0
            * 32 * 100, "kv.rows_live": 30000.0 * 32 * 100, "moe.picks": 1.0}
CTX = {"trace": {"window_s": 3.0}, "device": {"platform": "tpu"}}


@pytest.mark.parametrize("metric,num,want", [
    ("kv_rows_live_share.serve", "kv.rows_live", 100 * 30000 / 57344),
    ("kv_ring_share.serve", "kv.rows_ring", 100 * 24576 / 57344),
])
def test_each_metric_reads_its_two_counters(monkeypatch, metric, num, want):
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))
    spec = manifest.load_layer_metric(metric, MAN.root)
    assert spec == {"reader": "program_counter",
                    "args": {"num": [num], "den": ["kv.rows_read"],
                             "scale": 100.0}}
    entry = MAN.per_layer[metric]
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert (entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == ("model step", "serve_tok_s", "higher",
                                 "program_counter")
    assert program_counter.read(CTX, spec["args"]) == pytest.approx(want)
    assert program_counter.read({"trace": None}, spec["args"]) is None
    # 42.9% by the shapes: 6 rings of 4,096 of a lane's 57,344 rows
    if metric == "kv_ring_share.serve":
        assert round(want, 1) == 42.9


@pytest.mark.parametrize("values", [None, {}, {"moe.picks": 5.0},
                                    {"kv.rows_read": 0.0, "kv.rows_live": 0.0,
                                     "kv.rows_ring": 0.0}],
                         ids=["no-registry", "no-counter", "others-only",
                              "nothing-read"])
def test_a_program_without_the_counters_reads_none(monkeypatch, values):
    """The parent commit, or a window without a round."""
    monkeypatch.setattr(program_counter, "_values", lambda: values)
    for metric in ("kv_rows_live_share.serve", "kv_ring_share.serve"):
        spec = manifest.load_layer_metric(metric, MAN.root)
        assert program_counter.read(CTX, spec["args"]) is None


def test_the_cell_lists_what_applies_and_not_the_four_pinned_metrics():
    """The cell bounds `serve_tok_s` and `setup_s`, so it lists the
    per-layer metrics that move those two and none that moves
    `serve_itl_p95_ms` (two sets of six seeds spread that by 3.9% and
    6.9%: PERF.md section 6)."""
    mine = {m["name"] for g in ("end_to_end", "per_layer")
            for m in MAN.metrics_of(MAN.cell(CELL), g)}
    assert mine == {
        "setup_s", "serve_tok_s",
        "kv_rows_live_share.serve", "kv_ring_share.serve",
        "dispatches_per_token.serve", "device_idle.serve",
        "round_host_ms.serve", "sync_wait_share.serve",
        "decode_wait_ms.serve", "dispatch_ahead_share.serve",
        "backend_init_s.startup", "trace_lower_s.startup",
        "compile_s.startup", "cache_load_s.startup", "cache_misses.startup",
        "programs.startup", "cold_call_s.serve", "batcher_build_s.serve"}
    # accepted tests pin each of these to one cell
    assert not mine & {"moe_experts_touched.serve",
                       "moe_load_max_over_mean.serve",
                       "mla_rows_live_share.serve",
                       "prefill_window_share.serve"}
    ends = {m["name"] for m in MAN.metrics_of(MAN.cell(CELL), "end_to_end")}
    assert ends == {"setup_s", "serve_tok_s"}
    assert MAN.traffic_of(MAN.cell(CELL))["end_to_end"] \
        == {"serve_tok_s": "rate"}
    for m in MAN.metrics_of(MAN.cell(CELL), "per_layer"):
        assert m["moves"] in ends, m["name"]
    limits = manifest.load_limits(CELL)
    assert limits["control"] in OPERANDS
    assert limits["served_logit_gap"] == 0.29
