"""The Xing4.0 stage against its plain reference (chipbench/reference/
xing4.py, which imports nothing of the program), at a toy size on the CPU:
a leading dense layer and two expert layers with all 8 routed experts held,
every mixer latent attention, four residual streams mixed by 20 Sinkhorn
iterations around every mixer and every FFN, the same seeded weights on
both sides. The chip readings that set the real cell's limit are in PERF.md
section 2."""

import argparse
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import compare, manifest, run
from chipbench.readers import counter_per_span, program_counter, program_span
from chipbench.reference import xing4 as ref
from chipbench.reference.common import OPERANDS
from chipbench.runners import serve_xing4
from chipbench.traffic import length_pool
from mxnet_tpu.models import serving, transformer as tf

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
CELL = "xing4.0-29b-a4b-serve-rag32"
REAL = MAN.config_of(MAN.cell(CELL))
TINY = json.load(open(os.path.join(HERE, "tiny", "xing4.json")))
# tiny-size limit, set as the real one is: between the program's largest
# reading over seeds 1-6 (0.0075; the widest mean of a block of served
# tokens' gaps, here a stream's 40) and the float8 control's smallest
# (0.0225), near their geometric mean
TINY_SERVE = {"served_logit_gap": 0.013}
TRAFFIC = dict(
    manifest.load_traffic("rag32"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 24},
    output={"median": 8, "sigma": 0.7, "lo": 2, "hi": 20},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


# ---------------------------------------------------- the configuration ---

def test_the_real_configuration_is_the_first_stage_of_the_deployment():
    assert [ref.has_experts(REAL, i) for i in range(5)] \
        == [False] + [True] * 4
    cfg = serve_xing4.program_config(REAL)
    assert tf._layer_kinds(cfg) == ("mla",) * 5
    # no expert is cut: all 64 held, 4 a token
    assert tf._experts(cfg) == (64, 4, 0, 64, 1024)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_clamp_min, cfg.hc_clamp_max) == (4, 20, 1e-06, -30.0, 30.0)
    assert REAL["published"] == {"num_hidden_layers": 40,
                                 "first_k_dense_replace": 2,
                                 "num_nextn_predict_layers": 1}
    assert MAN.configs["xing4.0-29b-a4b"]["reduced"] == REAL["reduced"] \
        == ["num_hidden_layers", "first_k_dense_replace",
            "num_nextn_predict_layers"]
    assert REAL["pipeline_stages"] * REAL["num_hidden_layers"] == 40
    assert REAL["vocab_size"] == 131072 and REAL["ep_size"] == 1
    assert REAL["max_len"] == MAN.traffic_of(MAN.cell(CELL))["max_total"]
    assert REAL["assumed"] and REAL["departures"] and REAL["deployment"]
    assert (REAL["compute_dtype"], REAL["router_dtype"], REAL["hc_dtype"]) \
        == ("bfloat16", "float32", "float32")


def test_every_published_key_is_in_the_file_unchanged():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    want = {"hidden_size": 3584, "num_attention_heads": 32,
            "q_lora_rank": 768, "kv_lora_rank": 512,
            "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
            "v_head_dim": 128, "intermediate_size": 9216,
            "moe_intermediate_size": 1024, "n_routed_experts": 64,
            "num_experts_per_tok": 4, "routed_scaling_factor": 2,
            "rope_theta": 10000, "n_shared_experts": 1,
            "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
            "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
            "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
            "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                             "mscale": 1, "mscale_all_dim": 1,
                             "original_max_position_embeddings": 4096,
                             "type": "yarn"}}
    assert {k: REAL[k] for k in want} == want
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"Xing4.0-29B-A4B"' in line)
        assert MAN.configs["xing4.0-29b-a4b"]["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if REAL.get(k) != v}
        assert differ == set(REAL["reduced"])
        assert {k: row["config"][k] for k in differ} == REAL["published"]


def test_the_real_configuration_weighs_what_the_issue_counted():
    """Parameter counts from the reference's own shapes: 28.41 M in a
    layer's attention, 99.09 M in the dense MLP, 11.01 M an expert and
    704.64 M in the 64, 0.23 M in the router, 0.69 M in a layer's two
    frames, 939.52 M in embedding and head: 4,047.7 M (8.10 GB of
    bfloat16) for this stage; a position of a lane is 5 x (512 + 64)
    bfloat16 = 5,760 bytes, a lane 53.1 MB, 32 lanes 1.70 GB."""
    size = {name: int(np.prod(shape))
            for name, shape, _ in ref.leaf_specs(REAL)}

    def layer(i, leaves):
        return sum(size["layers.%d.%s" % (i, k)] for k in leaves)
    norms = ("q_norm", "kv_norm")
    assert round(layer(0, [k for k in ref.MLA_LEAVES if k not in norms])
                 / 1e6, 2) == 28.41
    assert round(layer(0, ref.DENSE_LEAVES) / 1e6, 2) == 99.09
    assert round(size["layers.1.w1"] * 3 / 64 / 1e6, 2) == 11.01
    assert round(layer(1, ("w1", "w3", "w2")) / 1e6, 2) == 704.64
    assert round(layer(1, ("ws1", "ws3", "ws2")) / 1e6, 2) == 11.01
    assert round(size["layers.1.gate"] / 1e6, 2) == 0.23
    frames = ["%s_%s" % (f, k) for f in ref.FRAMES for k in ref.HC_LEAVES]
    assert layer(3, frames) == 2 * (4 * 3584 * 24 + 24 + 3)
    assert round(layer(3, frames) / 1e6, 2) == 0.69
    assert round((size["embed"] + size["head"]) / 1e6, 2) == 939.52
    assert round(layer(0, ref.layer_leaves(REAL, 0)) / 1e6, 1) == 128.2
    assert round(layer(1, ref.layer_leaves(REAL, 1)) / 1e6, 1) == 745.0
    assert round(sum(size.values()) / 1e6, 1) == 4047.7
    # the program's tree holds the same parameters, a frame's in 3 leaves
    cfg = serve_xing4.program_config(REAL)
    flat = {n: jax.ShapeDtypeStruct(
        s, jnp.float32 if n.rsplit(".", 1)[-1] in ref.FLOAT32_LEAVES
        else jnp.bfloat16) for n, s, _ in ref.leaf_specs(REAL)}
    tree = jax.eval_shape(lambda w: serve_xing4.program_params(w, REAL), flat)
    assert sum(x.size for x in jax.tree.leaves(tree)) == sum(size.values())
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert 8.09e9 < nbytes < 8.11e9
    assert tree["layers"][2]["hc2_phi"].shape == (4 * 3584, 24)
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    lane = sum(x.size * x.dtype.itemsize
               for layer in row for x in layer.values())
    assert lane // cfg.max_len == 5760 and round(lane / 1e6, 1) == 53.1
    assert round(32 * lane / 1e9, 2) == 1.70


def test_the_programs_own_init_makes_the_runners_tree():
    """`init_params` and the runner's arrangement of the reference's
    weights agree leaf by leaf, shapes and types (at the toy size)."""
    cfg = serve_xing4.program_config(TINY)
    mine = tf.init_params(cfg, 0)
    theirs = serve_xing4.program_params(ref.init_weights(TINY, 0), TINY)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), mine) \
        == jax.tree.map(lambda x: (x.shape, str(x.dtype)), theirs)


def test_the_traffic_is_the_issues_64_pairs():
    traffic = MAN.traffic_of(MAN.cell(CELL))
    assert (traffic["kind"], traffic["clients"], traffic["pool"],
            traffic["pairing_seed"]) == ("closed-loop", 32, 64, 43)
    assert traffic["prompt"] == {"median": 3072, "sigma": 0.5, "lo": 1024,
                                 "hi": 8192}
    assert traffic["output"] == {"median": 160, "sigma": 0.5, "lo": 64,
                                 "hi": 384}
    assert (traffic["max_total"], traffic["greedy"], traffic["check_requests"],
            traffic["trace_seconds"]) == (9216, True, 6, 3)
    pool = length_pool(traffic)
    assert len(pool) == 64
    prompts, outputs = zip(*pool)
    assert (min(prompts), max(prompts)) == (1024, 8192)
    assert (min(outputs), max(outputs)) == (64, 384)
    assert 3400 < np.mean(prompts) < 3480 and 175 < np.mean(outputs) < 181
    assert max(p + o for p, o in pool) <= REAL["max_len"]
    # 9 blocks of 1,024 rows for the decode kernel
    from mxnet_tpu.kernels.latent_decode import latent_block
    assert latent_block(REAL["max_len"]) == 1024


def test_an_admission_of_the_real_stage_goes_in_chunks_of_2048():
    """2^25 stream elements a call: 2,048 tokens of four streams of
    3,584, so the mean prompt of 3,438 is two calls; the warm-up admits
    once for every width the pool uses."""
    traffic = MAN.traffic_of(MAN.cell(CELL))
    cfg = serve_xing4.program_config(REAL)
    assert serving.prefill_widths(cfg, 8192) == [2048] * 4
    assert serving.prefill_widths(cfg, 3438) == [2048, 2048]
    lengths = [p for p, _ in length_pool(traffic)]
    used = set()
    for n in lengths:
        used |= set(serving.prefill_widths(cfg, n))
    assert used <= {2048, 1024, 512, 256, 128, 64, 32, 16, 8}

    class Recorder(serve_xing4.Session):
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
    s = serve_xing4.Session(TINY, TRAFFIC, seed)
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
    logits give."""
    weights = ref.init_weights(TINY, 3)
    toks = _tokens(3, 45)
    served, control = ref.stream_gaps(weights, TINY, 20, toks)
    assert control is None and served.shape == (25,)
    padded = np.zeros((64,), np.int32)
    padded[:45] = toks
    logits = np.asarray(ref.forward_row(weights, jnp.asarray(padded), TINY))
    want = logits[19:44].max(-1) - logits[np.arange(19, 44), toks[20:45]]
    np.testing.assert_allclose(served, want, atol=1e-6)
    assert [ref.padded_width(n, REAL) for n in (1100, 4096, 4097, 8192,
                                                8193, 9216)] \
        == [2048, 4096, 8192, 8192, 9216, 9216]
    assert all(ref.padded_width(n, REAL) % ref.k2.BLOCK == 0
               for n in (1100, 5000, 9000))


def _run(trace=0, **kw):
    args = argparse.Namespace(seed=2, seconds=1.0, trace=trace)
    return run.run_cell(MAN, MAN.cell(CELL), args, config=TINY,
                        traffic=TRAFFIC, limits=TINY_SERVE, **kw)


def test_a_sound_served_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    # the p95 gap is not this cell's to bound: a round plus the
    # admissions that fell into it, 5.5% from seed to seed (PERF.md
    # section 6)
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


def test_a_traced_run_reports_the_counts_and_no_span_time():
    r = _run(trace=1)
    # counts are counts on any platform; the program_span metrics and
    # the rate over a span are host times, which a CPU run never reports
    assert set(r["metrics"]) == {"dispatches_per_token.serve",
                                 "device_idle.serve",
                                 "prefill_rows_live_share.serve"}
    assert 0 < r["metrics"]["prefill_rows_live_share.serve"]["value"] <= 100


def _forget(what):
    """A program that forgets one of the frame's three weights: H_res the
    identity (every stream keeps to itself), H_pre the mean of the
    streams, or H_post 1 on every stream."""
    real = tf._hc_weights

    def fault(x, phi, b, a, cfg):
        pre, post, res = real(x, phi, b, a, cfg)
        if what == "the mixing":
            res = jnp.broadcast_to(jnp.eye(res.shape[-1]), res.shape)
        elif what == "the read weights":
            pre = jnp.full_like(pre, 0.25)
        else:
            post = jnp.ones_like(post)
        return pre, post, res
    return fault


@pytest.mark.parametrize("what", ["the mixing", "the read weights",
                                  "the write weights"])
def test_a_program_that_forgets_part_of_the_frame_is_not_correct(
        monkeypatch, what):
    monkeypatch.setattr(tf, "_hc_weights", _forget(what))
    tf._PREFILL_JIT_CACHE.clear()
    try:
        assert not _run()["correct"]
    finally:
        monkeypatch.undo()
        tf._PREFILL_JIT_CACHE.clear()


# ------------------------------------------------- the two new metrics ---

COUNTERS = {"serving.prefill_tokens": 120000.0,
            "serving.prefill_rows": 150000.0, "hc.rows": 1.0}
CTX = {"trace": {"window_s": 3.0}, "device": {"platform": "tpu"}}
MS = 1000000


def test_prefill_rows_live_share_reads_its_two_counters(monkeypatch):
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))
    spec = manifest.load_layer_metric("prefill_rows_live_share.serve",
                                      MAN.root)
    assert spec["reader"] == "program_counter"
    entry = MAN.per_layer["prefill_rows_live_share.serve"]
    assert entry["workloads"] == [CELL] and entry["unit"] == "%"
    assert (entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == ("serving scheduler + cache", "serve_tok_s",
                                 "higher", "program_counter")
    assert program_counter.read(CTX, spec["args"]) == pytest.approx(80.0)
    assert program_counter.read({"trace": None}, spec["args"]) is None


def test_prefill_tok_s_reads_the_counter_over_the_spans_seconds(monkeypatch):
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))
    monkeypatch.setattr(program_span, "_totals", lambda: {
        "serving.prefill": {"count": 35, "total_ns": 2400 * MS},
        "serving.step": {"count": 100, "total_ns": 2900 * MS}})
    spec = manifest.load_layer_metric("prefill_tok_s.serve", MAN.root)
    assert spec["reader"] == "counter_per_span"
    entry = MAN.per_layer["prefill_tok_s.serve"]
    assert entry["workloads"] == [CELL] and entry["unit"] == "tokens/s"
    assert (entry["layer"], entry["moves"], entry["better"]) \
        == ("serving scheduler + cache", "serve_tok_s", "higher")
    assert counter_per_span.read(CTX, spec["args"]) \
        == pytest.approx(120000.0 / 2.4)
    # a host time taken on a CPU is not a number of this benchmark
    assert counter_per_span.read(dict(CTX, device={"platform": "cpu"}),
                                 spec["args"]) is None
    assert counter_per_span.read({"trace": None, "device": {}},
                                 spec["args"]) is None


@pytest.mark.parametrize("values,totals", [
    (None, {"serving.prefill": {"count": 1, "total_ns": MS}}),
    ({}, {"serving.prefill": {"count": 1, "total_ns": MS}}),
    ({"moe.picks": 5.0}, {"serving.prefill": {"count": 1, "total_ns": MS}}),
    (dict(COUNTERS), None), (dict(COUNTERS), {}),
    (dict(COUNTERS), {"serving.step": {"count": 1, "total_ns": MS}}),
], ids=["no-registry", "no-counter", "others-only", "no-totals",
        "empty-totals", "no-prefill-span"])
def test_a_program_without_the_counter_or_the_span_reads_none(
        monkeypatch, values, totals):
    """The parent commit, or a window without an admission."""
    monkeypatch.setattr(program_counter, "_values", lambda: values)
    monkeypatch.setattr(program_span, "_totals", lambda: totals)
    spec = manifest.load_layer_metric("prefill_tok_s.serve", MAN.root)
    assert counter_per_span.read(CTX, spec["args"]) is None
    if not values or "serving.prefill_rows" not in values:
        share = manifest.load_layer_metric("prefill_rows_live_share.serve",
                                           MAN.root)
        assert program_counter.read(CTX, share["args"]) is None


def test_the_cell_lists_what_applies_and_not_the_four_pinned_metrics():
    """The cell bounds `serve_tok_s` and `setup_s`, so it lists the
    per-layer metrics that move those two and none that moves
    `serve_itl_p95_ms` (six seeds spread that by 5.5%: PERF.md section
    6)."""
    mine = {m["name"] for g in ("end_to_end", "per_layer")
            for m in MAN.metrics_of(MAN.cell(CELL), g)}
    assert mine == {
        "setup_s", "serve_tok_s", "prefill_tok_s.serve",
        "prefill_rows_live_share.serve", "dispatches_per_token.serve",
        "device_idle.serve", "round_host_ms.serve", "sync_wait_share.serve",
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
    assert limits["control"] in OPERANDS and limits["served_logit_gap"] == 1.05
