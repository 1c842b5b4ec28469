"""The Nemotron-3-Nano stage against its plain reference (chipbench/reference/
nemotron_h.py, which imports nothing of the program), at a toy size on the
CPU: blocks of one sub-layer in the order M E M * E M, Mamba-2 in chunks of
8, 8 of 16 sigmoid-routed relu2 experts held, the same seeded weights on
both sides. The chip readings that set the real cell's limit are in
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
from chipbench.reference import nemotron_h as ref
from chipbench.reference.common import OPERANDS
from chipbench.runners import serve_nemotron_h
from chipbench.traffic import length_pool
from mxnet_tpu.models import serving, transformer as tf

HERE = os.path.dirname(__file__)
MAN = manifest.Manifest()
CONFIG = "nemotron3-nano-30b-a3b"
CELL = "nemotron3-nano-serve-subagent64"
REAL = MAN.config_of(MAN.cell(CELL))
TINY = json.load(open(os.path.join(HERE, "tiny", "nemotron_h.json")))
# tiny-size limit, set as the real one is: between the program's largest
# reading over seeds 1-6 (0.0054; the widest mean of a block of served
# tokens' gaps, here a stream's 40) and the float8 control's smallest
# (0.0145), near their geometric mean
TINY_SERVE = {"served_logit_gap": 0.0088}
# a whole toy run's limit: its three sampled streams are 12-24 served
# tokens, so a stream's mean is noisier than 40 tokens' (0.0001-0.0088
# over seeds 2-4; which streams a one-second window finishes depends on
# the machine's load), and the three forgotten parts below read
# 0.084-0.31
RUN_SERVE = {"served_logit_gap": 0.04}
TRAFFIC = dict(
    manifest.load_traffic("subagent64"), clients=3, pool=6, max_total=64,
    prompt={"median": 16, "sigma": 0.8, "lo": 4, "hi": 24},
    # no output under 12 tokens: a stream's gap is the mean over its
    # served tokens, and over two or three of them one near-tie that
    # bfloat16 breaks the other way reads 0.03 (seen under six workers,
    # where a slower window samples other streams)
    output={"median": 16, "sigma": 0.3, "lo": 12, "hi": 24},
    trace_seconds=0.3, check_requests=3, warm_max_s=30)
PUBLISHED = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


# ---------------------------------------------------- the configuration ---

def test_the_real_configuration_is_the_first_stage_of_the_deployment():
    cfg = serve_nemotron_h.program_config(REAL)
    kinds = {"M": "mamba2", "E": "ffn", "*": "attention"}
    assert tf._layer_kinds(cfg) == tuple(kinds[c] for c in "MEMEM*EMEMEM*")
    assert PUBLISHED.startswith(REAL["hybrid_override_pattern"])
    assert [PUBLISHED.count(c) for c in "ME*"] == [23, 23, 6]
    assert [REAL["hybrid_override_pattern"].count(c) for c in "ME*"] \
        == [6, 5, 2]
    assert not cfg.mixer_ffn and not tf._learned_pos(cfg)
    # every published width: the stream, the Mamba-2 sizes, the heads
    assert (cfg.d_model, cfg.n_heads, tf._kvh(cfg), tf._head_dim(cfg),
            cfg.max_len) == (2688, 32, 2, 128, 8192)
    assert tf._ssd_sizes(cfg) == (64, 64, 128, 8, 4) and cfg.ssd_chunk == 128
    # the router 128 wide and 6 a token; experts 0-63 held, 1,856 wide;
    # the one shared expert 3,712 wide, stated as two of the routed width
    assert tf._experts(cfg) == (128, 6, 0, 64, 1856)
    assert cfg.n_shared_experts * cfg.d_expert == 3712 \
        == REAL["moe_shared_expert_intermediate_size"]
    assert (cfg.ffn, cfg.expert_scoring, cfg.expert_scale, cfg.tied_head,
            cfg.norm_eps) == ("relu2", "sigmoid", 2.5, False, 1e-5)
    assert REAL["published"] == {
        "num_hidden_layers": 52, "hybrid_override_pattern": PUBLISHED,
        "n_routed_experts": 128, "vocab_size": 131072}
    assert MAN.configs[CONFIG]["reduced"] == REAL["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    # 2 chips a layer x 4 stages of 13 blocks; the guide's floors
    assert (REAL["chips_per_layer"], REAL["pipeline_stages"],
            REAL["expert_offset"]) == (2, 4, 0)
    assert REAL["pipeline_stages"] * REAL["num_hidden_layers"] == 52
    assert REAL["n_routed_experts"] * REAL["chips_per_layer"] == 128
    assert REAL["vocab_size"] * REAL["chips_per_layer"] == 131072
    assert REAL["num_hidden_layers"] >= 9 and REAL["n_routed_experts"] >= 8 \
        and REAL["vocab_size"] * 8 >= 131072
    assert REAL["max_len"] == MAN.traffic_of(MAN.cell(CELL))["max_total"]
    assert REAL["assumed"] and REAL["departures"] and REAL["deployment"]
    assert (REAL["compute_dtype"], REAL["state_dtype"]) \
        == ("bfloat16", "float32")
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    assert row[0]["ssm"].dtype == jnp.float32


def test_every_published_key_is_in_the_file_unchanged():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    want = {"hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
            "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
            "chunk_size": 128, "expand": 2, "num_attention_heads": 32,
            "num_key_value_heads": 2, "head_dim": 128,
            "moe_intermediate_size": 1856, "intermediate_size": 1856,
            "moe_shared_expert_intermediate_size": 3712,
            "n_shared_experts": 1, "num_experts_per_tok": 6,
            "routed_scaling_factor": 2.5, "norm_topk_prob": True,
            "n_group": 1, "topk_group": 1, "mlp_hidden_act": "relu2",
            "layer_norm_epsilon": 1e-05, "tie_word_embeddings": False,
            "time_step_min": 0.001, "time_step_max": 0.1,
            "time_step_floor": 0.0001, "rope_theta": 10000,
            "max_position_embeddings": 262144}
    assert {k: REAL[k] for k in want} == want
    if os.path.exists(catalog):
        row = next(json.loads(line) for line in open(catalog)
                   if '"NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"' in line)
        assert MAN.configs[CONFIG]["source"] == row["source_url"]
        differ = {k for k, v in row["config"].items() if REAL.get(k) != v}
        assert differ == set(REAL["reduced"])
        assert {k: row["config"][k] for k in differ} == REAL["published"]


def test_the_real_configuration_weighs_what_the_issue_counted():
    """Parameter counts from the reference's own shapes: 38.74 M a Mamba-2
    block, 23.40 M an attention block, 9.978 M a routed expert and 638.6 M
    in the 64 held, 19.96 M the shared expert, 0.34 M the router, 658.9 M
    an E block as held (1,297.5 M whole: 2.59 GB, five would not fit),
    176.2 M each in embedding and head: 3,926 M, 7.85 GB for this stage;
    whole, 23 x 38.74 + 23 x 1,297.5 + 6 x 23.40 + 704.6 = 31.58 B
    (published: 31.6 B). A lane holds 6 x (2.097 MB of float32 state +
    36.9 KB of conv window) = 12.8 MB and 2 x 8,192 K/V rows of 1,024
    bytes = 16.8 MB; 64 lanes 0.82 + 1.07 GB: 9.75 GB with the weights,
    9.97 GB as served, the experts' width padded to 1,920."""
    size = {name: int(np.prod(shape))
            for name, shape, _ in ref.leaf_specs(REAL)}

    def block(i):
        return sum(size["layers.%d.%s" % (i, k)]
                   for k in ref.layer_leaves(REAL, i))
    assert [round(block(i) / 1e6, 2) for i in (0, 5, 1)] \
        == [38.74, 23.4, 658.89]
    assert round(size["layers.1.w1"] * 2 / 64 / 1e6, 3) == 9.978
    assert round(size["layers.1.w1"] * 2 / 1e6, 1) == 638.6
    assert round(size["layers.1.ws1"] * 2 / 1e6, 2) == 19.96
    assert round(size["layers.1.gate"] / 1e6, 2) == 0.34
    whole = block(1) + size["layers.1.w1"] * 2
    assert round(whole / 1e6, 1) == 1297.5
    assert round((size["embed"] + size["head"]) / 1e6, 1) == 352.3
    assert round(sum(size.values()) / 1e6) == 3926
    assert round((23 * block(0) + 23 * whole + 6 * block(5)
                  + 4 * size["embed"] + size["ln_f"]) / 1e9, 2) == 31.58
    # the program's tree holds the same parameters
    cfg = serve_nemotron_h.program_config(REAL)
    flat = {n: jax.ShapeDtypeStruct(
        s, jnp.float32 if n.endswith("gate_bias") else jnp.bfloat16)
        for n, s, _ in ref.leaf_specs(REAL)}
    tree = ref.as_tree(flat, REAL)
    assert tree["layers"][0]["in_proj"].shape == (2688, 4096 + 6144 + 64)
    assert tree["layers"][0]["conv_w"].shape == (4, 6144)
    assert tree["layers"][5]["wq"].shape == (2688, 32, 128)
    assert tree["layers"][1]["w1"].shape == (64, 2688, 1856)
    assert tree["layers"][1]["ws2"].shape == (3712, 2688)
    specs = tf.param_specs(cfg)     # the program's own plan of leaves
    assert jax.tree.map(lambda x: 0, tree) == jax.tree.map(
        lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, (dict, list)))
    nbytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))
    assert round(nbytes / 1e9, 2) == 7.85
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    assert [sorted(layer) for layer in row] == [
        {"M": ["conv", "ssm"], "E": [], "*": ["k", "v"]}[c]
        for c in "MEMEM*EMEMEM*"]
    assert row[0]["ssm"].shape == (1, 64, 64, 128)
    assert row[0]["conv"].shape == (1, 3, 6144)
    nb = lambda layer: sum(x.size * x.dtype.itemsize for x in layer.values())
    assert round(nb(row[0]) / 1e6, 3) == 2.134 and nb(row[5]) == 8192 * 1024
    state, rows = 6 * nb(row[0]), 2 * nb(row[5])
    assert round(state / 1e6, 1) == 12.8 and round(rows / 1e6, 1) == 16.8
    assert round(64 * state / 1e9, 2) == 0.82 \
        and round(64 * rows / 1e9, 2) == 1.07
    assert round((nbytes + 64 * (state + rows)) / 1e9, 2) == 9.75
    # as served: the 64 x 5 x 2 expert matrices padded to 1,920, +0.22 GB
    served = jax.eval_shape(
        lambda w: serve_nemotron_h.program_sides(REAL, 0, w)[0], flat)
    assert served["layers"][1]["w1"].shape == (64, 2688, 1920)
    assert served["layers"][1]["w2"].shape == (64, 1920, 2688)
    assert served["layers"][1]["ws1"].shape == (2688, 3712)
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(served))
    assert round((held - nbytes) / 1e9, 2) == 0.22
    assert round((held + 64 * (state + rows)) / 1e9, 2) == 9.97


def test_the_programs_own_init_makes_the_runners_tree():
    """`init_params` and the runner's arrangement of the reference's
    weights agree leaf by leaf, shapes and types (at the toy size)."""
    cfg = serve_nemotron_h.program_config(TINY)
    mine = tf.init_params(cfg, 0)
    theirs = ref.as_tree(ref.init_weights(TINY, 0), TINY)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), mine) \
        == jax.tree.map(lambda x: (x.shape, str(x.dtype)), theirs)


def test_the_seeded_decays_lie_in_a_trained_models_range():
    """A_log = log U(1, 16) a head; dt_bias the inverse softplus of a
    step drawn log-uniformly from time_step_min to time_step_max."""
    w = ref.init_weights(TINY, 3, jnp.float32)
    a = np.exp(np.asarray(w["layers.0.A_log"]))
    step = np.log1p(np.exp(np.asarray(w["layers.0.dt_bias"])))
    assert 1.0 <= a.min() and a.max() <= 16.0 and a.std() > 1.0
    assert 0.00099 <= step.min() and step.max() <= 0.1001
    assert np.asarray(w["layers.1.gate_bias"]).dtype == np.float32


def test_a_program_that_cannot_state_the_fields_fails_before_any_weight(
        monkeypatch):
    """The parent commit's TransformerConfig has none of the fields: the
    constructor raises at once, and no weight was made."""
    import dataclasses
    old = dataclasses.make_dataclass("TransformerConfig", [
        (f.name, f.type, f) for f in dataclasses.fields(tf.TransformerConfig)
        if f.name != "mixer_ffn" and not f.name.startswith("ssd_")])
    monkeypatch.setattr(tf, "TransformerConfig", old)
    monkeypatch.setattr(ref, "init_weights", lambda *a, **k: 1 / 0)
    with pytest.raises(TypeError, match="unexpected keyword"):
        serve_nemotron_h.build(REAL, MAN.traffic_of(MAN.cell(CELL)), 1)


def test_the_traffic_is_the_issues_128_pairs():
    traffic = MAN.traffic_of(MAN.cell(CELL))
    assert (traffic["kind"], traffic["clients"], traffic["pool"],
            traffic["pairing_seed"]) == ("closed-loop", 64, 128, 50)
    assert traffic["prompt"] == {"median": 1536, "sigma": 0.8, "lo": 256,
                                 "hi": 6144}
    assert traffic["output"] == {"median": 384, "sigma": 0.7, "lo": 64,
                                 "hi": 1536}
    assert (traffic["max_total"], traffic["greedy"], traffic["check_requests"],
            traffic["trace_seconds"], traffic["warm_max_s"]) \
        == (8192, True, 6, 3, 90)
    pool = length_pool(traffic)
    assert len(pool) == 128
    prompts, outputs = zip(*pool)
    assert min(prompts) == 256 and max(prompts) == 6144
    assert min(outputs) == 64 and max(outputs) == 1536
    assert 1950 < np.mean(prompts) < 2050 and 470 < np.mean(outputs) < 490
    assert max(p + o for p, o in pool) <= REAL["max_len"]


def test_an_admission_of_the_real_stage_is_one_call():
    """2^25 stream elements a call: 8,192 tokens of a 2,688 stream, so
    every prompt of the pool is ONE bucketed call, and the chunked form
    runs whole chunks of 128 (every bucket from 256 up is a multiple):
    69.6% of the rows it scans are a prompt's own. The warm-up admits
    once for every width the pool uses."""
    traffic = MAN.traffic_of(MAN.cell(CELL))
    cfg = serve_nemotron_h.program_config(REAL)
    assert serving.prefill_widths(cfg, 6144) == [8192]
    assert serving.prefill_widths(cfg, 2000) == [2048]
    lengths = [p for p, _ in length_pool(traffic)]
    widths = [w for n in lengths for w in serving.prefill_widths(cfg, n)]
    assert len(widths) == len(lengths)
    assert set(widths) == {8192, 4096, 2048, 1024, 512, 256}
    assert all(w % cfg.ssd_chunk == 0 for w in widths)
    assert round(100 * sum(lengths) / sum(widths), 1) == 69.6

    class Recorder(serve_nemotron_h.Session):
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
    assert warmed == set(widths) and len(s.admitted) == 6


# ------------------------------------------ the program and the reference

@pytest.mark.parametrize("seed", [1, 2, 3, 4, 5, 6])
def test_served_streams_pass_and_the_float8_control_fails(seed):
    toks = _tokens(seed, 60)
    s = serve_nemotron_h.Session(TINY, TRAFFIC, seed)
    rid = s.admit(toks[:20], 40)
    done = {}
    while rid not in done:
        done.update(s.step())
    out = s.reference([(20, done[rid])], operand="fp8")[0]
    sound = compare.serving_checks([out["gaps"]], 0, 1, TINY_SERVE)
    assert all(c["ok"] for c in sound), sound
    control = compare.serving_checks([out["control_gaps"]], 0, 1, TINY_SERVE)
    assert not control[0]["ok"], control


def test_the_reference_scans_position_by_position_and_imports_no_program():
    """Its source names no module of the program, and its recurrence is a
    scan over T positions: the state after a sequence is the state after
    its two halves, the second started from the first's."""
    import inspect
    source = inspect.getsource(ref)
    assert "mxnet_tpu" not in source and "HIGHEST" in inspect.getsource(
        __import__("chipbench.reference.common", fromlist=["x"]))
    w = ref.init_weights(TINY, 2, jnp.float32)
    p = {k: w["layers.0." + k] for k in ref.LEAVES["M"]}
    sizes, eps = ref.mamba_sizes(TINY), TINY["layer_norm_epsilon"]
    x = jnp.asarray(np.random.RandomState(1).randn(12, 64), jnp.float32)
    _, whole = ref.mamba2(x, p, ref.exact, eps, sizes)
    assert whole.shape == (8, 8, 16)
    # a conv window of zeros between the halves: compare the states of
    # two runs that share it, a decayed first half plus the second's own
    _, first = ref.mamba2(x[:5], p, ref.exact, eps, sizes)
    _, tail = ref.mamba2(x[5:], p, ref.exact, eps, sizes)
    _, joined = ref.mamba2(x[5:], p, ref.exact, eps, sizes, first)
    delta = jax.nn.softplus(
        (x[5:] @ p["in_proj"])[:, -8:] + p["dt_bias"])
    keep = jnp.exp(jnp.sum(delta, axis=0) * -jnp.exp(p["A_log"]))
    np.testing.assert_allclose(joined, tail + keep[:, None, None] * first,
                               atol=1e-6)


def test_the_reference_heads_only_the_rows_that_chose_a_served_token():
    weights = ref.init_weights(TINY, 3)
    toks = _tokens(3, 45)
    served, control = ref.stream_gaps(weights, TINY, 20, toks)
    assert control is None and served.shape == (25,)
    padded = np.zeros((64,), np.int32)
    padded[:45] = toks
    logits = np.asarray(ref.forward_row(weights, jnp.asarray(padded), TINY))
    want = logits[19:44].max(-1) - logits[np.arange(19, 44), toks[20:45]]
    np.testing.assert_allclose(served, want, atol=1e-6)
    assert [ref.padded_width(n, REAL) for n in (300, 1024, 1025, 4097,
                                                8192)] \
        == [1024, 1024, 2048, 8192, 8192]
    assert all(ref.padded_width(n, REAL) % ref.BLOCK == 0
               for n in (300, 3000, 7000))


def _run(trace=0, **kw):
    args = argparse.Namespace(seed=2, seconds=1.0, trace=trace)
    return run.run_cell(MAN, MAN.cell(CELL), args, config=TINY,
                        traffic=TRAFFIC, limits=RUN_SERVE, **kw)


def test_a_sound_served_run_is_correct():
    r = _run()
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"serve_tok_s", "setup_s"}


def test_a_traced_run_reports_the_counts_and_no_span_time():
    r = _run(trace=1)
    # counts are counts on any platform; the program_span metrics are
    # host times, which a CPU run never reports
    assert set(r["metrics"]) == {"dispatches_per_token.serve",
                                 "device_idle.serve",
                                 "ssd_rows_live_share.serve",
                                 "ssd_lanes_live_share.serve"}
    for name in ("ssd_rows_live_share.serve", "ssd_lanes_live_share.serve"):
        assert 0 < r["metrics"][name]["value"] <= 100


def _forget(what):
    """A program that forgets part of the architecture: the squared ReLU
    (another activation), the experts' scale of 2.5, or which half of
    the 16 experts it holds."""
    def fault(cfg):
        import dataclasses
        real = serve_nemotron_h.program_config(cfg)
        if what == "the squared relu":
            return dataclasses.replace(real, ffn="gelu")
        if what == "the experts' scale":
            return dataclasses.replace(real, expert_scale=1.0)
        return dataclasses.replace(real, experts_held=(8, 8))
    return fault


@pytest.mark.parametrize("what", ["the squared relu", "the experts' scale",
                                  "which experts it holds"])
def test_a_program_that_forgets_part_of_the_layer_is_not_correct(
        monkeypatch, what):
    fault = _forget(what)
    monkeypatch.setattr(
        serve_nemotron_h.Session, "__init__",
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

COUNTERS = {"ssd.rows_live": 6 * 256030.0, "ssd.rows_scanned": 6 * 367616.0,
            "ssd.lane_steps": 6 * 64 * 1000.0,
            "ssd.lane_steps_live": 6 * 61 * 1000.0, "moe.picks": 1.0}
CTX = {"trace": {"window_s": 3.0}, "device": {"platform": "tpu"}}


@pytest.mark.parametrize("metric,num,den,layer,want", [
    ("ssd_rows_live_share.serve", "ssd.rows_live", "ssd.rows_scanned",
     "serving scheduler + cache", 100 * 256030 / 367616),
    ("ssd_lanes_live_share.serve", "ssd.lane_steps_live", "ssd.lane_steps",
     "model step", 100 * 61 / 64),
])
def test_each_metric_reads_its_two_counters(monkeypatch, metric, num, den,
                                            layer, want):
    monkeypatch.setattr(program_counter, "_values", lambda: dict(COUNTERS))
    spec = manifest.load_layer_metric(metric, MAN.root)
    assert spec == {"reader": "program_counter",
                    "args": {"num": [num], "den": [den], "scale": 100.0}}
    entry = MAN.per_layer[metric]
    # `in`, not `==`: a later cell with Mamba-2 blocks may list them too
    assert CELL in entry["workloads"] and entry["unit"] == "%"
    assert (entry["layer"], entry["moves"], entry["better"],
            entry["source"]) == (layer, "serve_tok_s", "higher",
                                 "program_counter")
    assert program_counter.read(CTX, spec["args"]) == pytest.approx(want)
    assert program_counter.read({"trace": None}, spec["args"]) is None


@pytest.mark.parametrize("values", [None, {}, {"moe.picks": 5.0},
                                    {"ssd.rows_live": 0.0,
                                     "ssd.rows_scanned": 0.0,
                                     "ssd.lane_steps": 0.0,
                                     "ssd.lane_steps_live": 0.0}],
                         ids=["no-registry", "no-counter", "others-only",
                              "nothing-counted"])
def test_a_program_without_the_counters_reads_none(monkeypatch, values):
    """The parent commit, or a window without a round."""
    monkeypatch.setattr(program_counter, "_values", lambda: values)
    for metric in ("ssd_rows_live_share.serve", "ssd_lanes_live_share.serve"):
        spec = manifest.load_layer_metric(metric, MAN.root)
        assert program_counter.read(CTX, spec["args"]) is None


def test_the_cell_lists_what_applies_and_not_the_pinned_metrics():
    """The cell bounds `serve_tok_s` and `setup_s`, so it lists the
    per-layer metrics that move those two and none that moves
    `serve_itl_p95_ms` (PERF.md section 6 has the seeds' spread)."""
    mine = {m["name"] for g in ("end_to_end", "per_layer")
            for m in MAN.metrics_of(MAN.cell(CELL), g)}
    assert mine == {
        "setup_s", "serve_tok_s",
        "ssd_rows_live_share.serve", "ssd_lanes_live_share.serve",
        "dispatches_per_token.serve", "device_idle.serve",
        "round_host_ms.serve", "sync_wait_share.serve",
        "decode_wait_ms.serve", "dispatch_ahead_share.serve",
        "backend_init_s.startup", "trace_lower_s.startup",
        "compile_s.startup", "cache_load_s.startup", "cache_misses.startup",
        "programs.startup", "cold_call_s.serve", "batcher_build_s.serve"}
    ends = {m["name"] for m in MAN.metrics_of(MAN.cell(CELL), "end_to_end")}
    assert ends == {"setup_s", "serve_tok_s"}
    assert MAN.traffic_of(MAN.cell(CELL))["end_to_end"] \
        == {"serve_tok_s": "rate"}
    for m in MAN.metrics_of(MAN.cell(CELL), "per_layer"):
        assert m["moves"] in ends, m["name"]
    assert MAN.cell(CELL)["chips"] == 1
    limits = manifest.load_limits(CELL)
    assert limits["control"] in OPERANDS and limits["readings"]
    # between the largest sound reading on the chip and the smallest
    # control's, with more than 1.5x of room on each side
    assert 1.5 * 0.2428 < limits["served_logit_gap"] == 0.41 < 0.6907 / 1.5
