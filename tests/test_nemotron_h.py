"""Blocks of ONE sub-layer (a Mamba-2 mixer, routed relu2 experts, or GQA
attention, each alone) through transformer.py and the ContinuousBatcher at
a toy size on the CPU, against their plain reference (chipbench/reference/
nemotron_h.py, which imports nothing of the program and runs the
recurrence position by position): the order M E M * E M, 8 Mamba-2 heads of
8 channels (not expand x hidden), 16 states, 2 groups of 4 heads, 4 taps,
chunks of 8; 4 query heads on 2 K/V heads of 32; 8 of 16 sigmoid-routed
relu2 experts held, 3 a token, a shared expert twice as wide. The same
seeded weights on both sides; float32 unless a case says otherwise, where
1e-4 is what sums taken in another order leave (readings 2e-7 to 5e-7) and
a float8 control reads 1e-2 or more."""

import dataclasses
import json
import os
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import nemotron_h as ref
from chipbench.reference.common import fp8_operand
from chipbench.runners import serve_nemotron_h
from mxnet_tpu.models import serving, ssd, transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

TINY = json.load(open(os.path.join(
    os.path.dirname(__file__), "bench_harness", "tiny", "nemotron_h.json")))
SIZES = ref.mamba_sizes(TINY)           # (H, P, N, G, K) = (8, 8, 16, 2, 4)
EPS = TINY["layer_norm_epsilon"]
TOL = 1e-4


def _sides(seed, dtype=jnp.float32, config=TINY):
    """(program params, program config, reference weights)."""
    weights = ref.init_weights(config, seed, dtype)
    cfg = dataclasses.replace(serve_nemotron_h.program_config(config),
                              dtype=dtype)
    return ref.as_tree(weights, config), cfg, weights


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


def _reference_logits(weights, toks, config=TINY, q=ref.exact):
    return np.asarray(ref.forward_row(weights, jnp.asarray(toks), config, q))


@pytest.fixture(scope="module")
def sides():
    return _sides(5)


@pytest.fixture(scope="module")
def mixer(sides):
    """A Mamba-2 block's leaves as float32 (block 0), with the decays
    and the skip drawn away from their round initial values."""
    rng = np.random.RandomState(11)
    p = {k: v.astype(jnp.float32) for k, v in sides[0]["layers"][0].items()}
    return dict(p, D=jnp.asarray(rng.uniform(0.5, 1.5, 8), jnp.float32),
                y_norm=jnp.asarray(rng.uniform(0.5, 1.5, 64), jnp.float32))


@pytest.fixture
def telemetry(monkeypatch):
    """MXNET_OBS on from a clean registry, and nothing left behind (see
    tests/test_kimi_linear.py)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    obs.reset()
    yield monkeypatch
    attribution.reset()
    obs.reset()


# the two forms as one program each (eagerly a call is some hundred
# launches)
SEQ = jax.jit(ssd.mixer_seq, static_argnums=(4, 5))
STEP = jax.jit(ssd.mixer_step, static_argnums=(3,))


def _rows(seed, t):
    return jnp.asarray(np.random.RandomState(seed).randn(t, 64), jnp.float32)


def _state(seed=None):
    zero = ssd.init_state(*SIZES, 1, jnp.float32)
    if seed is None:
        return zero
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(rng.randn(*v.shape), jnp.float32)
            for k, v in zero.items()}


# ---------------------------------------------------- the configuration ---

def test_the_toy_configuration_states_the_architecture():
    cfg = serve_nemotron_h.program_config(TINY)
    assert tf._layer_kinds(cfg) == ("mamba2", "ffn", "mamba2", "attention",
                                    "ffn", "mamba2")
    assert not cfg.mixer_ffn and not tf._learned_pos(cfg)
    assert tf._ssd_sizes(cfg) == SIZES and cfg.ssd_chunk == 8
    # E = heads x head size, not expand x hidden
    assert SIZES[0] * SIZES[1] == 64 != TINY["expand"] * TINY["hidden_size"]
    assert (tf._head_dim(cfg), cfg.d_model // cfg.n_heads, tf._kvh(cfg)) \
        == (32, 16, 2)
    assert tf._experts(cfg) == (16, 3, 0, 8, 32)
    # the one shared expert, twice a routed expert's width
    assert cfg.n_shared_experts * cfg.d_expert == 64
    assert (cfg.ffn, cfg.expert_scoring, cfg.expert_scale, cfg.tied_head,
            cfg.norm_eps) == ("relu2", "sigmoid", 2.5, False, 1e-5)
    # the plan says which blocks route: the "ffn" ones, and no other
    assert [tf._has_experts(cfg, i) for i in range(6)] \
        == [False, True, False, False, True, False]
    # a configuration that holds the new fields still hashes by value
    assert dataclasses.astuple(cfg) == dataclasses.astuple(
        serve_nemotron_h.program_config(TINY))


def test_a_block_has_the_leaves_of_its_one_sub_layer(sides):
    """No `ln2` and no feed-forward leaf in a block with a mixer, no `ln1`
    and no mixer leaf in an "ffn" block, whose state has no leaves; the
    program's own init makes the runner's tree, leaf for leaf."""
    params, cfg, _ = sides
    mine = tf.init_params(cfg, 0)
    assert jax.tree.map(lambda x: (x.shape, str(x.dtype)), mine) \
        == jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    m, e, a = (set(mine["layers"][i]) for i in (0, 1, 3))
    assert m == {"ln1", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log",
                 "D", "y_norm", "out_proj"}
    assert e == {"ln2", "gate", "gate_bias", "w1", "w2", "ws1", "ws2"}
    assert a == {"ln1", "wq", "wk", "wv", "wo"}
    assert mine["layers"][0]["in_proj"].shape == (64, 64 + (64 + 64) + 8)
    assert mine["layers"][0]["conv_w"].shape == (4, 128)
    assert mine["layers"][3]["wq"].shape == (64, 4, 32)
    assert "pos" not in mine and mine["head"].shape == (256, 64)
    specs = tf.param_specs(cfg)
    assert jax.tree.map(lambda x: 0, mine) == jax.tree.map(
        lambda x: 0, specs, is_leaf=lambda x: not isinstance(x, (dict, list)))
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 3))
    assert [sorted(layer) for layer in row] == [
        ["conv", "ssm"], [], ["conv", "ssm"], ["k", "v"], [],
        ["conv", "ssm"]]
    assert row[0]["ssm"].shape == (3, 8, 8, 16) \
        and row[0]["ssm"].dtype == jnp.float32
    assert row[0]["conv"].shape == (3, 3, 128) and row[3]["k"].shape \
        == (3, 64, 2, 32)
    # the default keeps both sub-layers in every block
    both = tf.init_params(dataclasses.replace(
        cfg, mixer_ffn=True, layer_kinds=("mamba2",) * 6), 0)["layers"][0]
    assert {"ln1", "ln2", "gate", "in_proj"} <= set(both)


@pytest.mark.parametrize("bad,match", [
    (dict(layer_kinds=("mamba2", "mlp") * 3), "'ffn'"),
    (dict(ssd_groups=3), "groups"),
    (dict(ssd_chunk=0), "ssd_chunk"),
    (dict(hc_mult=2), "one sub-layer"),
    (dict(ffn="relu"), "relu2"),
])
def test_a_configuration_that_cannot_be_built_is_refused(sides, bad, match):
    cfg = dataclasses.replace(sides[1], **bad)
    with pytest.raises(ValueError, match=match):
        tf.forward(tf.init_params(cfg, 0), jnp.zeros((1, 8), jnp.int32), cfg)


# ------------------------------------- the chunked form and the step form

@pytest.mark.parametrize("t", [5, 8, 19, 32], ids=[
    "below-a-chunk", "one-chunk", "no-multiple", "four-chunks"])
@pytest.mark.parametrize("start", [None, 7], ids=["from-zero", "continues"])
def test_the_chunked_form_equals_the_recurrence(mixer, t, start):
    """Chunks of 8 (2 groups under 8 heads) against the reference's
    position-by-position scan, from zeros and from a non-zero state
    (what a chunked admission's second call starts from): outputs and
    the state behind the last position. The reference has no conv
    window to continue from, so a continued run starts its window at
    zeros on both sides."""
    x, state = _rows(t, t), _state(start)
    state["conv"] = jnp.zeros_like(state["conv"])
    want, s_want = ref.mamba2(x, mixer, ref.exact, EPS, SIZES,
                              state["ssm"][0])
    got, new = SEQ(x[None], mixer, state, None, EPS, 8)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    np.testing.assert_allclose(new["ssm"][0], s_want, atol=1e-5)
    # whatever the chunk: one of 128 holds the whole sequence
    whole, _ = SEQ(x[None], mixer, state, None, EPS, ssd.CHUNK)
    np.testing.assert_allclose(whole[0], want, atol=1e-5)


@pytest.mark.parametrize("valid", [1, 8, 13])
def test_padded_rows_leave_no_trace(mixer, valid):
    """valid_len < T: the state, conv window included, is the state after
    valid_len - 1 EXACTLY (what the same call over the real rows alone
    leaves), and the real rows' outputs do not move."""
    x = _rows(3, 24)
    alone, s_alone = SEQ(x[None, :valid], mixer, _state(2), None,
                                   EPS, 8)
    got, s_got = SEQ(x[None], mixer, _state(2),
                               jnp.int32(valid), EPS, 8)
    np.testing.assert_allclose(got[0, :valid], alone[0], atol=1e-6)
    for leaf in ("ssm", "conv"):
        np.testing.assert_array_equal(s_got[leaf], s_alone[leaf])


def test_the_step_form_continues_the_sequence_form(mixer):
    """13 rows by the sequence form, then 6 one at a time by the step
    form from its state: the sequence form over all 19."""
    x = _rows(4, 19)
    want, s_want = SEQ(x[None], mixer, _state(), None, EPS, 8)
    _, state = SEQ(x[None, :13], mixer, _state(), None, EPS, 8)
    for i in range(13, 19):
        y, state = STEP(x[None, i], mixer, state, EPS)
        np.testing.assert_allclose(y[0], want[0, i], atol=1e-5)
    for leaf in ("ssm", "conv"):
        np.testing.assert_allclose(state[leaf], s_want[leaf], atol=1e-5)


def test_the_state_is_float32_and_bfloat16_would_not_do(mixer):
    """A bfloat16 model's lane keeps its recurrence in float32. Were the
    state rounded to bfloat16 between steps, 200 slow steps (delta a
    near -0.002: a step adds a few thousandths of the state, under half
    a bfloat16 ulp of it) would read 1e-2 away from the float32 state;
    kept in float32 they read 1e-5."""
    slow = dict(mixer, dt_bias=jnp.full((8,), -6.0), A_log=jnp.zeros((8,)))
    x = _rows(6, 200)
    want = ref.mamba2(x, slow, ref.exact, EPS, SIZES)[1]

    @partial(jax.jit, static_argnums=0)
    def run(kept):
        def step(i, state):
            state = ssd.mixer_step(x[None, i], slow, state, EPS)[1]
            return dict(state, ssm=state["ssm"].astype(kept)
                        .astype(jnp.float32))
        return jax.lax.fori_loop(0, 200, step, _state())

    state, lossy = run(jnp.float32), run(jnp.bfloat16)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(state["ssm"][0] - want))) < 1e-5 * scale
    assert float(jnp.max(jnp.abs(lossy["ssm"][0] - want))) > 1e-2 * scale
    cfg = serve_nemotron_h.program_config(TINY)
    assert cfg.dtype == jnp.bfloat16
    row = jax.eval_shape(lambda: tf.init_cache(cfg, 1))
    assert row[0]["ssm"].dtype == jnp.float32 \
        and row[0]["conv"].dtype == jnp.bfloat16
    _, new = tf.decode_step(tf.init_params(cfg, 0), tf.init_cache(cfg, 1),
                            jnp.zeros((1,), jnp.int32), jnp.int32(0), cfg)
    assert new[0]["ssm"].dtype == jnp.float32


# ------------------------------------------ the program and the reference

@pytest.mark.parametrize("dtype,stat,tol,why", [
    (jnp.float32, jnp.max, TOL, "float32 both sides, sums in another order"),
    # the MEAN gap over all logits: bfloat16 through 6 blocks reads
    # 0.0022-0.0071 over seeds 1-6, float8 operands 0.032-0.041; the limit
    # is near their geometric mean. The widest single logit has less room
    # (0.09-0.36 against 0.41-0.66): a pick of 3 in 16 that bfloat16
    # orders otherwise than float32 moves a whole expert of this toy
    # model, as in tests/test_smallthinker.py
    (jnp.bfloat16, jnp.mean, 0.015,
     "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, stat, tol, why):
    params, cfg, weights = _sides(3, dtype)
    toks = _tokens(3, 64)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks[None])[0]
    want = _reference_logits(weights, toks)
    gap = float(stat(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)
    control = _reference_logits(weights, toks, q=fp8_operand)
    assert float(stat(jnp.abs(control - want))) > tol


@pytest.mark.parametrize("t_p", [5, 21, 40])
def test_prefill_then_decode_equals_the_full_forward(sides, t_p):
    params, cfg, weights = sides
    toks = _tokens(t_p, 48)
    want = _reference_logits(weights, toks)
    logits, cache = jax.jit(lambda p, c, t: tf.prefill(p, c, t, cfg))(
        params, tf.init_cache(cfg, 1), toks[None, :t_p])
    np.testing.assert_allclose(logits[0], want[t_p - 1], atol=TOL)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for i in range(t_p, 48):
        logits, cache = step(params, cache, toks[i:i + 1], jnp.int32(i))
        np.testing.assert_allclose(logits[0], want[i], atol=TOL)
    assert cache[1] == {} and cache[4] == {}


@pytest.mark.parametrize("widths", [[(13, 16), (17, 32)], [(8, 8), (3, 4),
                                                           (19, 32)]],
                         ids=["two-calls", "three-calls"])
def test_an_admission_in_chunks_equals_the_one_call(sides, widths):
    """prefill_chunk with a logits row, bucket by bucket as the batcher
    admits: every call's padding leaves no trace in the three states,
    and each continues from the one before (a chunk of the chunked form
    from a non-zero state)."""
    params, cfg, weights = sides
    toks = _tokens(8, 30)
    want = _reference_logits(weights, toks)
    fn = tf._jitted_prefill_chunk_row(cfg)
    cache, at = tf.init_cache(cfg, 1), 0
    for n, width in widths:
        padded = np.zeros((1, width), np.int32)
        padded[0, :n] = toks[at:at + n]
        logits, cache = fn(params, cache, jnp.asarray(padded), jnp.int32(at),
                           jnp.int32(n - 1))
        at += n
        np.testing.assert_allclose(logits[0], want[at - 1], atol=TOL)
    # and every row's logits from the same entry point without one
    logits, _ = tf._jitted_prefill_chunk(cfg)(
        params, tf.init_cache(cfg, 1), toks[None], jnp.int32(0))
    np.testing.assert_allclose(logits[0], want, atol=TOL)


# ------------------------------------------------- relu2 and the share ---

def _relu2_sides(d, f):
    """One "ffn" block d wide: experts 2-5 of 8 held, f wide, 3 a token,
    and a shared expert of 2 f."""
    cfg = tf.TransformerConfig(
        d_model=d, n_heads=2, n_layers=1, layer_kinds=("ffn",), ffn="relu2",
        n_experts=8, experts_per_token=3, expert_scoring="sigmoid",
        expert_scale=2.5, experts_held=(2, 4), d_expert=f,
        n_shared_experts=2, max_len=32)
    return cfg, tf.init_params(cfg, 1)["layers"][0]


@pytest.mark.parametrize("d,f,arm", [(32, 48, "ragged_dot"),
                                     (128, 128, "kernel")])
def test_relu2_experts_equal_the_references(d, f, arm):
    """w2 relu(w1 x)^2, no w3, routed and shared: through
    jax.lax.ragged_dot at a toy width and through kernels/
    grouped_matmul.py's kernel (interpreted) where 128 divides the
    widths. 1e-4 of the output's scale: float32, sums in another
    order."""
    from mxnet_tpu.kernels.grouped_matmul import grouped_tiles
    cfg, p = _relu2_sides(d, f)
    assert "w3" not in p and "ws3" not in p and p["ws1"].shape == (d, 2 * f)
    assert (grouped_tiles(20 * 3, d, f, 4) is not None) == (arm == "kernel")
    x = jnp.asarray(np.random.RandomState(2).randn(20, d), jnp.float32)
    loads = []
    got = tf._expert_ffn(x[None], p, cfg, loads)[0]
    want = ref.experts_part(x, p, ref.exact, 3, 2.5, 2) \
        + ref.shared_part(x, p, ref.exact)
    np.testing.assert_allclose(got, want,
                               atol=1e-4 * float(jnp.max(jnp.abs(want))))
    assert int(loads[0].sum()) < 60         # some picks fell elsewhere
    # a gate would change the result: the form is not gated_relu's
    gated = dataclasses.replace(cfg, ffn="gated_relu")
    assert float(jnp.max(jnp.abs(tf._expert_ffn(
        x[None], dict(p, w3=-p["w1"], ws3=-p["ws1"]), gated, None)[0]
        - want))) > 1e-2


@pytest.mark.parametrize("ffn", ["relu2", "gelu", "gated_silu",
                                 "gated_relu"])
def test_a_padded_expert_width_is_the_same_function(ffn):
    """pad_expert_width: 96 hidden units padded by 32 zero ones to the
    128 lanes the kernel's blocks are made of. Every form of the
    feed-forward reads act(0) = 0 there, so the padded experts give what
    the published ones give (1e-5 of the output's scale: float32, sums
    in another order), through the kernel where the published width
    kept jax.lax.ragged_dot. The layers are refilled in the caller's own
    list, and a width the lanes divide comes back as it is."""
    from mxnet_tpu.kernels.grouped_matmul import grouped_tiles
    cfg, p = _relu2_sides(128, 96)
    cfg = dataclasses.replace(cfg, ffn=ffn)
    if ffn.startswith("gated"):
        rng = np.random.RandomState(4)
        p = dict(p, w3=jnp.asarray(rng.randn(*p["w1"].shape) / 11,
                                   jnp.float32),
                 ws3=jnp.asarray(rng.randn(*p["ws1"].shape) / 11,
                                 jnp.float32))
    x = jnp.asarray(np.random.RandomState(2).randn(1, 20, 128), jnp.float32)
    want = tf._expert_ffn(x, p, cfg, None)
    tree = {"layers": [dict(p), {"ln1": 1}]}
    layers = tree["layers"]
    padded, wide = tf.pad_expert_width(tree, cfg)
    assert padded is tree and tree["layers"] is layers
    assert wide.d_expert == 128 and cfg.d_expert == 96
    q = layers[0]
    assert q["w1"].shape == (4, 128, 128) and q["w2"].shape == (4, 128, 128)
    assert q["ws1"] is p["ws1"] and q["gate"] is p["gate"] \
        and layers[1] == {"ln1": 1}
    assert not np.asarray(q["w1"][:, :, 96:]).any() \
        and not np.asarray(q["w2"][:, 96:]).any()
    assert grouped_tiles(60, 128, 96, 4) is None \
        and grouped_tiles(60, 128, 128, 4) is not None
    got = tf._expert_ffn(x, q, wide, None)
    np.testing.assert_allclose(got, want,
                               atol=1e-5 * float(jnp.max(jnp.abs(want))))
    again, same = tf.pad_expert_width(tree, wide)
    assert same.d_expert == 128 and again["layers"][0] is q
    dense = tf.TransformerConfig(d_model=32, n_heads=2, n_layers=1)
    assert tf.pad_expert_width({"layers": []}, dense)[1] is dense


def test_the_runner_serves_the_experts_at_a_width_the_lanes_divide():
    """At the toy size 32 -> 128; the served streams still follow the
    unpadded reference (tests/bench_harness/test_bench_nemotron_h.py)."""
    params, cfg = serve_nemotron_h.program_sides(TINY, 3)
    assert cfg.d_expert == 128 and TINY["moe_intermediate_size"] == 32
    assert params["layers"][1]["w1"].shape == (8, 64, 128)
    assert params["layers"][1]["w2"].shape == (8, 128, 64)
    assert params["layers"][1]["ws1"].shape == (64, 64)
    plain = ref.as_tree(ref.init_weights(TINY, 3), TINY)
    toks = _tokens(3, 24)[None]
    got = tf.forward(params, toks, cfg)
    want = tf.forward(plain, toks, serve_nemotron_h.program_config(TINY))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_the_dense_relu2_feed_forward_has_no_gate():
    cfg = tf.TransformerConfig(d_model=32, n_heads=2, n_layers=1, d_ff=48,
                               ffn="relu2", max_len=16)
    p = tf.init_params(cfg, 0)["layers"][0]
    assert "w3" not in p and "w3" not in tf.param_specs(cfg)["layers"][0]
    x = jnp.asarray(np.random.RandomState(1).randn(1, 5, 32), jnp.float32)
    want = jnp.square(jax.nn.relu(x @ p["w1"])) @ p["w2"]
    np.testing.assert_allclose(tf._ffn(x, p, cfg), want, atol=1e-5)


def test_two_shares_of_the_experts_add_up_to_the_uncut_block(sides):
    """The guide's share test: the 16 routed experts as two shares of 8
    (experts_held, as two chips of a layer hold them), each routing over
    all 16 and computing its own experts' part, the shared expert, which
    every chip computes alike, counted ONCE: the sum is the uncut
    reference's E block, and the share the cell runs is its part."""
    _, cfg, _ = sides
    uncut = dict(TINY, n_routed_experts=16, expert_offset=0)
    full = ref.init_weights(uncut, 4, jnp.float32)
    p = {k: full["layers.1." + k] for k in ref.LEAVES["E"]}
    x = jnp.asarray(np.random.RandomState(3).randn(24, 64), jnp.float32)
    want = ref.block(x, p, "E", ref.exact, EPS, (3, 2.5, 0),
                     SIZES) - x                         # the block's f(norm x)
    h = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + EPS)
    total = 0
    for first in (0, 8):
        share = dict(p, **{k: p[k][first:first + 8] for k in ("w1", "w2")})
        share.pop("ws1"), share.pop("ws2")              # the routed part
        held = dataclasses.replace(cfg, experts_held=(first, 8))
        part = tf._expert_ffn(h[None], share, held, None)[0]
        np.testing.assert_allclose(
            part, ref.experts_part(h, share, ref.exact, 3, 2.5, first),
            atol=1e-5)
        total = total + part
    total = total + tf._mlp(h[None], p["ws1"], p["ws2"], None, cfg)[0]
    np.testing.assert_allclose(total, want, atol=1e-5)


# ------------------------------------------------------------- refusals ---

def _mesh(**axes):
    from jax.sharding import Mesh
    n = int(np.prod(list(axes.values())))
    return Mesh(np.array(jax.devices()[:n]).reshape(tuple(axes.values())),
                tuple(axes))


@pytest.mark.parametrize("what,call", [
    ("paged", lambda p, c: ContinuousBatcher(p, c, max_batch=2, paged=True)),
    ("spec_k", lambda p, c: ContinuousBatcher(p, c, max_batch=2, spec_k=2)),
    ("kv_cache_int8", lambda p, c: ContinuousBatcher(
        p, dataclasses.replace(c, kv_cache_int8=True), max_batch=2)),
    ("kv_cache_int8", lambda p, c: tf.init_cache(
        dataclasses.replace(c, kv_cache_int8=True), 1)),
    ("paged KV pool", lambda p, c: tf.init_paged_cache(c, 8, 4)),
    ("speculative verification", lambda p, c: tf.verify_chunk(
        p, tf.init_cache(c, 1), jnp.zeros((1, 3), jnp.int32),
        jnp.zeros((1,), jnp.int32), c)),
    ("speculative decoding", lambda p, c: tf.speculative_generate(
        p, p, jnp.zeros((1, 4), jnp.int32), 4, c, c)),
    ("mesh-sharded forward", lambda p, c: tf.forward(
        p, jnp.zeros((2, 8), jnp.int32), c, mesh=_mesh(dp=2))),
    ("shard_params", lambda p, c: tf.shard_params(p, c, _mesh(tp=2))),
    ("shard_cache", lambda p, c: tf.shard_cache(
        tf.init_cache(c, 2), c, _mesh(dp=2))),
])
def test_what_cannot_carry_the_state_refuses_the_kind_by_name(sides, what,
                                                              call):
    params, cfg, _ = sides
    with pytest.raises(ValueError, match="'mamba2'") as e:
        call(params, cfg)
    assert what in str(e.value) and "state a head" in str(e.value)


def test_int8_weights_refuse_the_mixer_and_a_lone_ffn_block_is_dense_only(
        sides):
    params, cfg, _ = sides
    with pytest.raises(ValueError, match="'mamba2'"):
        tf.quantize_weights_int8(params)
    lone = dataclasses.replace(cfg, layer_kinds=("attention", "ffn") * 3)
    with pytest.raises(ValueError, match="'ffn'"):
        tf.init_paged_cache(lone, 8, 4)


# ------------------------------------------------------------- batcher ---

def _solo(params, cfg, prompt, n_new):
    out = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new, cfg)
    return [int(t) for t in np.asarray(out)[0]]


@pytest.mark.parametrize("kw", [
    {}, {"chunk_size": 4}, {"pipeline_depth": 1}],
    ids=["defaults", "chunk4", "depth1"])
def test_a_request_reuses_the_lane_another_left(sides, kw):
    """Three requests on two lanes, admitted at different times: the
    third takes over a lane whose three states the first has filled, and
    equals solo generate() token for token, as do the others: the old
    occupant's state is gone."""
    params, cfg, _ = sides
    rng = np.random.RandomState(9)
    jobs = [(list(rng.randint(1, 256, n)), m)
            for n, m in ((30, 14), (5, 40), (4, 9))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, **kw)
    got, order = srv.run(jobs)
    assert len(got) == 3
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _solo(params, cfg, prompt, n_new)


def test_beam_search_regathers_the_states_like_any_lanes_rows(sides):
    params, cfg, _ = sides
    prompt = list(_tokens(3, 11))
    seqs, _ = tf.beam_search(params, jnp.asarray([prompt], jnp.int32), 20,
                             cfg, beam=1)
    assert [int(t) for t in np.asarray(seqs)[0, 0]] \
        == _solo(params, cfg, prompt, 20)


def test_the_batchers_streams_follow_the_references_logits(sides):
    """Logits, not tokens: every served token's reference logit lies
    within 1e-4 of the reference's best at its position (float32 on both
    sides), for lanes admitted at different times through bucketed
    prefills and a reused lane."""
    params, cfg, weights = sides
    rng = np.random.RandomState(12)
    jobs = [(list(rng.randint(1, 256, n)), m)
            for n, m in ((21, 12), (6, 30), (9, 11), (3, 7))]
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    got, order = srv.run(jobs)
    for (prompt, n_new), rid in zip(jobs, order):
        toks = np.asarray(got[rid], np.int32)
        assert len(toks) == len(prompt) + n_new
        logits = _reference_logits(weights, toks)
        rows = np.arange(len(prompt) - 1, len(toks) - 1)
        gaps = logits[rows].max(-1) - logits[rows, toks[rows + 1]]
        assert gaps.max() < TOL, gaps.max()


def test_a_cached_prefix_carries_the_state_at_its_end(sides):
    params, cfg, _ = sides
    prefix = list(_tokens(20, 12))
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    srv.cache_prefix(prefix)
    prompt = prefix + list(_tokens(21, 5))
    got, order = srv.run([(prompt, 10)])
    assert list(got[order[0]]) == _solo(params, cfg, prompt, 10)


# ------------------------------------------------- scopes and counters ---

def test_the_parts_carry_their_scopes(sides):
    params, cfg, _ = sides
    decode = jax.jit(lambda p, c, t: tf.decode_step(
        p, c, t, jnp.int32(3), cfg)).lower(
            params, tf.init_cache(cfg, 2),
            jnp.zeros((2,), jnp.int32)).as_text(debug_info=True)
    chunk = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg)).lower(
            params, tf.init_cache(cfg, 1),
            jnp.zeros((1, 16), jnp.int32)).as_text(debug_info=True)
    assert "mx.ssd.step" in decode and "mx.ssd.chunk" not in decode
    assert "mx.ssd.chunk" in chunk and "mx.ssd.conv" in chunk \
        and "mx.ssd.step" not in chunk
    for text in (decode, chunk):
        for scope in ("mx.attn.full", "mx.moe.route", "mx.moe.experts",
                      "mx.moe.shared"):
            assert scope in text
        assert "mx.ssm." not in text


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["two-in-flight", "depth1"])
def test_admissions_and_rounds_count_the_mixers_rows(sides, telemetry, loop):
    """An admission of 13 tokens runs a bucket of 16 through the three
    Mamba-2 blocks, two whole chunks of 8; one of 5 a bucket of 8. A
    round reads and writes every lane's state in each of them, with a
    request or not."""
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=3, **loop)
    assert srv._ssd_layers == 3
    # a lane's recurrent bytes: 3 x (8 x 8 x 16 float32 + 3 x 128)
    assert srv._lane_state_bytes == 3 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    srv.admit(list(_tokens(15, 13)), 20)
    srv.admit(list(_tokens(16, 5)), 20)
    assert obs.counter("ssd.rows_live").value == 3 * (13 + 5)
    assert obs.counter("ssd.rows_scanned").value == 3 * (16 + 8)
    assert obs.counter("serving.prefill_tokens").value == 18
    for _ in range(4):
        srv.step()
    n = obs.counter("serving.dispatches").value
    assert n >= 4
    assert obs.counter("ssd.lane_steps").value == n * 3 * 3
    assert obs.counter("ssd.lane_steps_live").value == n * 3 * 2
    assert obs.counter("moe.picks").value > 0
    assert srv.health_snapshot()["serving.state_bytes"] \
        == 2 * srv._lane_state_bytes
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    frozen = {k: c.value for k, c in obs.counters().items()}
    srv.step()
    assert {k: c.value for k, c in obs.counters().items()} == frozen


def test_a_width_that_is_no_whole_chunk_counts_the_padding(sides, telemetry):
    """A bucket clamped to the row's end (max_len 44: a rest of 37 runs
    at 44, five and a half chunks of 8) is scanned in six."""
    params, cfg, _ = sides
    short = dataclasses.replace(cfg, max_len=44)
    srv = ContinuousBatcher(params, short, max_batch=1)
    assert serving.prefill_widths(short, 37) == [44]
    srv.admit(list(_tokens(2, 37)), 3)
    assert obs.counter("ssd.rows_live").value == 3 * 37
    assert obs.counter("ssd.rows_scanned").value == 3 * 48


def test_a_model_without_the_mixer_counts_none_of_it(telemetry):
    cfg = tf.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                               n_layers=1, d_ff=64, max_len=32)
    srv = ContinuousBatcher(tf.init_params(cfg, 0), cfg, max_batch=2)
    srv.admit([1, 2, 3], 4)
    srv.step()
    assert not any(name.startswith("ssd.") for name in obs.counters())
