"""Latent attention with a query rank and a rotated, YaRN-scaled key part
through transformer.py and the ContinuousBatcher at a toy size on the CPU,
against its plain reference (chipbench/reference/kimi_k2.py, which imports
nothing of the program): every mixer latent attention, a leading dense
layer and routed experts of which this program holds a share. The same
seeded weights on both sides; float32 unless a case says otherwise."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench import manifest
from chipbench.reference import kimi_k2 as ref
from chipbench.runners import serve_kimi_k2
from mxnet_tpu.kernels.latent_decode import latent_block
from mxnet_tpu.models import serving, transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

TINY = json.load(open(os.path.join(
    os.path.dirname(__file__), "bench_harness", "tiny", "kimi_k2.json")))
MAN = manifest.Manifest()
REAL = MAN.config_of(MAN.cell("kimi-k2.6-serve-agent32"))


def _sides(seed, dtype=jnp.float32):
    """(program params, program config, reference weights)."""
    weights = ref.init_weights(TINY, seed, dtype)
    cfg = serve_kimi_k2.program_config(TINY)
    cfg.dtype = dtype
    return ref.as_tree(weights, TINY), cfg, weights


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(1, 256, (n,)).astype(np.int32)


def _reference_logits(weights, toks):
    """The reference's full forward over toks (padded to its width)."""
    width = ref.padded_width(len(toks), TINY)
    padded = np.zeros((width,), np.int32)
    padded[: len(toks)] = toks
    return ref.forward_row(weights, jnp.asarray(padded), TINY)[: len(toks)]


def _alone(params, cfg, prompt, n_new):
    srv = ContinuousBatcher(params, cfg, max_batch=1, pipeline_depth=1)
    got, order = srv.run([(prompt, n_new)])
    return list(got[order[0]])


@pytest.fixture(scope="module")
def sides():
    return _sides(5)


@pytest.fixture
def telemetry(monkeypatch):
    """MXNET_OBS on from a clean registry, and nothing left behind (see
    tests/test_kimi_linear.py)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    obs.reset()
    yield monkeypatch
    attribution.reset()
    obs.reset()


@pytest.fixture
def chunks_of_8(monkeypatch):
    """An admission's prefill in whole chunks of 8 tokens at the toy
    width (64), as the real one's are 4,096 at 7,168."""
    monkeypatch.setattr(serving, "PREFILL_CHUNK_ELEMS", 8 * 64)


# ------------------------------------------------------- configuration ---

def test_the_toy_configuration_states_the_architecture():
    cfg = serve_kimi_k2.program_config(TINY)
    assert tf._layer_kinds(cfg) == ("mla",) * 3
    assert [tf._has_experts(cfg, i) for i in range(3)] \
        == [ref.has_experts(TINY, i) for i in range(3)] \
        == [False, True, True]
    # 16 routed, 4 a token, this share holds experts 4..7
    assert tf._experts(cfg) == (16, 4, 4, 4, 32)
    assert cfg.mla_q_rank == 24 and cfg.rope and cfg.rope_scaling.factor == 8
    params = tf.init_params(cfg, 0)
    want = ref.as_tree(ref.init_weights(TINY, 0), TINY)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, want)
    assert set(params["layers"][0]) >= {"wq_a", "q_norm", "wq_b"}
    assert "wq" not in params["layers"][0]
    assert jax.tree.structure(params) == jax.tree.structure(
        tf.param_specs(cfg), is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec))


def test_a_configuration_with_a_scaling_record_hashes_by_value():
    """_serving_jit keys on dataclasses.astuple(cfg): two equal
    configurations share one program, another factor is another."""
    a, b = (serve_kimi_k2.program_config(TINY) for _ in range(2))
    c = dataclasses.replace(a, rope_scaling=a.rope_scaling._replace(
        factor=4))
    assert hash(dataclasses.astuple(a)) == hash(dataclasses.astuple(b))
    assert tf._jitted_decode_step(a) is tf._jitted_decode_step(b)
    assert tf._jitted_decode_step(a) is not tf._jitted_decode_step(c)


@pytest.mark.parametrize("change,word", [
    ({"layer_kinds": ("mla", "attention", "mla")}, "'attention' layer"),
    ({"rope": False, "positions": "none"}, "rope=True"),
], ids=["beside-attention", "without-rotation"])
def test_a_scaling_record_is_read_by_rotating_latent_attention_alone(
        change, word):
    cfg = dataclasses.replace(serve_kimi_k2.program_config(TINY), **change)
    with pytest.raises(ValueError, match="rope_scaling") as e:
        tf.init_params(cfg, 0)
    assert word in str(e.value)


# ------------------------------------------------------------ rotation ---

def _yarn_by_hand(dim, theta, factor, span, fast, slow):
    """The blend in float64 from the configuration file's words: pair i
    turns span * theta_i / 2 pi times over the original positions."""
    i = np.arange(dim // 2)
    theta_i = theta ** (-2.0 * i / dim)

    def pair(turns):
        return dim * np.log(span / (2 * np.pi * turns)) / (2 * np.log(theta))
    lo, hi = max(np.floor(pair(fast)), 0), min(np.ceil(pair(slow)), dim - 1)
    keep = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    return (1 - keep) * theta_i / factor + keep * theta_i, (lo, hi)


def _turned(x, positions, freqs):
    """Pairs (x_i, x_{i + E/2}) as complex numbers times e^(i t f)."""
    half = x.shape[-1] // 2
    z = (x[..., :half] + 1j * x[..., half:]).astype(np.complex128)
    z = z * np.exp(1j * positions[:, None].astype(np.float64) * freqs)
    return np.concatenate([z.real, z.imag], axis=-1)


# past the 4,096 positions the family first saw, up to the lane's last
POSITIONS = np.array([0, 1, 31, 4095, 4096, 4097, 9000, 16383, 19455])


def test_the_real_frequencies_are_the_blend_the_file_describes():
    freqs, (lo, hi) = _yarn_by_hand(64, 50000.0, 64, 4096, 32, 1)
    assert (lo, hi) == (8, 20)
    cfg = serve_kimi_k2.program_config(REAL)
    table, gain = tf._rope_table(cfg, 64)
    np.testing.assert_allclose(table, freqs, rtol=1e-6)
    np.testing.assert_allclose(ref.rotation_of(REAL)[0], freqs, rtol=1e-12)
    # whole up to pair 8, divided by 64 from pair 20, between them between
    theta = 50000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(table[:9], theta[:9], rtol=1e-6)
    np.testing.assert_allclose(table[20:], theta[20:] / 64, rtol=1e-6)
    assert np.all(table[9:20] < theta[9:20])
    assert np.all(table[9:20] > theta[9:20] / 64)
    # mscale 1 over mscale_all_dim 1 leaves cos and sin alone; the scores
    # are multiplied by (0.1 ln 64 + 1)^2
    assert gain == 1.0 and ref.rotation_of(REAL)[1] == 1.0
    assert ref.rotation_of(REAL)[2] == pytest.approx(1.41589 ** 2, rel=1e-5)
    assert 1 / tf._latent_score_norm(cfg, 192) == pytest.approx(
        0.144680, rel=1e-5)


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_rotation_equals_the_complex_form_past_the_original_positions(
        side):
    """float32 angles: a position of 19,455 times a frequency rounded to
    24 bits is off by up to 19455 * 2^-24 = 1.2e-3 radians, so 3e-3 of a
    unit feature; the complex form is float64."""
    freqs, _ = _yarn_by_hand(64, 50000.0, 64, 4096, 32, 1)
    x = np.random.RandomState(0).randn(len(POSITIONS), 64).astype(np.float32)
    want = _turned(x, POSITIONS, freqs)
    if side == "program":
        cfg = serve_kimi_k2.program_config(REAL)
        # the key part: one a position, no head axis; and the query's
        kr = tf._rope(jnp.asarray(x)[:, None, :], jnp.asarray(POSITIONS),
                      cfg.rope_base, tf._rope_table(cfg, 64))[:, 0]
        q = tf._rope(jnp.broadcast_to(jnp.asarray(x)[:, None, :],
                                      (len(POSITIONS), 3, 64)),
                     jnp.asarray(POSITIONS), cfg.rope_base,
                     tf._rope_table(cfg, 64))
        np.testing.assert_allclose(q[:, 2], kr, atol=0)
        got = kr
    else:
        got = ref.rotate(jnp.asarray(x), jnp.asarray(POSITIONS),
                         ref.rotation_of(REAL)[0])
    np.testing.assert_allclose(got, want, atol=3e-3 * np.abs(x).max())
    # the turn is by the position: a pair's length does not change
    np.testing.assert_allclose(
        np.hypot(got[:, :32], got[:, 32:]), np.hypot(x[:, :32], x[:, 32:]),
        rtol=1e-5)


def test_a_score_depends_on_the_distance_alone():
    """q at position a against k at position b: the rotated parts' dot
    product is that of the unrotated ones turned by (a - b), whatever a."""
    cfg = serve_kimi_k2.program_config(REAL)
    table = tf._rope_table(cfg, 64)
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(1, 1, 64), jnp.float32) for _ in range(2))

    def score(a, b):
        return float(jnp.sum(
            tf._rope(q, jnp.asarray([a]), cfg.rope_base, table)
            * tf._rope(k, jnp.asarray([b]), cfg.rope_base, table)))
    assert score(5000, 4000) == pytest.approx(score(1000, 0), abs=2e-3)
    assert score(19000, 18990) == pytest.approx(score(10, 0), abs=2e-3)
    assert abs(score(1000, 0) - score(0, 0)) > 1e-2


# ------------------------------------------- logits against the reference

@pytest.mark.parametrize("dtype,tol,why", [
    (jnp.float32, 1e-4, "float32 both sides, sums in another order"),
    # bfloat16 through 3 layers is 0.02; a pick ordered otherwise than
    # the reference's moves a whole expert of this toy share (0.15)
    (jnp.bfloat16, 0.3, "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, tol, why):
    params, cfg, weights = _sides(3, dtype)
    toks = _tokens(3, 64)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks[None])[0]
    want = ref.forward_row(weights, jnp.asarray(toks), TINY)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)


@pytest.mark.parametrize("t_p,width", [(19, 32), (7, 8), (40, 40)])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        sides, t_p, width):
    """The admission path (a bucket wider than the prompt, the logits of
    the last real row) and then one position after another over the
    rotated rows, past the 32 positions the toy scaling calls original:
    logits, not tokens. 1e-4: float32, the absorbed and the chunked forms
    against the reference's full attention."""
    params, cfg, weights = sides
    toks = _tokens(4, 60)
    want = _reference_logits(weights, toks)
    padded = np.zeros((1, width), np.int32)
    padded[0, :t_p] = toks[:t_p]
    logits, cache = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg, logits_row=jnp.int32(t_p - 1)))(
            params, tf.init_cache(cfg, 1), jnp.asarray(padded))
    np.testing.assert_allclose(logits[0], want[t_p - 1], atol=1e-4)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for t in range(t_p, 60):
        logits, cache = step(params, cache, jnp.asarray(toks[t:t + 1]),
                             jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], atol=1e-4)


@pytest.mark.parametrize("start", [1, 23, 37])
def test_a_chunk_prefilled_past_position_zero_is_rotated_for_its_place(
        sides, start):
    """A continuation: prefill (self-attention over the fresh rows) then
    a chunk at `start` read from the cache equals the whole forward."""
    params, cfg, weights = sides
    toks = _tokens(6, 48)
    want = _reference_logits(weights, toks)
    last, cache = tf.prefill(params, tf.init_cache(cfg, 1),
                             jnp.asarray(toks[None, :start]), cfg)
    np.testing.assert_allclose(last[0], want[start - 1], atol=1e-4)
    logits, _ = tf.prefill_chunk(params, cache,
                                 jnp.asarray(toks[None, start:]),
                                 jnp.int32(start), cfg)
    np.testing.assert_allclose(logits[0], want[start:], atol=1e-4)


def test_a_stored_row_holds_its_position(sides):
    """What the cache keeps of position t is the key part turned by t:
    the same token at two positions leaves two different rows, equal
    again once each is turned back."""
    params, cfg, _ = sides
    toks = np.full((1, 12), 7, np.int32)
    _, cache = tf.prefill_chunk(params, tf.init_cache(cfg, 1),
                                jnp.asarray(toks), jnp.int32(0), cfg)
    kr = np.asarray(cache[0]["kr"][0, :12])
    # layer 0 sees the same embedding at every position
    assert np.abs(kr[3] - kr[9]).max() > 1e-3
    table = tf._rope_table(cfg, 8)
    back = tf._rope(jnp.asarray(kr)[:, None, :], -jnp.arange(12),
                    cfg.rope_base, table)[:, 0]
    np.testing.assert_allclose(back, np.broadcast_to(back[0], back.shape),
                               atol=1e-5)


# ------------------------------------------------------------- batcher ---

@pytest.mark.parametrize("kw", [
    {}, {"chunk_size": 4}, {"pipeline_depth": 1}],
    ids=["defaults", "chunk4", "depth1"])
def test_three_staggered_requests_on_two_lanes_equal_each_served_alone(
        sides, kw):
    """The third request waits for a lane and overwrites its previous
    occupant's rotated rows whole; every stream equals the request
    served alone at a window of one, and solo generate()."""
    params, cfg, _ = sides
    rng = np.random.RandomState(9)
    jobs = [(list(rng.randint(1, 256, n)), m)
            for n, m in ((5, 9), (13, 4), (9, 7))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, **kw)
    got, order = srv.run(jobs)
    assert len(got) == 3
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _alone(params, cfg, prompt, n_new)
        solo = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new,
                           cfg)
        assert list(got[rid]) == [int(t) for t in np.asarray(solo)[0]]


def _served_gap(weights, prompt_len, tokens):
    """By how much the served tokens lie under the reference's best."""
    want = _reference_logits(weights, np.asarray(tokens, np.int32))
    rows = want[prompt_len - 1: len(tokens) - 1]
    served = np.asarray(tokens[prompt_len:])
    return float(jnp.max(jnp.max(rows, axis=-1)
                         - rows[jnp.arange(len(served)), served]))


def test_a_continuation_resumes_at_its_position(sides):
    """A stream stopped after 6 tokens and resumed through
    admit_continuation (its history prefilled into a fresh lane) serves
    what the uninterrupted one does, and every token is the reference's
    first choice: the re-prefilled rows sit where they were rotated
    for."""
    params, cfg, weights = sides
    prompt = list(_tokens(11, 14))
    whole = _alone(params, cfg, prompt, 20)
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    rid = srv.admit(prompt, 6)
    done = {}
    while rid not in done:
        done.update(srv.step())
    assert done[rid] == whole[:20]
    rid2 = srv.admit_continuation(done[rid], 14, emitted=6)
    while rid2 not in done:
        done.update(srv.step())
    assert done[rid2] == whole
    assert _served_gap(weights, 14, whole) < 1e-4


@pytest.mark.parametrize("kw", [{}, {"pipeline_depth": 1}],
                         ids=["default", "depth1"])
def test_a_re_admitted_lane_keeps_its_rows_at_their_positions(sides, kw):
    """A failed dispatch drops every lane; each live request is prefilled
    again from its tokens so far (another lane, another bucket) and goes
    on as if nothing had happened."""
    params, cfg, weights = sides
    jobs = [(list(_tokens(12, 9)), 16), (list(_tokens(13, 21)), 12)]
    srv = ContinuousBatcher(params, cfg, max_batch=3, **kw)
    rids = [srv.admit(p, n) for p, n in jobs]
    done = {}
    for _ in range(4):
        done.update(srv.step())
    srv._recover_dispatch_failure(RuntimeError("injected"))
    while len(done) < 2:
        done.update(srv.step())
    for (prompt, n_new), rid in zip(jobs, rids):
        assert done[rid] == _alone(params, cfg, prompt, n_new)
        assert _served_gap(weights, len(prompt), done[rid]) < 1e-4


def test_a_cached_prefix_keeps_its_rows_and_the_suffix_joins_them(sides):
    params, cfg, _ = sides
    rng = np.random.RandomState(10)
    prefix = list(rng.randint(1, 256, 11))
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    assert srv.cache_prefix(prefix) == 11
    jobs = [(prefix + list(rng.randint(1, 256, n)), 5) for n in (1, 6)]
    jobs.append((prefix, 4))
    got, order = srv.run(jobs)
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _alone(params, cfg, prompt, n_new)


def test_an_admission_in_chunks_equals_the_one_call(sides, chunks_of_8):
    """29 tokens go in as 8 + 8 + 8 and a rest of 5 in a bucket of 8:
    the last row's logits are the reference's, the rows behind equal the
    one bucket's, and the stream is the same."""
    params, cfg, weights = sides
    toks = list(_tokens(14, 29))
    assert serving.prefill_widths(cfg, 29) == [8, 8, 8, 8]
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    logits, row = srv._prefill_rows(srv._fresh_row(), toks, 0)
    want = _reference_logits(weights, np.asarray(toks, np.int32))
    np.testing.assert_allclose(logits[0], want[-1], atol=1e-4)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :29] = toks
    _, whole = tf._jitted_prefill_chunk_row(cfg)(
        params, srv._fresh_row(), jnp.asarray(padded), jnp.int32(0),
        jnp.int32(28))
    for got, one in zip(row, whole):
        for name in ("c", "kr"):
            np.testing.assert_allclose(got[name][0, :29], one[name][0, :29],
                                       atol=1e-5)
    got, order = srv.run([(toks, 9)])
    solo = tf.generate(params, jnp.asarray([toks], jnp.int32), 9, cfg)
    assert list(got[order[0]]) == [int(t) for t in np.asarray(solo)[0]]


@pytest.mark.parametrize("what,make", [
    ("paged", lambda p, c: ContinuousBatcher(p, c, max_batch=2, paged=True)),
    ("kv_cache_int8", lambda p, c: ContinuousBatcher(
        p, dataclasses.replace(c, kv_cache_int8=True), max_batch=2)),
    ("spec_k", lambda p, c: ContinuousBatcher(p, c, max_batch=2, spec_k=2)),
])
def test_paged_blocks_int8_and_speculation_still_refuse_the_kind_by_name(
        sides, what, make):
    params, cfg, _ = sides
    with pytest.raises(ValueError, match="'mla'") as e:
        make(params, cfg)
    assert what in str(e.value) and "latent rows" in str(e.value)


# ------------------------------------------------------------ counters ---

def test_a_decode_round_counts_the_rows_it_fetched_and_those_that_live(
        sides, telemetry):
    """Three latent layers, two lanes of max_len 64, which is one block
    of the kernel: a round fetches a lane's one block, 3 x 2 x 64 rows,
    wherever the lanes stand; live are the rows at or before each lane's
    position (5 + 1 and 3 + 1 tokens at the first round, one more a lane
    a round), never more than were fetched."""
    params, cfg, _ = sides
    assert latent_block(cfg.max_len) == 64
    srv = ContinuousBatcher(params, cfg, max_batch=2, pipeline_depth=1)
    srv.admit([5, 6, 7, 8, 9], 6)
    srv.admit([1, 2, 3], 6)
    for _ in range(3):
        srv.step()
        assert obs.counter("mla.rows_live").value \
            <= obs.counter("mla.rows_read").value
    assert obs.counter("mla.rows_read").value == 3 * (3 * 2 * 64)
    assert obs.counter("mla.rows_live").value \
        == 3 * ((6 + 4) + (7 + 5) + (8 + 6))
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    srv.step()
    assert obs.counter("mla.rows_read").value == 3 * (3 * 2 * 64)


def test_two_rounds_in_flight_count_the_rows_one_does(sides, telemetry):
    """The counts are taken when a round's tokens are fetched: the rows
    fetched from the positions the round was dispatched with, every lane
    of max_batch among them, the live ones from what the host knows of
    the lanes then; so two rounds in flight add what a window of one
    does. A lane without a request is parked at position 0 and fetched
    up to where the carry has moved it since: one block here, and none
    of its rows lives."""
    params, cfg, _ = sides
    jobs = [([5, 6, 7, 8, 9], 7), ([1, 2, 3], 7)]
    read = {}
    for depth in (1, 2):
        obs.reset()
        srv = ContinuousBatcher(params, cfg, max_batch=3,
                                pipeline_depth=depth)
        srv.run(jobs)
        read[depth] = (obs.counter("mla.rows_read").value,
                       obs.counter("mla.rows_live").value)
    assert read[1] == read[2]
    assert read[1][0] == 6 * (3 * 3 * 64)              # six decode rounds
    assert read[1][1] == 3 * sum((6 + i) + (4 + i) for i in range(6))
    assert read[1][1] <= read[1][0]


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["default", "depth1"])
def test_a_chunked_round_counts_every_step_of_it(sides, telemetry, loop):
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=2, chunk_size=4, **loop)
    srv.admit([5, 6, 7, 8, 9], 9)
    srv.step()
    assert obs.counter("mla.rows_read").value == 4 * (3 * 2 * 64)
    assert obs.counter("mla.rows_live").value == 3 * (6 + 7 + 8 + 9)


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["default", "depth1"])
def test_the_rows_fetched_follow_the_lanes_lengths(sides, telemetry, loop):
    """max_len 384 is three blocks of 128. Over 30 rounds a lane that
    starts at 126 rows fetches one block until it holds 129, then two;
    one that starts at 6 fetches one throughout; the third lane has no
    request and is fetched up to its parked position's block: the same
    counts with one round in flight and with two."""
    params, cfg, _ = sides
    cfg = dataclasses.replace(cfg, max_len=384)
    assert latent_block(cfg.max_len) == 128
    srv = ContinuousBatcher(params, cfg, max_batch=3, **loop)
    srv.admit([5, 6, 7, 8, 9], 60)
    srv.admit(list(_tokens(3, 125)), 60)
    for _ in range(30):
        srv.step()
    blocks = lambda rows: -(-rows // 128) * 128
    assert obs.counter("mla.rows_read").value == 3 * sum(
        blocks(6 + i) + blocks(126 + i) + 128 for i in range(30)) \
        == 3 * (30 * 128 + 3 * 128 + 27 * 256 + 30 * 128)
    assert obs.counter("mla.rows_live").value == 3 * sum(
        (6 + i) + (126 + i) for i in range(30))
    assert obs.counter("mla.rows_read").value < 30 * (3 * 3 * 384)


def test_a_cache_the_kernel_cannot_tile_counts_every_row(sides, telemetry):
    """max_len 1,032 is more than one block and no multiple of 128: the
    decode contraction is the two XLA passes over all of it, and the
    counter says so: max_len rows a lane a latent layer a round."""
    params, cfg, _ = sides
    cfg = dataclasses.replace(cfg, max_len=1032)
    assert latent_block(cfg.max_len) is None
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    srv.admit([5, 6, 7, 8, 9], 6)
    for _ in range(3):
        srv.step()
    assert obs.counter("mla.rows_read").value == 3 * (3 * 2 * 1032)
    assert obs.counter("mla.rows_live").value == 3 * (6 + 7 + 8)


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["default", "depth1"])
@pytest.mark.parametrize("max_len,chunk,ran", [
    (64, 1, "kernel"), (384, 4, "kernel"), (1032, 1, "scatter")],
    ids=["64", "384-chunked", "1032"])
def test_a_dispatch_counts_its_latent_layers_row_stores(
        sides, telemetry, loop, max_len, chunk, ran):
    """A decode step stores one `kr` row a lane a latent layer: through
    the writer of kernels/latent_decode.py wherever latent_block tiles
    max_len (one block of 64, three of 128), through the scatter at
    1,032. Counted a dispatch, three latent layers a step of it, with
    one round in flight and with two; the other counter stays 0 and
    both are in health_snapshot()."""
    params, cfg, _ = sides
    cfg = dataclasses.replace(cfg, max_len=max_len)
    assert (latent_block(max_len) is not None) == (ran == "kernel")
    srv = ContinuousBatcher(params, cfg, max_batch=2, chunk_size=chunk,
                            **loop)
    srv.admit([5, 6, 7, 8, 9], 20)
    for _ in range(3):
        srv.step()
    assert srv.dispatch_count >= 3
    snap = srv.health_snapshot()
    assert snap["mla.row_store_" + ran] \
        == obs.counter("mla.row_store_" + ran).value \
        == 3 * chunk * srv.dispatch_count
    assert snap["mla.row_store_" + {"kernel": "scatter",
                                    "scatter": "kernel"}[ran]] == 0
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    srv.step()
    assert obs.counter("mla.row_store_" + ran).value \
        == snap["mla.row_store_" + ran]


def test_a_model_without_latent_layers_counts_no_rows(telemetry):
    cfg = tf.TransformerConfig(max_len=32)
    srv = ContinuousBatcher(tf.init_params(cfg, 0), cfg, max_batch=2)
    srv.admit([1, 2, 3], 4)
    srv.step()
    assert "mla.rows_read" not in obs.counters()
    assert "mla.row_store_kernel" not in obs.counters()
    assert "mla.row_store_kernel" not in srv.health_snapshot()


# ----------------------------------------------------- the shares add up

def test_the_32_shares_add_up_to_the_uncut_layer():
    """The deployment's own split at toy widths: 384 routed experts, 8 a
    token, 12 held a chip. The expert layer's result over expert_offset
    0, 12, ..., 372 (what the 32 chips of a layer would each compute
    through the program's _ffn), with the shared expert counted once,
    equals the uncut reference's layer: all 384 experts held, one
    forward; and every pick of every token lands on exactly one chip."""
    published = dict(TINY["published"], n_routed_experts=384)
    toy = dict(TINY, published=published, num_experts_per_tok=8)
    uncut = dict(toy, n_routed_experts=384, expert_offset=0)
    weights = ref.init_weights(uncut, 7, jnp.float32)
    p = {k: weights["layers.1." + k] for k in ref.EXPERT_LEAVES}
    h = jnp.asarray(np.random.RandomState(7).randn(1, 24, 64), jnp.float32)
    want = ref.experts_part(h[0], p, ref.exact, 8, 2.827, 0) \
        + ref.shared_part(h[0], p, ref.exact)
    # what every chip computes alike: the shared expert, once
    shared = tf._mlp(h, p["ws1"], p["ws2"], p["ws3"], dataclasses.replace(
        serve_kimi_k2.program_config(toy), dtype=jnp.float32))
    total, picks = jnp.zeros_like(h), 0
    for offset in range(0, 384, 12):
        cfg = dataclasses.replace(serve_kimi_k2.program_config(
            dict(toy, n_routed_experts=12, expert_offset=offset)),
            dtype=jnp.float32)
        assert tf._experts(cfg)[:4] == (384, 8, offset, 12)
        share = {k: (v[offset: offset + 12] if k in ("w1", "w3", "w2")
                     else v) for k, v in p.items()}
        loads = []
        total = total + tf._ffn(h, share, cfg, loads) - shared
        picks += int(loads[0].sum())
    assert picks == 24 * 8
    np.testing.assert_allclose((total + shared)[0], want, atol=1e-5)
