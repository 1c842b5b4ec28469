"""The Kimi-Linear architecture through transformer.py and the
ContinuousBatcher at a toy size on the CPU, against its plain reference
(chipbench/reference/kimi_linear.py, which imports nothing of the
program): KDA mixers beside latent attention, a leading dense layer and
routed experts of which this program holds a share. The same seeded
weights on both sides; float32 unless a case says otherwise."""

import dataclasses
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chipbench.reference import kimi_linear as ref
from chipbench.runners import serve_kimi_linear
from mxnet_tpu.models import transformer as tf
from mxnet_tpu.models.serving import ContinuousBatcher
from mxnet_tpu.observability import attribution, core as obs

TINY = json.load(open(os.path.join(
    os.path.dirname(__file__), "bench_harness", "tiny", "kimi_linear.json")))
KINDS = ("kda", "kda", "kda", "mla")


def _sides(seed, dtype=jnp.float32):
    """(program params, program config, reference weights)."""
    weights = ref.init_weights(TINY, seed, dtype)
    cfg = serve_kimi_linear.program_config(TINY)
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


@pytest.fixture(scope="module")
def sides():
    return _sides(5)


@pytest.fixture
def telemetry(monkeypatch):
    """MXNET_OBS on from a clean registry, and nothing left behind: the
    programs a batcher registers for attribution while it is on would
    otherwise be counted by whatever test of this process archives the
    registry next (tests/test_profile_store.py)."""
    monkeypatch.setenv("MXNET_OBS", "1")
    obs.reset()
    yield monkeypatch
    attribution.reset()
    obs.reset()


def test_the_toy_configuration_states_the_architecture():
    cfg = serve_kimi_linear.program_config(TINY)
    assert tf._layer_kinds(cfg) == ref.layer_kinds(TINY) == KINDS
    assert [tf._has_experts(cfg, i) for i in range(4)] \
        == [ref.has_experts(TINY, i) for i in range(4)] \
        == [False, True, True, True]
    # 16 routed, 4 a token, this share holds experts 4..7
    assert tf._experts(cfg) == (16, 4, 4, 4, 16)
    params = tf.init_params(cfg, 0)
    want = ref.as_tree(ref.init_weights(TINY, 0), TINY)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(jnp.shape, want)
    assert jax.tree.structure(params) == jax.tree.structure(
        tf.param_specs(cfg), is_leaf=lambda x: isinstance(
            x, jax.sharding.PartitionSpec))


def test_the_cache_holds_three_kinds_of_state():
    cfg = dataclasses.replace(serve_kimi_linear.program_config(TINY),
                              dtype=jnp.bfloat16)
    cache = tf.init_cache(cfg, 3)
    for kind, layer in zip(KINDS, cache):
        if kind == "kda":
            assert set(layer) == {"conv", "kda"}
            assert layer["conv"].shape == (3, 3, 3 * 4 * 8)
            assert layer["conv"].dtype == jnp.bfloat16
            assert layer["kda"].shape == (3, 4, 8, 8)
            assert layer["kda"].dtype == jnp.float32
        else:
            # one latent a position: kv_lora_rank + qk_rope_head_dim
            assert set(layer) == {"c", "kr"}
            assert layer["c"].shape == (3, 64, 16)
            assert layer["kr"].shape == (3, 64, 4)
            assert layer["c"].dtype == layer["kr"].dtype == jnp.bfloat16


@pytest.mark.parametrize("dtype,tol,why", [
    # the same arithmetic in the same precision, summed in another order
    # (chunks instead of positions, a grouped matmul instead of a loop)
    (jnp.float32, 1e-4, "float32 both sides"),
    # the program rounds every projection's operands and results to
    # bfloat16 through 4 layers (0.02 at most positions; logits are
    # O(0.5) at this size), and a token whose 4th and 5th scores lie
    # within that rounding picks another expert than the reference: with
    # 4 of 16 held, a whole expert's output comes or goes (0.13-0.15 at
    # six of the 45 positions)
    (jnp.bfloat16, 0.3, "bfloat16 program against the float32 reference"),
])
def test_forward_logits_equal_the_references(dtype, tol, why):
    params, cfg, weights = _sides(3, dtype)
    toks = _tokens(3, 45)
    got = jax.jit(lambda p, t: tf.forward(p, t, cfg))(params, toks[None])[0]
    want = _reference_logits(weights, toks)
    gap = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    assert gap < tol, (why, gap)
    if dtype == jnp.bfloat16:
        # and is no closer than its precision allows
        assert gap > 1e-4


@pytest.mark.parametrize("t_p,width", [(19, 32), (7, 8)])
def test_prefill_then_decode_through_the_cache_equals_the_full_forward(
        sides, t_p, width):
    """The admission path (a bucket wider than the prompt, the logits of
    the last real row) and then one position after another through all
    three kinds of state: logits, not tokens. 1e-4: float32, the absorbed
    and the chunked forms against the reference's position by position."""
    params, cfg, weights = sides
    toks = _tokens(4, 45)
    want = _reference_logits(weights, toks)
    padded = np.zeros((1, width), np.int32)
    padded[0, :t_p] = toks[:t_p]
    logits, cache = jax.jit(lambda p, c, t: tf.prefill_chunk(
        p, c, t, jnp.int32(0), cfg, logits_row=jnp.int32(t_p - 1)))(
            params, tf.init_cache(cfg, 1), jnp.asarray(padded))
    np.testing.assert_allclose(logits[0], want[t_p - 1], atol=1e-4)
    step = jax.jit(lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg))
    for t in range(t_p, 45):
        logits, cache = step(params, cache, jnp.asarray(toks[t:t + 1]),
                             jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(logits[0], want[t], atol=1e-4)


def test_prefill_at_position_zero_and_a_suffix_chunk_agree(sides):
    """prefill (self-attention over the fresh rows) then a chunk from the
    cache equals the whole forward: the two contractions of a latent
    layer and a carried matrix state."""
    params, cfg, weights = sides
    toks = _tokens(6, 40)
    want = _reference_logits(weights, toks)
    last, cache = tf.prefill(params, tf.init_cache(cfg, 1),
                             jnp.asarray(toks[None, :23]), cfg)
    np.testing.assert_allclose(last[0], want[22], atol=1e-4)
    logits, _ = tf.prefill_chunk(params, cache, jnp.asarray(toks[None, 23:]),
                                 jnp.int32(23), cfg)
    np.testing.assert_allclose(logits[0], want[23:], atol=1e-4)


def test_the_absorbed_contraction_equals_the_up_projected_one(sides):
    """Decode's form (W_kvb folded into the query and the output, the
    latents contracted as they lie) against a chunk's form (keys and
    values up-projected, contracted through heads) on the same rows."""
    params, cfg, _ = sides
    p = params["layers"][3]
    rng = np.random.RandomState(7)
    rows = {"c": jnp.asarray(rng.randn(3, 64, 16), jnp.float32),
            "kr": jnp.asarray(rng.randn(3, 64, 4), jnp.float32)}
    q = jnp.asarray(rng.randn(3, 4, 12), jnp.float32)
    pos = jnp.asarray([5, 63, 0], jnp.int32)
    got = tf._latent_decode_attention(q, rows, pos, p, cfg)
    want = tf._latent_chunk_attention(q[:, None], rows, pos[:, None], p,
                                      cfg)[:, 0]
    assert got.shape == (3, 4, 8)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_a_chunk_wider_than_a_query_block_is_attended_in_blocks(
        sides, monkeypatch):
    params, cfg, _ = sides
    p = params["layers"][3]
    rng = np.random.RandomState(8)
    rows = {"c": jnp.asarray(rng.randn(2, 64, 16), jnp.float32),
            "kr": jnp.asarray(rng.randn(2, 64, 4), jnp.float32)}
    q = jnp.asarray(rng.randn(2, 21, 4, 12), jnp.float32)
    positions = 11 + jnp.arange(21)
    want = tf._latent_chunk_attention(q, rows, positions, p, cfg)
    monkeypatch.setattr(tf, "MLA_QUERY_BLOCK", 8)    # 3 blocks, 3 rows over
    got = tf._latent_chunk_attention(q, rows, positions, p, cfg)
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("width", [16, 24])
def test_the_stored_rows_are_attended_in_blocks_up_to_the_last_seen(
        sides, monkeypatch, width):
    """Blocks of stored rows with a running maximum and sum, stopped
    behind the last position a query block sees, against the softmax
    over all rows at once; 24 does not divide the 64 rows, and the rows
    from 48 on hold NaN: no block that is read reaches them."""
    params, cfg, _ = sides
    p = params["layers"][3]
    rng = np.random.RandomState(10)
    rows = {"c": jnp.asarray(rng.randn(2, 64, 16), jnp.float32),
            "kr": jnp.asarray(rng.randn(2, 64, 4), jnp.float32)}
    q = jnp.asarray(rng.randn(2, 21, 4, 12), jnp.float32)
    positions = 11 + jnp.arange(21)
    k, v = tf._latent_up(rows, p, cfg)
    s = jnp.einsum("bqhd,bthd->bhqt", q, k) / np.sqrt(12.0)
    s = jnp.where(jnp.arange(64) <= positions[:, None], s, -jnp.inf)
    want = jnp.einsum("bhqt,bthv->bqhv", jax.nn.softmax(s, axis=-1), v)
    monkeypatch.setattr(tf, "MLA_KEY_BLOCK", width)
    monkeypatch.setattr(tf, "MLA_QUERY_BLOCK", 8)
    unread = {name: x.at[:, 48:].set(jnp.nan) for name, x in rows.items()}
    got = tf._latent_chunk_attention(q, unread, positions, p, cfg)
    np.testing.assert_allclose(got, want, atol=2e-6)


# ------------------------------------------------------------ serving ---

def _alone(params, cfg, prompt, n_new):
    srv = ContinuousBatcher(params, cfg, max_batch=1, pipeline_depth=1)
    got, order = srv.run([(prompt, n_new)])
    return list(got[order[0]])


@pytest.mark.parametrize("kw", [
    {}, {"chunk_size": 4}, {"pipeline_depth": 2}, {"pipeline_depth": 1},
    {"chunk_size": 4, "pipeline_depth": 1}],
    ids=["defaults", "chunk4", "depth2", "depth1", "chunk4-depth1"])
def test_three_staggered_requests_on_two_lanes_equal_each_served_alone(
        sides, kw):
    """The third request waits for a lane and overwrites its previous
    occupant's latent rows, conv windows and matrix states whole; every
    stream equals the request served alone at a window of one, and
    solo generate(). The defaults keep two rounds in flight."""
    params, cfg, _ = sides
    rng = np.random.RandomState(9)
    jobs = [(list(rng.randint(1, 256, n)), m)
            for n, m in ((5, 9), (13, 4), (9, 7))]
    srv = ContinuousBatcher(params, cfg, max_batch=2, **kw)
    assert srv.pipeline_depth == kw.get("pipeline_depth", 2)
    got, order = srv.run(jobs)
    assert len(got) == 3
    for (prompt, n_new), rid in zip(jobs, order):
        assert list(got[rid]) == _alone(params, cfg, prompt, n_new)
        solo = tf.generate(params, jnp.asarray([prompt], jnp.int32), n_new,
                           cfg)
        assert list(got[rid]) == [int(t) for t in np.asarray(solo)[0]]


def test_a_cached_prefix_carries_all_three_kinds_of_state(sides):
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


def test_the_gauges_count_the_matrix_state_and_the_latent_rows(
        sides, telemetry):
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=3)
    srv.admit([5, 6, 7, 8, 9], 4)
    srv.admit([1, 2, 3], 4)
    # a lane: three KDA layers of (3 x 96 conv + 4 x 8 x 8 matrices),
    # float32; one latent layer of 20 values a position
    lane = 3 * (3 * 96 * 4 + 4 * 8 * 8 * 4)
    snap = srv.health_snapshot()
    assert snap["serving.state_bytes"] == 2 * lane
    assert snap["serving.kv_bytes"] == (6 + 4) * (20 * 4)


def test_a_decode_round_counts_its_routing(sides, telemetry):
    """Three expert layers, 4 picks a token of 16 experts, 4 held here:
    the program returns the counts beside its tokens and the batcher adds
    them to moe.* while spans record; picks and experts_held are fixed by
    the shapes, the others by the routing."""
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=2)
    srv.admit([5, 6, 7, 8, 9], 6)
    srv.admit([1, 2, 3], 6)
    for _ in range(3):
        srv.step()
    c = {name: obs.counter("moe." + name).value for name in tf.MOE_STATS}
    assert c["picks"] == 3 * (2 * 4 * 3)
    assert c["experts_held"] == 3 * (4 * 3) and c["layers"] == 3 * 3
    assert 0 < c["picks_here"] < c["picks"]
    assert 0 < c["experts_touched"] <= c["experts_held"]
    assert c["layers"] <= c["load_max"] <= c["picks_here"]
    # nothing is counted while nothing records
    telemetry.setenv("MXNET_OBS", "0")
    srv.step()
    assert obs.counter("moe.picks").value == c["picks"]


def test_two_rounds_in_flight_count_the_routing_one_does(sides, telemetry):
    """The chunk returns its routing counts, the in-flight record
    carries them and they are added
    when the chunk's tokens are fetched: over the same requests (a full
    pool, equal budgets, so no lane parks early) every moe.* counter
    reads what a window of one adds. The chunk still in flight when
    the last stream ends is dropped unfetched, and uncounted."""
    params, cfg, _ = sides
    jobs = [([5, 6, 7, 8, 9], 7), ([1, 2, 3], 7)]
    read = {}
    for depth in (1, 2):
        obs.reset()
        srv = ContinuousBatcher(params, cfg, max_batch=2,
                                pipeline_depth=depth)
        got, order = srv.run(jobs)
        read[depth] = ({name: obs.counter("moe." + name).value
                        for name in tf.MOE_STATS},
                       [list(got[r]) for r in order])
    assert read[1] == read[2]
    assert read[1][0]["picks"] == 6 * (2 * 4 * 3)      # six decode rounds
    assert read[1][0]["experts_touched"] > 0


@pytest.mark.parametrize("loop", [{}, {"pipeline_depth": 1}],
                         ids=["default", "depth1"])
def test_a_chunked_round_sums_its_steps_counts(sides, telemetry, loop):
    params, cfg, _ = sides
    srv = ContinuousBatcher(params, cfg, max_batch=2, chunk_size=4, **loop)
    srv.admit([5, 6, 7, 8, 9], 9)
    srv.step()
    assert obs.counter("moe.picks").value == 4 * (2 * 4 * 3)
    assert obs.counter("moe.layers").value == 4 * 3


@pytest.mark.parametrize("kind,layers", [("kda", ("kda", "attention")),
                                         ("mla", ("attention", "mla"))])
@pytest.mark.parametrize("what,call", [
    ("paged", lambda p, c: ContinuousBatcher(p, c, max_batch=2, paged=True)),
    ("spec_k", lambda p, c: ContinuousBatcher(p, c, max_batch=2, spec_k=2)),
    ("kv_cache_int8", lambda p, c: ContinuousBatcher(
        p, dataclasses.replace(c, kv_cache_int8=True), max_batch=2)),
    ("kv_cache_int8", lambda p, c: tf.init_cache(
        dataclasses.replace(c, kv_cache_int8=True), 1)),
    ("paged", lambda p, c: tf.init_paged_cache(c, 4, 16)),
    ("decode_step_paged", lambda p, c: tf.decode_step_paged(
        p, None, None, None, None, c)),
    ("verify_chunk", lambda p, c: tf.verify_chunk(p, None, None, None, c)),
    ("speculative decoding", lambda p, c: tf.speculative_generate(
        p, p, jnp.ones((1, 3), jnp.int32), 4, c, c)),
    ("quantize_weights_int8", lambda p, c: tf.quantize_weights_int8(p)),
    ("mesh-sharded forward", lambda p, c: tf.forward(
        p, jnp.ones((1, 4), jnp.int32), c, mesh=object())),
    ("shard_params", lambda p, c: tf.shard_params(p, c, None)),
    ("shard_cache", lambda p, c: tf.shard_cache(None, c, None)),
])
def test_what_cannot_carry_the_new_kinds_refuses_by_name(kind, layers, what,
                                                         call):
    """Paged blocks (and so preemption's snapshots, which only a paged
    pool takes), speculation, int8 and tensor-parallel sharding name the
    kind they cannot carry instead of serving other tokens."""
    cfg = dataclasses.replace(serve_kimi_linear.program_config(TINY),
                              dtype=jnp.float32, n_layers=2,
                              layer_kinds=layers, n_experts=0)
    params = tf.init_params(cfg, 0)
    with pytest.raises(ValueError, match="'%s'" % kind) as e:
        call(params, cfg)
    assert what in str(e.value)


def test_a_latent_layer_without_its_sizes_is_refused():
    cfg = tf.TransformerConfig(n_layers=2, layer_kinds=("attention", "mla"))
    with pytest.raises(ValueError, match="mla_rank"):
        tf.init_params(cfg, 0)
