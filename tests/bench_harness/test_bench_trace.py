"""The trace reduction on a hand-made `.xplane.pb` (made from the text
proto beside it with `ProfileData.text_proto_to_serialized_xspace`), the
per-layer readers on its result, and the MFU function."""

import os

import pytest

from chipbench import flops, trace
from chipbench.readers import (counter_ratio, driver_clock, mfu,
                               trace_busy_per_step, trace_idle,
                               trace_launches_per_step)

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(os.path.join(DATA, "synthetic.xplane.pb")))


def test_fixture_matches_its_text_proto():
    from jax.profiler import ProfileData
    text = open(os.path.join(DATA, "synthetic_xspace.textproto")).read()
    again = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    assert trace.reduce(again) == trace.reduce(
        trace.load(os.path.join(DATA, "synthetic.xplane.pb")))


def test_busy_is_the_union_of_op_intervals(reduced):
    # ops [1,3) [2,4) [6,7) [10,10.5) ms -> 3 + 1 + 0.5
    assert reduced["busy_s"] == pytest.approx(4.5e-3)
    assert reduced["planes"] == 1 and reduced["ops"] == 4


def test_launches_are_the_module_events(reduced):
    assert reduced["launches"] == 3


def test_rankings(reduced):
    assert reduced["device_ops"] == [["fusion.1", pytest.approx(3e-3)],
                                     ["copy.2", pytest.approx(2.5e-3)]]
    # gap [4,6) lies in bench.step, gap [7,10) in bench.fetch_loss
    assert reduced["idle_gaps"] == [
        ["bench.fetch_loss", pytest.approx(3e-3)],
        ["bench.step", pytest.approx(2e-3)]]


@pytest.mark.parametrize("intervals,total", [
    ([], 0.0), ([(0, 1)], 1.0), ([(0, 2), (1, 3)], 3.0),
    ([(5, 6), (0, 1), (0.5, 0.7)], 2.0), ([(0, 10), (2, 3), (4, 5)], 10.0)])
def test_union(intervals, total):
    assert trace.union_ns(intervals) == pytest.approx(total)


def test_readers_on_the_reduced_trace(reduced):
    ctx = {"trace": dict(reduced, steps=2, window_s=0.010)}
    assert trace_idle.read(ctx, {}) == pytest.approx(55.0)
    assert trace_busy_per_step.read(ctx, {}) == pytest.approx(2.25)
    assert trace_launches_per_step.read(ctx, {}) == pytest.approx(1.5)


def test_counter_and_clock_readers():
    ctx = {"counters": {"dispatches": 50, "tokens": 1000},
           "clock": {"ttft_p50_ms": 41.5}}
    assert counter_ratio.read(ctx, {"num": "dispatches",
                                    "den": "tokens"}) == 0.05
    assert driver_clock.read(ctx, {"key": "ttft_p50_ms"}) == 41.5
    assert counter_ratio.read({"counters": {"tokens": 0}},
                              {"num": "dispatches", "den": "tokens"}) is None


CFG = {"n_embd": 4, "n_inner": 8, "n_layer": 2, "vocab_size": 10}


def test_flops_on_a_hand_computed_case():
    # per layer 4*4*4 + 2*4*8 = 128 matmul parameters, x2 layers = 256,
    # head 10*4 = 40 -> 296; attention 6 * 2 * 16 * 4 = 768
    assert flops.lm_matmul_params(CFG) == 296
    assert flops.lm_train_flops_per_token(CFG, 16) == 6 * 296 + 768


def test_mfu_on_a_hand_computed_case():
    ctx = {"config": CFG, "traffic": {"seq": 16},
           "clock": {"rate": 197e12 / 2544 / 4},
           "device": {"kind": "TPU v5 lite"}, "chips": 1}
    assert mfu.read(ctx, {"rate_key": "rate"}) == pytest.approx(25.0)


def test_mfu_refuses_a_device_without_published_peaks():
    ctx = {"config": CFG, "traffic": {"seq": 16}, "clock": {"rate": 1.0},
           "device": {"kind": "cpu"}, "chips": 1}
    with pytest.raises(KeyError):
        mfu.read(ctx, {"rate_key": "rate"})
