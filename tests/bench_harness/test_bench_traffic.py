"""The seeded length sampler and the closed-loop driver on a hand-made
timeline."""

import itertools

import numpy as np
import pytest

from chipbench import manifest, traffic

MIX = manifest.load_traffic("closed24")


def _first(seed, n=100):
    return [(p.tolist(), o) for p, o in itertools.islice(
        traffic.request_stream(MIX, seed, 50257), n)]


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 5])
def test_sampler_is_a_pure_function_of_the_seed(seed):
    assert _first(seed) == _first(seed)
    assert _first(seed) != _first(seed + 1)


@pytest.mark.parametrize("seed", [1, 99, 2 ** 31 + 11])
def test_sampler_respects_clips_and_cap(seed):
    for prompt, out in _first(seed, 96):
        assert MIX["prompt"]["lo"] <= len(prompt) <= MIX["prompt"]["hi"]
        assert 1 <= out <= MIX["output"]["hi"]
        assert len(prompt) + out <= MIX["max_total"]
        assert min(prompt) >= 1 and max(prompt) < 50257


def test_every_seed_serves_the_same_set_of_sizes():
    sizes = lambda seed: sorted((len(p), o) for p, o in _first(seed, 48))
    assert sizes(3) == sizes(4) == sorted(traffic.length_pool(MIX))
    pool = traffic.length_pool(MIX)
    assert len(pool) == MIX["pool"]
    # heavy tailed: the upper clips are reached
    assert min(p for p, _ in pool) >= 64 and max(p for p, _ in pool) == 1536
    assert max(o for _, o in pool) == 384


def test_percentile_is_nearest_rank():
    assert traffic.percentile([4, 1, 3, 2], 50) == 2
    assert traffic.percentile([4, 1, 3, 2], 95) == 4
    assert traffic.percentile(list(range(1, 101)), 95) == 95


class _Clock(object):
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class _Req(object):
    def __init__(self, rid, prompt, n_new):
        self.rid, self.prompt, self.n_new, self.emitted = \
            rid, list(prompt), n_new, 1


class StubServer(object):
    """admit costs 1 ms a prompt token, a step 0.5 s and delivers `chunk`
    tokens to every live request."""

    def __init__(self, clock, chunk=1):
        self.clock, self.chunk, self.live, self.n = clock, chunk, {}, 0

    def admit(self, prompt, n_new):
        self.clock.t += 0.001 * len(prompt)
        self.n += 1
        self.live[self.n] = _Req(self.n, prompt, n_new)
        return self.n

    def step(self):
        self.clock.t += 0.5
        done = {}
        for rid, r in list(self.live.items()):
            r.emitted = min(r.n_new, r.emitted + self.chunk)
            if r.emitted >= r.n_new:
                done[rid] = r.prompt + [7] * r.n_new
                del self.live[rid]
        return done

    def progress(self):
        return {rid: r.emitted for rid, r in self.live.items()}


def _requests(spec):
    for n_prompt, n_new in spec:
        yield np.ones((n_prompt,), np.int32), n_new


def test_closed_loop_on_a_hand_made_timeline():
    # A(100 tokens, 3 new) B(200, 2) C(300, 2): by hand,
    # A arrives at .1 .8 1.6, B at .3 .8, C at 1.1 1.6
    clock = _Clock()
    loop = traffic.ClosedLoop(
        StubServer(clock), {"clients": 2},
        _requests([(100, 3), (200, 2), (300, 2), (100, 9), (100, 9)]),
        clock=clock)
    loop.round()
    loop.round()
    assert clock.t == pytest.approx(1.6)
    m = loop.reduce(0.0, 1.6)
    assert m["tokens"] == 7
    assert m["tok_s"] == pytest.approx(7 / 1.6)
    assert m["itl_p95_ms"] == pytest.approx(800.0)    # gaps .5 .5 .7 .8
    assert m["itl_p50_ms"] == pytest.approx(500.0)
    assert m["ttft_p50_ms"] == pytest.approx(200.0)   # .1 .2 .3
    assert [len(r["tokens"]) for r in m["finished"]] == [103, 202, 302]
    assert loop.turned_over == 3
    # a later window sees only what arrived in it
    late = loop.reduce(1.0, 1.6)
    assert late["tokens"] == 3 and late["admitted"] == 0


def test_a_chunk_gives_gaps_of_zero_and_one_of_its_time():
    clock = _Clock()
    loop = traffic.ClosedLoop(StubServer(clock, chunk=2), {"clients": 1},
                              _requests([(100, 5), (100, 5)]), clock=clock)
    loop.round()
    loop.round()
    rec = loop.records[0]
    assert rec["arrivals"] == pytest.approx([0.1, 0.6, 0.6, 1.1, 1.1])
    m = loop.reduce(0.0, 2.0)
    assert m["gaps"] == 4 and m["itl_p50_ms"] == pytest.approx(0.0)
    assert m["itl_p95_ms"] == pytest.approx(500.0)


def test_run_until_stops_at_the_time_or_the_turnover():
    clock = _Clock()
    loop = traffic.ClosedLoop(StubServer(clock), {"clients": 2},
                              _requests([(100, 2)] * 50), clock=clock)
    loop.run_until(turned_over=2)
    assert loop.turned_over >= 2
    t = clock.t
    loop.run_until(t_end=t + 2.0)
    assert t + 2.0 <= clock.t < t + 3.0


def test_sample_keeps_the_longest():
    done = [{"prompt": [1] * 5, "tokens": [1] * (5 + n)} for n in
            (3, 9, 4, 2, 8)]
    for seed in range(5):
        pick = traffic.sample_finished(done, seed, 3)
        assert len(pick) == 3 and pick[0] is done[1]
    assert traffic.sample_finished([], 0, 3) == []


class _Steps(object):
    items_per_step = 128

    def __init__(self, clock):
        self.clock, self.n = clock, 0

    def step(self):
        self.clock.t += 0.25
        self.n += 1
        return float(self.n)

    def fetch(self, handle):
        return handle

    def barrier(self):
        self.clock.t += 0.05


def test_train_steps_counts_whole_steps_over_the_whole_window():
    clock = _Clock()
    s = _Steps(clock)
    w = traffic.run_train_steps(s, {"fetch_every": 2}, seconds=1.0,
                                clock=clock)
    # opening barrier .05, four steps of .25 reach 1.0 s, closing barrier
    assert w["steps"] == 4 and w["items"] == 512
    assert w["window_s"] == pytest.approx(1.05)
    assert w["losses"] == [2.0, 4.0]
    w = traffic.run_train_steps(s, {"fetch_every": 10}, steps=3, clock=clock)
    assert w["steps"] == 3 and w["losses"] == []
