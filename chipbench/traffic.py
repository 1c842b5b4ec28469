"""The one general traffic generator and the two drivers. A traffic mix is a
data file `chipbench/traffic/<name>.json` with a `kind`:

  train-steps   steps back to back on one seeded batch, the loss fetched
                every `fetch_every`-th step, a barrier at the end
  closed-loop   `clients` callers, each sending its next request the moment
                its last one finished; lengths from a fixed, stratified set
                (every seed serves the same set of sizes, in another order)

Drivers talk to the system under test through a small session object that
a runner builds (chipbench/runners/): they never import the program.
All times are the host's `time.perf_counter`.
"""

import math
import statistics
import time
from contextlib import contextmanager

import numpy as np


def _norm_ppf(p):
    return statistics.NormalDist().inv_cdf(p)


def _lognormal_quantiles(spec, n):
    """n stratified lengths of a log-normal(median, sigma) clipped to
    [lo, hi]: the quantiles at (i + 0.5) / n."""
    out = []
    for i in range(n):
        v = spec["median"] * math.exp(spec["sigma"] * _norm_ppf((i + 0.5) / n))
        out.append(int(min(max(round(v), spec["lo"]), spec["hi"])))
    return out


def length_pool(traffic):
    """The fixed set of (prompt_len, output_len) pairs of a closed-loop
    mix: a function of the traffic file alone, never of --seed."""
    n = traffic["pool"]
    prompts = _lognormal_quantiles(traffic["prompt"], n)
    outputs = _lognormal_quantiles(traffic["output"], n)
    order = np.random.RandomState(traffic["pairing_seed"]).permutation(n)
    pairs = []
    for p, o in zip(prompts, (outputs[j] for j in order)):
        o = min(o, traffic["max_total"] - p)
        pairs.append((p, o))
    return pairs


def request_stream(traffic, seed, vocab):
    """Endless seeded stream of (prompt tokens, n_new): the pool in a
    seed-permuted order, cycled; token contents drawn from the seed."""
    pool = length_pool(traffic)
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    while True:
        for j in rng.permutation(len(pool)):
            p, o = pool[j]
            yield rng.randint(1, vocab, (p,)).astype(np.int32), o


@contextmanager
def _no_span(name):
    yield


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q / 100.0 * len(s)) - 1))]


# ---------------------------------------------------------- train-steps ---

def run_train_steps(session, traffic, seconds=None, steps=None, span=_no_span,
                    clock=time.perf_counter):
    """Drive `session.step()` back to back for `seconds` (or exactly
    `steps`), fetching every `fetch_every`-th loss; close by a barrier.
    Returns {"steps", "window_s", "items", "losses"}; a rate taken from it
    counts whole finished steps over the whole window."""
    every = traffic["fetch_every"]
    losses, n = [], 0
    session.barrier()
    t_open = clock()
    while True:
        if steps is not None:
            if n >= steps:
                break
        elif clock() - t_open >= seconds:
            break
        with span("bench.step"):
            handle = session.step()
        n += 1
        if n % every == 0:
            with span("bench.fetch_loss"):
                losses.append(session.fetch(handle))
    with span("bench.barrier"):
        session.barrier()
    window = clock() - t_open
    return {"steps": n, "window_s": window,
            "items": n * session.items_per_step, "losses": losses}


# ---------------------------------------------------------- closed-loop ---

class ClosedLoop(object):
    """Closed-loop driver over a server session with
    `admit(prompt, n_new) -> rid | None` (returns once the first token is
    visible), `step() -> {rid: tokens}` of the requests that finished, and
    `progress() -> {rid: tokens emitted so far}` of the live ones.

    Every token's host-visible arrival time is kept, so that any window of
    the run can be reduced afterwards with `reduce()`."""

    def __init__(self, session, traffic, requests, clock=time.perf_counter,
                 span=_no_span):
        self.session, self.requests = session, requests
        self.clients = traffic["clients"]
        self.clock, self.span = clock, span
        self.live = {}        # rid -> record
        self.records = []     # every request ever admitted, in order
        self.turned_over = 0

    def _admit_idle(self):
        while len(self.live) < self.clients:
            prompt, n_new = next(self.requests)
            t0 = self.clock()
            with self.span("bench.admit"):
                rid = self.session.admit(prompt, n_new)
            t1 = self.clock()
            if rid is None:
                self.requests = _push_back((prompt, n_new), self.requests)
                return
            rec = {"rid": rid, "prompt": prompt, "n_new": n_new,
                   "t_admit": t0, "arrivals": [t1], "tokens": None,
                   "t_done": None}
            self.live[rid] = rec
            self.records.append(rec)

    def round(self):
        """Admit for every idle client, then one scheduling step."""
        self._admit_idle()
        with self.span("bench.step"):
            finished = self.session.step()
        now = self.clock()
        progress = self.session.progress()
        for rid, rec in list(self.live.items()):
            if rid in finished:
                rec["tokens"] = list(finished[rid])
                emitted = len(rec["tokens"]) - len(rec["prompt"])
                rec["t_done"] = now
                del self.live[rid]
                self.turned_over += 1
            else:
                emitted = progress.get(rid, len(rec["arrivals"]))
            rec["arrivals"].extend([now] * (emitted - len(rec["arrivals"])))

    def run_until(self, t_end=None, turned_over=None, give_up=None):
        while True:
            now = self.clock()
            if t_end is not None and now >= t_end:
                return
            if turned_over is not None and (
                    self.turned_over >= turned_over
                    or (give_up is not None and now >= give_up)):
                return
            self.round()

    def reduce(self, t_open, t_close):
        """Metrics of the window [t_open, t_close] from the arrival times.
        A chunk that delivers k tokens at once gives k-1 gaps of 0 and one
        of the chunk's time: that is what a client sees."""
        tokens, gaps, ttft = 0, [], []
        for rec in self.records:
            arr = rec["arrivals"]
            tokens += sum(1 for t in arr if t_open <= t <= t_close)
            gaps.extend(b - a for a, b in zip(arr, arr[1:])
                        if t_open <= b <= t_close)
            if t_open <= rec["t_admit"] and arr[0] <= t_close:
                ttft.append(arr[0] - rec["t_admit"])
        window = t_close - t_open
        done = [r for r in self.records if r["t_done"] is not None
                and t_open <= r["t_done"] <= t_close]
        return {"window_s": window, "tokens": tokens,
                "tok_s": tokens / window,
                "itl_p95_ms": 1e3 * percentile(gaps, 95) if gaps else None,
                "itl_p50_ms": 1e3 * percentile(gaps, 50) if gaps else None,
                "ttft_p50_ms": 1e3 * percentile(ttft, 50) if ttft else None,
                "gaps": len(gaps), "admitted": len(ttft),
                "finished": done}


def _push_back(item, stream):
    yield item
    for x in stream:
        yield x


def sample_finished(finished, seed, n):
    """A seeded sample of `n` finished requests with the longest (most
    served tokens) always in it."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["tokens"])
                                   - len(finished[i]["prompt"])))
    rest = order[1:]
    rng = np.random.RandomState((int(seed) + 17) % (2 ** 32))
    rng.shuffle(rest)
    return [finished[i] for i in [order[0]] + rest[: n - 1]]
