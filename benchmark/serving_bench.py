"""End-to-end serving throughput: the levers, one number each.

Measures models/transformer.py's serving stack at batch=1 (the latency-
bound serving shape; decode_bench.py covers batched decode):

  prefill         prompt tokens/s through the one-pass batched prefill
  generate        greedy tokens/s (prefill + lax.scan decode)
  generate_int8   same, with weight-only int8 params (dequant fused
                  into the matmuls)
  generate_int8kv int8 weights AND int8 KV cache (kv_cache_int8):
                  the decode loop streams the cache at int8 width
  speculative     tokens/s with a small random-init draft proposing
                  k=4 per round + measured acceptance (greedy-exact;
                  random draft ~never agrees, so this is the
                  all-overhead LOWER bound)
  spec_selfdraft  same machinery with draft=target. With TRAINED
                  weights this is the always-accepts upper bound; on
                  the bench's random-init weights the near-tie logits
                  make the chunked-verify and per-token argmax flip
                  (documented fp tie noise), so read acceptance as
                  what it measures: tie density, not a ceiling
  continuous      aggregate tokens/s serving a mixed-length request
                  queue through the ContinuousBatcher slot pool vs
                  the same jobs sequentially through generate()

With ``--paged`` it runs the paged-KV A/B instead: a mixed-length
workload through the dense-lane batcher vs the paged one at an EQUAL
cache-HBM budget (the paged pool holds exactly the dense lanes' cache
positions, split into MXNET_KV_BLOCK_SIZE blocks, spread over more
lanes). Streams are bit-identical (tested); what changes is
ADMISSION — dense burns a [max_len] row per request, paged burns the
request's actual worst-case blocks — so the leg prints peak/total
admitted-request columns alongside tokens/s, then the PR 7 latency
percentile table from one instrumented paged run. On CPU the A/B model runs float32: CPU bf16 is software-
emulated at ~2x the compute cost, and that emulation tax drowns the
host-side round-trip effect the A/B exists to measure (on TPU, where
bf16 is native, the leg keeps the serving default dtype).

With ``--megakernel`` it runs the decode-megakernel A/B instead: the
same paged x int8-KV x speculative workload with
MXNET_PAGED_DECODE_PALLAS off (fused-XLA gather) vs on (the batched-
lane Pallas kernel, kernels/paged_decode.py), bs in {8, 16} x T in
{1024, 4096}. Greedy streams are enforced bit-exact between arms (the
leg exits nonzero otherwise); the row reports tokens/s per arm, the
speedup, and GB/step with the kernel's own attribution-scope bytes
broken out.

With ``--spec-k K`` it runs the BATCHED speculative-decoding A/B
instead: the same request pool through the plain batcher vs spec_k=K
n-gram self-drafting, on two workloads — repetitive (templated
prompts, the prompt-lookup habitat) and adversarial (uniform-random
prompts, where drafts mostly miss and the MXNET_SPEC_ACCEPT_FLOOR
controller walks per-lane k down). Streams are bit-identical (tested);
what changes is the TARGET-DISPATCHES-PER-EMITTED-TOKEN column — the
host-to-device dispatches paid per token — plus the
measured acceptance rate and the live adaptive-k floor.

With ``--overload`` it runs the overload-resilience leg instead (no
throughput number — a degradation ledger): a seeded mixed-priority
burst at ~4x the fleet's KV-block capacity over a 2-replica router
with the circuit breaker and brownout ladder on, one replica chaos-
killed mid-storm. The JSON row carries the completed/shed/expired
split, preemption + bit-exact-resume counts, per-priority completion
attainment, the brownout rung high-water mark, the breaker transition
list, and the preempt-stall percentiles; the leg exits nonzero if the
degradation contract breaks (a deadlock, a non-priority-0 drop, a
diverged stream, or the killed replica failing to return).

With ``--mem-pressure`` it runs the HBM-pressure resilience leg
(again a degradation ledger, not a throughput number): a seeded
mixed-length paged workload takes one deterministic
RESOURCE_EXHAUSTED on its decode dispatch — the batcher must shrink
the KV pool and retry (park blocks, preempt a lane through the
bit-exact resume path) instead of rebuilding lanes — and a second
batcher walks the kv_shrink brownout rung down through a FAILED pool
grow (reduced capacity, no crash) and a clean grow that restores it.
The JSON row carries blocks parked vs requested, lanes parked and
resumed, the kv_shrink/OOM-taxonomy counters, stream bit-exactness
vs solo generate(), the grow-back outcome, and whether the health
snapshot exports mem.headroom_bytes; the leg exits nonzero if any of
it breaks (docs/ROBUSTNESS.md "Memory pressure").

With ``--journal`` it runs the durability-tax A/B leg: the same
seeded paged + pipelined workload with the request write-ahead
journal off and on. The hard contract is the journal being OFF-PATH —
streams and dispatch counts bit-identical between legs — with the
overhead percentage reported (chip target <3%; the CPU-smoke gate is
``MXNET_SERVING_JOURNAL_AB_MAX_PCT``, default 25, because 1-core
timing noise dwarfs the real tax).

After the throughput legs, the continuous-batching pools run once more
INSTRUMENTED (MXNET_OBS forced on for that run only) to print the
request-level TTFT / ITL / e2e / queue-wait percentile table from the
batcher's log-bucketed histograms, emit the same distributions as a
machine-readable JSON line, and — with ``--json PATH`` — write them as an artifact file.

    python - < benchmark/serving_bench.py
    python - --spec-k 4 < benchmark/serving_bench.py
    python - --json serving_latency.json < benchmark/serving_bench.py
    MXNET_SERVING_SMOKE=1 JAX_PLATFORMS=cpu python - < benchmark/serving_bench.py

Run from the repo root via stdin so cwd lands on sys.path.
"""

import json
import os
import sys
import time

import numpy as np

SMOKE = bool(os.environ.get("MXNET_SERVING_SMOKE"))


def _time_tokens(fn, n_tokens, warm_runs=1, timed_runs=3):
    """Median wall-clock tokens/s over timed_runs calls of fn()."""
    for _ in range(warm_runs):
        fn()
    rates = []
    for _ in range(timed_runs):
        t0 = time.time()
        fn()
        rates.append(n_tokens / (time.time() - t0))
    return float(np.median(rates))


def _spec_k_arg(argv=None):
    """--spec-k K from the stdin-run argv; None when absent."""
    argv = sys.argv[1:] if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--spec-k" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--spec-k="):
            return int(a.split("=", 1)[1])
    return None


def _json_arg(argv=None):
    """--json PATH from the stdin-run argv: write the per-leg latency
    distributions there."""
    argv = sys.argv[1:] if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--json" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--json="):
            return a.split("=", 1)[1]
    return None


_LATENCY_HISTS = ("serving.ttft_ms", "serving.itl_ms",
                  "serving.e2e_ms", "serving.queue_ms")


def _latency_report(run_fn, leg, **extra):
    """One extra run with telemetry ON: collect the request-level
    TTFT/ITL/e2e/queue-wait histograms the batcher records, print the
    percentile table + one machine-readable JSON line, and return the distributions for the --json
    artifact. The timed legs above run with telemetry off — the
    distributions come from their own run so the throughput numbers
    stay uninstrumented."""
    from mxnet_tpu.observability import core as obs
    from mxnet_tpu.observability import histogram as hist
    obs.set_enabled(True)
    obs.reset()
    try:
        run_fn()
        dists = {name: h.snapshot()
                 for name, h in sorted(hist.histograms().items())
                 if name in _LATENCY_HISTS}
        goodput = obs.counters().get("serving.goodput_tok_s")
        goodput = goodput.value if goodput is not None else None
    finally:
        obs.set_enabled(None)
        obs.reset()
    fmt = "%-22s %8s %10s %10s %10s %10s %10s"
    print("%s latency percentiles (ms, instrumented run):" % leg)
    print(fmt % ("metric", "count", "mean", "p50", "p90", "p99",
                 "p99.9"))
    for name, s in dists.items():
        print(fmt % (name, s["count"], "%.3f" % s["mean"],
                     "%.3f" % s["p50"], "%.3f" % s["p90"],
                     "%.3f" % s["p99"], "%.3f" % s["p999"]))
    rec = dict(extra)
    rec.update({"leg": "%s_latency" % leg, "goodput_tok_s": goodput,
                "distributions": dists})
    print(json.dumps(rec), flush=True)
    from benchmark.common import record_bench_profile
    record_bench_profile(
        "%s_latency" % leg, value=goodput, unit="tok/s",
        metric="%s_goodput_tok_s" % leg,
        p50_ms={name: s["p50"] for name, s in dists.items()})
    return rec


def _write_artifact(path, reports):
    if not path:
        return
    with open(path, "w") as f:
        json.dump({"bench": "serving_bench", "reports": reports}, f,
                  indent=1)
    print("wrote latency artifact -> %s" % path, flush=True)


def spec_ab(k):
    """The batched-speculation A/B (see the module docstring): the
    same request pool through the plain batcher vs spec_k=k n-gram
    self-drafting, repetitive AND adversarial workloads, one JSON row
    per leg. The headline column is target dispatches per emitted
    token — every dispatch is a host sync, so that ratio is the
    latency lever speculation pulls (how much each sync costs on the
    attached chip is not measured yet)."""
    from benchmark.common import fetch_barrier  # noqa: F401  (parity)
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher

    backend = jax.default_backend()
    if SMOKE:
        # the headline column here is a DISPATCH COUNT ratio —
        # timing-independent, so the vocabulary's size does not bind.
        # What the leg does need is a
        # verified stream with real repetition: d_model 16 gives the
        # random-init smoke model a strong enough greedy attractor
        # that its own rollouts stand in for repetitive text
        vocab = 8192
        d_model, heads, layers, max_len = 16, 2, 1, 96
        t_prompt, n_new, n_jobs, slots, chunk = 24, 64, 4, 2, 1
    else:
        vocab = 32000
        d_model, heads, layers, max_len = 512, 8, 8, 4096
        t_prompt, n_new, n_jobs, slots = 512, 128, 16, 8
        chunk = int(os.environ.get("MXNET_SERVE_CHUNK", "16"))
    dtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    cfg = tf.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=layers, d_ff=4 * d_model, max_len=max_len,
        dtype=dtype)
    params = tf.init_params(cfg, seed=0)
    jrng = np.random.RandomState(3)
    # repetitive: each prompt is a window of the MODEL'S OWN greedy
    # rollout — the serve-continuation / quoted-context shape, where
    # the continuation's n-grams already occur in the prompt. This is
    # prompt-lookup drafting's habitat (code, templated output,
    # re-served context in the real world)
    rep_jobs = []
    for _ in range(n_jobs):
        seed = list(jrng.randint(1, vocab, 6))
        stream = np.asarray(tf.generate(
            params, jnp.asarray([seed], jnp.int32), t_prompt + 10,
            cfg, greedy=True)[0])
        rep_jobs.append((list(stream[-t_prompt:]), n_new))
    adv_jobs = [(list(jrng.randint(1, vocab, t_prompt)), n_new)
                for _ in range(n_jobs)]
    total_new = n_jobs * n_new
    print("serving speculative A/B: backend=%s dtype=%s d_model=%d "
          "layers=%d k=%d chunk=%d slots=%d jobs=%d"
          % (backend, np.dtype(dtype).name, d_model, layers, k,
             chunk, slots, n_jobs), flush=True)

    def run(jobs, **kw):
        srv = ContinuousBatcher(params, cfg, max_batch=slots,
                                chunk_size=chunk, **kw)
        pending = list(jobs)
        k_live = float(k)
        while pending or srv.active_count:
            while pending and srv.has_capacity:
                p, n = pending.pop(0)
                srv.admit(p, n)
            srv.step()
            if srv._spec_on and srv.active_count:
                # adaptive-k low-water mark, read while lanes are LIVE
                # (finish resets a lane's k back to spec_k)
                k_live = min(k_live, srv.health_snapshot()
                             ["serving.spec_k_live"])
        return srv, k_live

    def leg(name, jobs, **kw):
        run(jobs, **kw)                       # compile / warm
        t0 = time.time()
        srv, k_live = run(jobs, **kw)
        rate = total_new / (time.time() - t0)
        dpt = srv.dispatch_count / total_new  # dispatches per token
        snap = srv.health_snapshot()
        row = {"leg": "serving_spec_ab", "workload": name,
               "spec_k": kw.get("spec_k", 0),
               "tokens_per_s": round(rate, 1),
               "target_dispatches_per_token": round(dpt, 3),
               "accept_rate": round(
                   snap.get("serving.spec_draft_ratio", 0.0), 3),
               "spec_k_live_min": k_live if kw.get("spec_k") else None,
               "slots": slots, "jobs": n_jobs, "vocab": vocab,
               "backend": backend}
        print(json.dumps(row), flush=True)
        return row

    base = leg("repetitive", rep_jobs)
    spec = leg("repetitive", rep_jobs, spec_k=k)
    leg("adversarial", adv_jobs, spec_k=k, spec_accept_floor=0.6)
    cut = (base["target_dispatches_per_token"]
           / spec["target_dispatches_per_token"])
    print('{"leg": "serving_spec_ab_summary", "spec_k": %d, '
          '"dispatch_cut": %.2f}' % (k, cut), flush=True)
    rep = _latency_report(lambda: run(rep_jobs, spec_k=k),
                          "serving_spec_ab", spec_k=k, slots=slots,
                          backend=backend)
    _write_artifact(_json_arg(), [rep])


def paged_ab():
    """The paged-KV A/B (see the module docstring): same HBM budget,
    dense lanes vs block pool, mixed-length mixed-arrival workload.
    Columns: peak concurrently-admitted requests, total tokens/s."""
    from benchmark.common import fetch_barrier  # noqa: F401  (parity)
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher

    backend = jax.default_backend()
    if SMOKE:
        vocab = 8192
        d_model, heads, layers, max_len = 32, 2, 1, 96
        t_prompt = 24
        n_jobs, dense_slots, block_size = 12, 2, 8
    else:
        vocab = 32000
        d_model, heads, layers, max_len = 512, 8, 8, 4096
        t_prompt = 512
        n_jobs, dense_slots = 32, 8
        block_size = int(os.environ.get("MXNET_KV_BLOCK_SIZE", "16"))
    dtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    cfg = tf.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=layers, d_ff=4 * d_model, max_len=max_len,
        dtype=dtype)
    params = tf.init_params(cfg, seed=0)
    # the HBM budget: dense_slots full-context rows, expressed in
    # blocks for the paged pool; 4x the lanes so admission is bounded
    # by BLOCKS, not lane count
    num_blocks = dense_slots * (max_len // block_size) + 1
    paged_slots = dense_slots * 4
    jrng = np.random.RandomState(1)
    # mixed-length: short interactive prompts next to near-full ones,
    # budgets well under max_len — the regime where a dense row wastes
    # most of its positions
    jobs = []
    for _ in range(n_jobs):
        t_p = int(jrng.randint(max(2, t_prompt // 8), t_prompt))
        n_new = int(jrng.randint(8, max(9, t_prompt // 2)))
        jobs.append((list(jrng.randint(1, vocab, t_p)), n_new))
    total_new = sum(n for _, n in jobs)
    print("serving paged A/B: backend=%s dtype=%s d_model=%d "
          "layers=%d max_len=%d block=%d budget=%d blocks "
          "(dense %d lanes, paged %d lanes)"
          % (backend, np.dtype(dtype).name, d_model, layers, max_len,
             block_size, num_blocks - 1, dense_slots, paged_slots),
          flush=True)

    def make(paged):
        if paged:
            return ContinuousBatcher(
                params, cfg, max_batch=paged_slots, paged=True,
                block_size=block_size, num_blocks=num_blocks)
        return ContinuousBatcher(params, cfg, max_batch=dense_slots)

    def run_mixed(paged, stats=None):
        srv = make(paged)
        waiting, arr_i, step_i = [], 0, 0
        peak = 0
        while arr_i < len(jobs) or waiting or srv.active_count:
            if arr_i < len(jobs) and step_i % 2 == 0:
                waiting.append((jobs[arr_i], time.perf_counter_ns()))
                arr_i += 1
            while waiting and srv.has_capacity:
                (p, n), enq = waiting[0]
                if srv.admit(p, n, enqueued_ns=enq) is None:
                    break
                waiting.pop(0)
            peak = max(peak, srv.active_count)
            srv.step()
            step_i += 1
        if stats is not None:
            stats["peak_admitted"] = peak

    stats = {"dense": {}, "paged": {}}
    run_mixed(False, stats["dense"])        # warm + admission stats
    run_mixed(True, stats["paged"])
    dense_rate = _time_tokens(lambda: run_mixed(False), total_new)
    paged_rate = _time_tokens(lambda: run_mixed(True), total_new)
    fmt = "%-8s %18s %14s"
    print(fmt % ("config", "peak admitted", "tokens/s"))
    print(fmt % ("dense", stats["dense"]["peak_admitted"],
                 "%.1f" % dense_rate))
    print(fmt % ("paged", stats["paged"]["peak_admitted"],
                 "%.1f" % paged_rate))
    print('{"leg": "continuous_paged_ab", "block_size": %d, '
          '"num_blocks": %d, "dense_slots": %d, "paged_slots": %d, '
          '"dense_peak_admitted": %d, "paged_peak_admitted": %d, '
          '"dense_tokens_per_s": %.1f, "paged_tokens_per_s": %.1f, '
          '"admitted_ratio": %.2f, "throughput_ratio": %.3f, '
          '"jobs": %d, "backend": "%s"}'
          % (block_size, num_blocks, dense_slots, paged_slots,
             stats["dense"]["peak_admitted"],
             stats["paged"]["peak_admitted"],
             dense_rate, paged_rate,
             stats["paged"]["peak_admitted"]
             / max(stats["dense"]["peak_admitted"], 1),
             paged_rate / dense_rate, n_jobs, backend), flush=True)
    rep = _latency_report(lambda: run_mixed(True), "continuous_paged",
                          block_size=block_size,
                          num_blocks=num_blocks,
                          paged_slots=paged_slots, backend=backend)
    _write_artifact(_json_arg(), [rep])


def megakernel_ab():
    """The decode-megakernel A/B (``--megakernel``): the SAME paged x
    int8-KV x speculative workload through the ContinuousBatcher with
    MXNET_PAGED_DECODE_PALLAS off (fused-XLA gather + dense
    contraction, today's path) vs on (kernels/paged_decode.py batched-
    lane Pallas kernel reading the pool through the tables). The
    _serving_jit key includes the flag, so each arm compiles its own
    programs — no cross-arm cache staleness.

    ACCEPTANCE BAR (ISSUE 16): on chip the kernel arm must BEAT the
    dense-XLA arm's tokens/s on the paged x int8 x spec mix at
    bs >= 8 (configs below sweep bs in {8, 16} x T in {1024, 4096}),
    and the attribution rows must report the kernel's bytes moved
    (`paged_decode_kernel` / `paged_verify_kernel` scopes in the
    GB/step column). Greedy streams are enforced BIT-EXACT between
    arms — the leg exits nonzero on any stream mismatch, so a faster
    wrong kernel can never post a number. The honest prior this kernel
    answers: the per-sequence flash-decode kernel LOST its A/B 841 vs
    4075 tok/s (PERF.md round 5); the gather-path bytes are what it
    never attacked.
    """
    from benchmark.common import fetch_barrier  # noqa: F401  (parity)
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.observability import attribution

    backend = jax.default_backend()
    dtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    if SMOKE:
        configs = [(4, 128)]                   # (slots, max_len)
        vocab, d_model, heads, layers = 8192, 32, 2, 1
        t_prompt, n_jobs, spec_k, block_size = 16, 6, 2, 8
    else:
        configs = [(8, 1024), (8, 4096), (16, 1024), (16, 4096)]
        vocab, d_model, heads, layers = 32000, 512, 8, 8
        t_prompt, n_jobs, spec_k = 256, 24, 3
        block_size = int(os.environ.get("MXNET_KV_BLOCK_SIZE", "16"))

    def one_config(slots, max_len):
        cfg = tf.TransformerConfig(
            vocab_size=vocab, d_model=d_model, n_heads=heads,
            n_layers=layers, d_ff=4 * d_model, max_len=max_len,
            dtype=dtype, kv_cache_int8=True)
        params = tf.init_params(cfg, seed=0)
        num_blocks = slots * (max_len // block_size) + 1
        jrng = np.random.RandomState(17)
        jobs = []
        for _ in range(n_jobs):
            t_p = int(jrng.randint(max(2, t_prompt // 8), t_prompt))
            n_new = int(jrng.randint(8, max(9, t_prompt // 2)))
            jobs.append((list(jrng.randint(1, vocab, t_p)), n_new))
        total_new = sum(n for _, n in jobs)

        def run(collect=None):
            srv = ContinuousBatcher(
                params, cfg, max_batch=slots, paged=True,
                block_size=block_size, num_blocks=num_blocks,
                spec_k=spec_k)
            waiting, arr_i, step_i = list(jobs), 0, 0
            while waiting or srv.active_count:
                while waiting and srv.has_capacity:
                    p, n = waiting[0]
                    if srv.admit(p, n) is None:
                        break
                    waiting.pop(0)
                for rid, toks in srv.step().items():
                    if collect is not None:
                        collect[rid] = list(toks)
                step_i += 1

        def arm(on):
            # trace-time flag: set BEFORE any dispatch compiles; the
            # jit key carries it, so arms never share a program
            if on:
                os.environ["MXNET_PAGED_DECODE_PALLAS"] = "1"
            else:
                os.environ.pop("MXNET_PAGED_DECODE_PALLAS", None)
            streams = {}
            run(collect=streams)               # warm + stream capture
            rate = _time_tokens(run, total_new)
            # GB/step through the attribution scopes: lower the real
            # serving entry points under this arm's flag and read the
            # per-scope HBM rollup (the kernel arm's bytes land under
            # paged_decode_kernel / paged_verify_kernel)
            origin = "bench.megakernel.%s" % ("pallas" if on else
                                              "dense")
            pool = tf.init_paged_cache(cfg, num_blocks, block_size)
            tables = jnp.zeros((slots, max_len // block_size),
                               jnp.int32)
            toks = jnp.zeros((slots,), jnp.int32)
            pos = jnp.zeros((slots,), jnp.int32)
            step_fn = jax.jit(lambda p, pl, tb, t, ps:
                              tf.decode_step_paged(p, pl, tb, t, ps,
                                                   cfg))
            attribution.register_program(
                origin, None, step_fn, (params, pool, tables, toks,
                                        pos))
            ana = attribution.program_analysis(origin) or {}
            totals = ana.get("totals", {})
            kscopes = {name: round(ent.get("hbm_bytes", 0) / 1e9, 4)
                       for name, ent in ana.get("scopes", {}).items()
                       if "paged_" in name and "_kernel" in name}
            return streams, rate, {
                "gb_per_step": round(totals.get("hbm_bytes", 0) / 1e9,
                                     4),
                "kernel_scope_gb": kscopes}

        d_streams, d_rate, d_bytes = arm(False)
        p_streams, p_rate, p_bytes = arm(True)
        os.environ.pop("MXNET_PAGED_DECODE_PALLAS", None)
        exact = d_streams == p_streams
        row = {"leg": "serving_megakernel",
               "slots": slots, "max_len": max_len,
               "spec_k": spec_k, "block_size": block_size,
               "int8_kv": True, "jobs": n_jobs,
               "streams_bit_exact": exact,
               "dense_tokens_per_s": round(d_rate, 1),
               "pallas_tokens_per_s": round(p_rate, 1),
               "speedup": round(p_rate / max(d_rate, 1e-9), 3),
               "dense_gb_per_step": d_bytes["gb_per_step"],
               "pallas_gb_per_step": p_bytes["gb_per_step"],
               "pallas_kernel_scope_gb": p_bytes["kernel_scope_gb"],
               "backend": backend}
        print(json.dumps(row), flush=True)
        if not exact:
            bad = sorted(r for r in d_streams
                         if d_streams[r] != p_streams.get(r))
            print("megakernel A/B FAILED: greedy streams diverge "
                  "between arms (requests %s) — a kernel that does "
                  "not reproduce the dense path's tokens has no "
                  "business posting a throughput number" % bad[:8],
                  flush=True)
            sys.exit(1)
        return row

    fmt = "%-14s %8s %10s %10s %8s"
    print("serving megakernel A/B: backend=%s dtype=%s d_model=%d "
          "layers=%d spec_k=%d block=%d int8_kv=on"
          % (backend, np.dtype(dtype).name, d_model, layers, spec_k,
             block_size), flush=True)
    print(fmt % ("config", "dense", "pallas", "speedup", "exact"))
    rows = []
    for slots, max_len in configs:
        r = one_config(slots, max_len)
        rows.append(r)
        print(fmt % ("bs%d/T%d" % (slots, max_len),
                     "%.1f" % r["dense_tokens_per_s"],
                     "%.1f" % r["pallas_tokens_per_s"],
                     "%.3f" % r["speedup"],
                     r["streams_bit_exact"]), flush=True)
    _write_artifact(_json_arg(), rows)


def overload_ab():
    """The overload-resilience leg (``--overload``): a seeded mixed-
    priority burst at ~4x the fleet's KV-block capacity lands on a
    2-replica router (breaker + brownout on) while a chaos spec kills
    replica r1 mid-storm — the ISSUE 12 acceptance workload, run as a
    bench leg. Nothing here is a throughput number; the row reports
    the DEGRADATION ledger: completed / shed / expired split (shed
    and expired only ever priority 0), preemption + resume counts,
    per-priority completion attainment, the brownout rung high-water
    mark, the breaker transition list for the killed replica, and
    whether every completed stream stayed bit-exact vs solo
    generate() — plus the preempt-stall percentiles from the same
    instrumented run."""
    from benchmark.common import fetch_barrier  # noqa: F401  (parity)
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.router import ReplicaRouter
    from mxnet_tpu.observability import chaos
    from mxnet_tpu.observability import core as obs
    from mxnet_tpu.observability import histogram as hist

    backend = jax.default_backend()
    if SMOKE:
        vocab = 8192
        d_model, heads, layers, max_len = 32, 2, 1, 96
        t_prompt, block_size = 6, 8
        steady_new, storm_new = 10, 8
        n_p2, n_p1, n_p0 = 3, 3, 4
    else:
        vocab = 32000
        d_model, heads, layers, max_len = 512, 8, 8, 4096
        t_prompt, block_size = 96, 16
        steady_new, storm_new = 128, 64
        n_p2, n_p1, n_p0 = 4, 4, 6
    dtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    cfg = tf.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=layers, d_ff=4 * d_model, max_len=max_len,
        dtype=dtype)
    params = tf.init_params(cfg, seed=0)
    # each replica gets exactly the blocks two steady streams pin, so
    # the storm can only be funded by preemption, brownout shed, or
    # deadline expiry — the degradation machinery under test
    steady_life = (t_prompt + steady_new - 2) // block_size + 1
    num_blocks = 2 * steady_life + 1
    jrng = np.random.RandomState(12)

    def prompt():
        return list(jrng.randint(1, vocab, t_prompt))

    steady = [(prompt(), steady_new, 0, None) for _ in range(4)]
    storm = ([(prompt(), storm_new, 2, None) for _ in range(n_p2)]
             + [(prompt(), storm_new, 1, None) for _ in range(n_p1)]
             + [(prompt(), storm_new, 0, None) for _ in range(n_p0)]
             + [(prompt(), storm_new, 0, 0) for _ in range(2)])
    jobs = steady + storm
    print("serving overload: backend=%s dtype=%s d_model=%d layers=%d "
          "block=%d pool=%d blocks/replica, %d steady + %d storm jobs"
          % (backend, np.dtype(dtype).name, d_model, layers,
             block_size, num_blocks - 1, len(steady), len(storm)),
          flush=True)

    solo = {}
    prio = {}
    obs.set_enabled(True)
    obs.reset()
    chaos.reset()
    t0 = time.time()
    try:
        pre0 = obs.counter("serving.preemptions").value
        r = ReplicaRouter.build(
            params, cfg, n_replicas=2, max_batch=3, shed_queue=8,
            breaker=True, paged=True, block_size=block_size,
            num_blocks=num_blocks, brownout=True)

        def submit(batch):
            for p, n, pr, ddl in batch:
                rid = r.submit(p, n, priority=pr, deadline_ms=ddl)
                prio[rid] = pr
                solo[rid] = np.asarray(tf.generate(
                    params, jnp.asarray([p], jnp.int32), n, cfg,
                    greedy=True))[0].tolist()

        results = {}
        submit(steady)
        rounds = 0
        for _ in range(2):
            results.update(r.step())
            rounds += 1
        chaos.install("serving.dispatch.r1:error:at=1;"
                      "serving.dispatch.r1:error:at=2;"
                      "serving.dispatch.r1:error:at=3;"
                      "serving.dispatch.r1:error:at=4")
        submit(storm)
        rung_max = 0
        while (r._queue or r._live) and rounds < 600:
            results.update(r.step())
            rung_max = max([rung_max] + [rep._bo_rung
                                         for rep in r.replicas])
            rounds += 1
        wall = time.time() - t0
        deadlocked = bool(r._queue or r._live)
        preemptions = obs.counter("serving.preemptions").value - pre0
        stall = hist.histograms().get("serving.preempt_stall_ms")
        stall = stall.snapshot() if stall is not None else None
        # one stall observation per preempted-then-resumed stream
        resumed = stall["count"] if stall else 0
        for rep in r.replicas:
            rep.check_invariants(quiesce=True)   # zero leaked blocks
    finally:
        chaos.reset()
        obs.set_enabled(None)
        obs.reset()

    dropped = set(r.shed_rids) | set(r.expired_rids)
    exact = all(results.get(rid) == solo[rid]
                for rid in prio if rid not in dropped)
    attain = {}
    for p in (0, 1, 2):
        members = [rid for rid in prio if prio[rid] == p]
        ok = sum(1 for rid in members
                 if rid not in dropped
                 and results.get(rid) == solo[rid])
        attain["p%d" % p] = round(ok / float(len(members)), 3)
    row = {
        "leg": "serving_overload", "jobs": len(jobs),
        "completed": len(prio) - len(dropped),
        "shed": len(r.shed_rids), "expired": len(r.expired_rids),
        "dropped_priorities": sorted({prio[rid] for rid in dropped}),
        "preemptions": preemptions, "resumed": resumed,
        "brownout_rung_max": rung_max,
        "breaker_transitions": [list(ev) for ev in r.breaker_events],
        "replica_recovered": (r._alive == [True, True]
                              and r._brk_state == ["closed", "closed"]),
        "attainment": attain, "bit_exact": exact,
        "deadlocked": deadlocked, "rounds": rounds,
        "wall_s": round(wall, 2),
        "preempt_stall_ms": stall, "backend": backend,
    }
    print(json.dumps(row), flush=True)
    if deadlocked or not exact or not row["replica_recovered"] \
            or any(p > 0 for p in row["dropped_priorities"]) \
            or attain["p2"] < 1.0 or attain["p1"] < 1.0:
        print("serving overload leg FAILED its degradation contract",
              flush=True)
        sys.exit(1)


def mem_pressure_ab():
    """The memory-pressure leg (``--mem-pressure``): a seeded mixed-
    length paged workload absorbs one deterministic RESOURCE_EXHAUSTED
    on its decode dispatch — the batcher must respond with the ISSUE 14
    shrink-and-retry (park KV blocks, preempt the lowest-priority lane
    through the bit-exact resume path, redispatch against the smaller
    pool) instead of the lane-rebuild — and a second batcher walks the
    ``kv_shrink`` brownout rung down through a FAILED pool grow
    (capacity loss, never a crash) and a clean grow that restores full
    capacity. Nothing here is a throughput number; the row reports the
    DEGRADATION ledger: blocks parked vs requested, lanes parked and
    resumed, the kv_shrink/OOM-taxonomy counters, whether every stream
    stayed bit-exact vs solo generate() across the shrink, zero leaked
    blocks at quiesce, and the grow-back outcome — plus whether the
    health snapshot carries the ``mem.headroom_bytes`` field the
    router's starvation gate reads."""
    from benchmark.common import fetch_barrier  # noqa: F401  (parity)
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.observability import chaos
    from mxnet_tpu.observability import core as obs
    from mxnet_tpu.observability import membudget

    backend = jax.default_backend()
    if SMOKE:
        vocab = 8192
        d_model, heads, layers, max_len = 32, 2, 1, 96
        t_prompt, block_size = 24, 8
        n_new, n_jobs, slots = 16, 6, 3
    else:
        vocab = 32000
        d_model, heads, layers, max_len = 512, 8, 8, 2048
        t_prompt = 192
        block_size = int(os.environ.get("MXNET_KV_BLOCK_SIZE", "16"))
        n_new, n_jobs, slots = 64, 8, 4
    dtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    cfg = tf.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=layers, d_ff=4 * d_model, max_len=max_len,
        dtype=dtype)
    params = tf.init_params(cfg, seed=0)
    # pool sized so the workload fits comfortably BEFORE the shrink —
    # the injected OOM, not admission pressure, is what forces parking.
    # The forced shrink leaves exactly one full stream-lifetime of
    # blocks usable, so free capacity alone can never cover it and the
    # lane-park/resume path is guaranteed to exercise, while any single
    # stream still fits the post-shrink pool.
    life = (t_prompt + n_new - 2) // block_size + 1
    num_blocks = slots * life + 2
    shrink_n = (num_blocks - 1) - life
    jrng = np.random.RandomState(23)
    jobs = []
    for _ in range(n_jobs):
        t_p = int(jrng.randint(max(2, t_prompt // 2), t_prompt))
        jobs.append((list(jrng.randint(1, vocab, t_p)), n_new))
    print("serving mem-pressure: backend=%s dtype=%s d_model=%d "
          "layers=%d block=%d pool=%d blocks, forced shrink=%d, "
          "%d jobs over %d lanes"
          % (backend, np.dtype(dtype).name, d_model, layers,
             block_size, num_blocks - 1, shrink_n, n_jobs, slots),
          flush=True)

    solo = [np.asarray(tf.generate(
        params, jnp.asarray([p], jnp.int32), n, cfg,
        greedy=True))[0].tolist() for p, n in jobs]
    obs.set_enabled(True)
    obs.reset()
    chaos.reset()
    membudget.reset()
    # arm the budget subsystem for the leg's duration: warn-only (no
    # enforcement), but note_oom taxonomy counting and the healthz
    # memory section are armed-gated — the off-path stays one guarded
    # branch for everyone who didn't opt in
    os.environ["MXNET_MEM_BUDGET"] = "warn"
    os.environ["MXNET_MEM_KV_SHRINK_BLOCKS"] = str(shrink_n)
    t0 = time.time()
    try:
        shrinks0 = obs.counter("serving.kv_shrinks").value
        # ---- phase A: OOM on the decode dispatch -> shrink-and-retry
        chaos.inject("serving.dispatch", "oom", at=2)
        srv = ContinuousBatcher(params, cfg, max_batch=slots,
                                paged=True, block_size=block_size,
                                num_blocks=num_blocks)
        queue = list(jobs)
        order, results, alias = [], {}, {}
        parked_max = lanes_parked_max = resumed = rounds = 0
        while queue or srv.preempted or srv.active_count:
            while queue and srv.has_capacity:
                rid = srv.admit(queue[0][0], queue[0][1])
                if rid is None:
                    break
                order.append(rid)
                queue.pop(0)
            # resume parked lanes as capacity frees (the run() policy,
            # inlined so the ledger can watch the preemption ledger)
            while srv.preempted and srv.has_capacity:
                req, t_ns = srv.preempted[0]
                rid = srv.admit_continuation(
                    req.tokens, req.n_new - req.emitted, seed=req.seed,
                    emitted=req.emitted, stop_token=req.stop_token,
                    priority=req.priority, preempted_ns=t_ns)
                if rid is None:
                    break
                srv.preempted.pop(0)
                alias[rid] = alias.get(req.rid, req.rid)
                resumed += 1
            results.update(srv.step())
            lanes_parked_max = max(lanes_parked_max,
                                   len(srv.preempted))
            parked_max = max(parked_max, srv._alloc.parked_blocks)
            rounds += 1
            if rounds >= 600:
                break
        deadlocked = bool(queue or srv.preempted or srv.active_count)
        fired_dispatch = chaos.stats["oom"]
        kv_shrinks = int(
            obs.counter("serving.kv_shrinks").value - shrinks0)
        srv.check_invariants(quiesce=True)   # zero leaked blocks
        # the starvation-gate export: present whenever the platform
        # reports device memory stats (CPU doesn't — absent there is
        # the correct answer, not a miss)
        mem_section = ("mem.headroom_bytes" in srv.health_snapshot()
                       or membudget.headroom_bytes() is None)
        chaos.reset()
        if alias:
            results = {alias.get(rid, rid): toks
                       for rid, toks in results.items()}
        exact = all(results.get(rid) == solo[j]
                    for j, rid in enumerate(order))

        # ---- phase B: kv_shrink rung walk with a FAILED grow-back ----
        srv2 = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                 block_size=block_size,
                                 num_blocks=2 * life + 2, brownout=True)
        os.environ.pop("MXNET_MEM_KV_SHRINK_BLOCKS", None)
        srv2._set_rung(4)                  # kv_shrink rung parks
        rung_parked = srv2._bo_parked
        chaos.inject("kv.pool.grow", "oom", at=0)
        srv2._set_rung(0)                  # grow-back OOMs: stay shrunk
        fired_grow = chaos.stats["oom"]
        stayed_shrunk = (srv2._alloc.parked_blocks == rung_parked
                         and rung_parked > 0)
        chaos.reset()
        restored = (srv2.grow_pool(rung_parked) == rung_parked
                    and srv2._alloc.parked_blocks == 0)
        p, n = jobs[0]
        rid = srv2.admit(p, n)
        done = {}
        grounds = 0
        while rid not in done and grounds < 200:
            done.update(srv2.step())
            grounds += 1
        post_grow_exact = done.get(rid) == solo[0]
        srv2.check_invariants(quiesce=True)
        wall = time.time() - t0
        mb_stats = dict(membudget.stats)
    finally:
        os.environ.pop("MXNET_MEM_KV_SHRINK_BLOCKS", None)
        os.environ.pop("MXNET_MEM_BUDGET", None)
        chaos.reset()
        membudget.reset()
        obs.set_enabled(None)
        obs.reset()

    row = {
        "leg": "serving_mempressure", "jobs": n_jobs, "slots": slots,
        "block_size": block_size, "num_blocks": num_blocks,
        "shrink_requested": shrink_n, "parked_blocks_max": parked_max,
        "lanes_parked_max": lanes_parked_max, "resumed": resumed,
        "kv_shrinks": kv_shrinks, "oom_injected": fired_dispatch,
        "oom_caught": mb_stats["oom_caught"],
        "oom_transient": mb_stats["oom_transient"],
        "oom_structural": mb_stats["oom_structural"],
        "bit_exact": exact, "deadlocked": deadlocked,
        "rounds": rounds, "health_mem_section": mem_section,
        "grow": {"rung_parked": rung_parked,
                 "grow_oom_injected": fired_grow,
                 "stayed_shrunk": stayed_shrunk,
                 "restored": restored,
                 "post_grow_bit_exact": post_grow_exact},
        "wall_s": round(wall, 2), "backend": backend,
    }
    print(json.dumps(row), flush=True)
    if deadlocked or not exact or fired_dispatch != 1 \
            or kv_shrinks != 1 or parked_max < shrink_n \
            or lanes_parked_max < 1 or resumed < 1 \
            or not mem_section or fired_grow != 1 \
            or not stayed_shrunk or not restored \
            or not post_grow_exact:
        print("serving mem-pressure leg FAILED its degradation "
              "contract", flush=True)
        sys.exit(1)


def journal_ab():
    """The durability-tax leg (``--journal``): the SAME seeded
    mixed-length paged + pipelined workload runs twice — journal off,
    then journal on (a fresh WAL dir, default fsync policy) — and the
    row reports the token throughput of both legs plus the overhead
    percentage. The HARD contract is that the journal is off-path:
    every stream's tokens and the batcher's dispatch_count must be
    BIT-identical between legs (a journal that changes scheduling or
    numerics is a correctness bug, not a tax), and the journal must
    actually have recorded the workload (every rid tombstoned, GC-able
    state). The overhead gate is ``MXNET_SERVING_JOURNAL_AB_MAX_PCT``
    (default 25 — CPU smoke timing is noisy; the chip-queue target
    from the ISSUE is <3% and the row is what tracks it)."""
    import tempfile

    from benchmark.common import fetch_barrier  # noqa: F401  (parity)
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.journal import RequestJournal
    from mxnet_tpu.models.serving import ContinuousBatcher

    backend = jax.default_backend()
    if SMOKE:
        vocab = 8192
        d_model, heads, layers, max_len = 32, 2, 1, 96
        t_prompt, block_size = 24, 8
        n_new, n_jobs, slots = 16, 6, 3
    else:
        vocab = 32000
        d_model, heads, layers, max_len = 512, 8, 8, 2048
        t_prompt = 192
        block_size = int(os.environ.get("MXNET_KV_BLOCK_SIZE", "16"))
        n_new, n_jobs, slots = 64, 8, 4
    dtype = jnp.float32 if backend == "cpu" else jnp.bfloat16
    cfg = tf.TransformerConfig(
        vocab_size=vocab, d_model=d_model, n_heads=heads,
        n_layers=layers, d_ff=4 * d_model, max_len=max_len,
        dtype=dtype)
    params = tf.init_params(cfg, seed=0)
    life = (t_prompt + n_new - 2) // block_size + 1
    num_blocks = slots * life + 2
    jrng = np.random.RandomState(31)
    jobs = []
    for _ in range(n_jobs):
        t_p = int(jrng.randint(max(2, t_prompt // 2), t_prompt))
        jobs.append((list(jrng.randint(1, vocab, t_p)), n_new, 0))
    print("serving journal: backend=%s dtype=%s d_model=%d layers=%d "
          "block=%d pool=%d blocks, %d jobs over %d lanes"
          % (backend, np.dtype(dtype).name, d_model, layers,
             block_size, num_blocks, n_jobs, slots), flush=True)

    def leg(journal):
        srv = ContinuousBatcher(params, cfg, max_batch=slots,
                                paged=True, block_size=block_size,
                                num_blocks=num_blocks,
                                pipeline_depth=2, journal=journal)
        t0 = time.perf_counter()
        results, order = srv.run(list(jobs))
        dt = time.perf_counter() - t0
        toks = [results[rid] for rid in order]
        srv.check_invariants(quiesce=True)
        return toks, srv.dispatch_count, n_jobs * n_new / dt

    leg(False)                         # warm the compile caches
    toks_off, disp_off, rate_off = leg(False)
    with tempfile.TemporaryDirectory() as td:
        toks_on, disp_on, rate_on = leg(td)
        j = RequestJournal(td)
        depth, records = j.depth_bytes, j.lag_records
        live, fin, skipped = j.replay()
        j.close()
    bit_exact = toks_on == toks_off
    dispatch_equal = disp_on == disp_off
    recorded = not live and len(fin) == n_jobs and not skipped
    overhead = (rate_off - rate_on) / rate_off * 100.0
    max_pct = float(os.environ.get(
        "MXNET_SERVING_JOURNAL_AB_MAX_PCT", "25"))
    row = {
        "leg": "journal_ab", "backend": backend,
        "tokens_per_s_off": round(rate_off, 1),
        "tokens_per_s_on": round(rate_on, 1),
        "overhead_pct": round(overhead, 2),
        "max_overhead_pct": max_pct,
        "bit_exact": bit_exact, "dispatch_equal": dispatch_equal,
        "journal_recorded": recorded,
        "journal_depth_bytes": depth, "journal_records": records,
    }
    print(json.dumps(row), flush=True)
    if not (bit_exact and dispatch_equal and recorded):
        print("serving journal leg FAILED its off-path contract "
              "(tokens/dispatches must be bit-identical with the "
              "journal attached)", flush=True)
        sys.exit(1)
    if overhead > max_pct:
        print("serving journal leg FAILED: %.2f%% overhead exceeds "
              "the %.1f%% gate" % (overhead, max_pct), flush=True)
        sys.exit(1)


def main():
    from benchmark.common import fetch_barrier
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf

    if SMOKE:
        d_model, heads, layers, max_len = 32, 2, 1, 96
        t_prompt, n_new, k_draft = 24, 16, 4
        draft_layers, draft_d = 1, 16
    else:
        d_model, heads, layers, max_len = 512, 8, 8, 4096
        t_prompt, n_new, k_draft = 512, 128, 4
        draft_layers, draft_d = 2, 128

    cfg = tf.TransformerConfig(
        vocab_size=32000, d_model=d_model, n_heads=heads,
        n_layers=layers, d_ff=4 * d_model, max_len=max_len,
        dtype=jnp.bfloat16)
    draft_cfg = tf.TransformerConfig(
        vocab_size=32000, d_model=draft_d, n_heads=2,
        n_layers=draft_layers, d_ff=4 * draft_d, max_len=max_len,
        dtype=jnp.bfloat16)
    params = tf.init_params(cfg, seed=0)
    # the draft is a trained-small stand-in; seeding it FROM the target
    # seed keeps proposals non-degenerate enough to measure acceptance
    draft_params = tf.init_params(draft_cfg, seed=0)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(1, 32000, (1, t_prompt)), jnp.int32)

    backend = jax.default_backend()
    print("serving bench: backend=%s d_model=%d layers=%d prompt=%d "
          "n_new=%d" % (backend, d_model, layers, t_prompt, n_new),
          flush=True)

    # --- prefill: one batched MXU pass over the prompt ---
    cache0 = tf.init_cache(cfg, 1)
    pre = tf._jitted_prefill(cfg)

    def run_prefill():
        logits, _ = pre(params, cache0, prompt)
        fetch_barrier(logits)

    rate = _time_tokens(run_prefill, t_prompt)
    print('{"leg": "prefill", "tokens_per_s": %.1f}' % rate, flush=True)

    # --- greedy generate ---
    def run_generate():
        out = tf.generate(params, prompt, n_new, cfg)
        fetch_barrier(out)
        return out

    rate = _time_tokens(run_generate, n_new)
    print('{"leg": "generate", "tokens_per_s": %.1f}' % rate,
          flush=True)

    # --- weight-only int8 ---
    q8 = tf.quantize_weights_int8(params)

    def run_generate_int8():
        out = tf.generate(q8, prompt, n_new, cfg)
        fetch_barrier(out)

    rate = _time_tokens(run_generate_int8, n_new)
    print('{"leg": "generate_int8", "tokens_per_s": %.1f}' % rate,
          flush=True)

    # --- fully-quantized serving: int8 weights + int8 KV cache (the
    # decode loop reads the cache at int8 width, MXU int8 both dots) ---
    import dataclasses
    cfg_kv8 = dataclasses.replace(cfg, kv_cache_int8=True)

    def run_generate_int8kv():
        out = tf.generate(q8, prompt, n_new, cfg_kv8)
        fetch_barrier(out)

    rate = _time_tokens(run_generate_int8kv, n_new)
    print('{"leg": "generate_int8kv", "tokens_per_s": %.1f}' % rate,
          flush=True)

    # --- speculative (greedy-exact; acceptance is data-dependent) ---
    def spec_leg(name, dp, dc):
        def run():
            out, stats = tf.speculative_generate(
                params, dp, prompt, n_new, cfg, dc,
                k_draft=k_draft, return_stats=True)
            np.asarray(out)      # host fetch = full barrier
            return stats

        run()                # warm (compiles draft + verify programs)
        rates, accepts = [], []
        for _ in range(3):
            t0 = time.time()
            stats = run()
            rates.append(n_new / (time.time() - t0))
            accepts.append(np.mean(stats["acceptances"])
                           if stats["acceptances"] else 0.0)
        print('{"leg": "%s", "tokens_per_s": %.1f, '
              '"mean_accepted_per_round": %.2f, "k_draft": %d}'
              % (name, float(np.median(rates)),
                 float(np.mean(accepts)), k_draft), flush=True)

    spec_leg("speculative", draft_params, draft_cfg)
    spec_leg("spec_selfdraft", params, cfg)

    # --- continuous batching: mixed-length queue, slot pool vs
    # sequential generate() ---
    from mxnet_tpu.models.serving import ContinuousBatcher
    n_jobs = 4 if SMOKE else 16
    slots = 2 if SMOKE else 8
    jrng = np.random.RandomState(1)
    jobs = [(list(jrng.randint(1, 32000, int(jrng.randint(
        max(2, t_prompt // 2), t_prompt)))), n_new)
            for _ in range(n_jobs)]
    total_new = sum(n for _, n in jobs)

    # multi-step scheduling: k ragged steps per dispatch. k=1 is the
    # one-token-per-dispatch baseline; the chunked pool amortizes
    # the per-dispatch host sync
    chunk = int(os.environ.get("MXNET_SERVE_CHUNK", "1" if SMOKE
                               else "16"))

    def run_pool(k=1):
        srv = ContinuousBatcher(params, cfg, max_batch=slots,
                                chunk_size=k)
        return srv.run(jobs)

    def run_sequential():
        for prompt, n in jobs:
            out = tf.generate(params, jnp.asarray([prompt], jnp.int32),
                              n, cfg)
            fetch_barrier(out)

    # same warm/median-of-3 protocol as every other leg: the pool-vs-
    # sequential comparison is the headline, so it gets the least-noisy
    # number a shared host can produce
    pool_rate = _time_tokens(run_pool, total_new)
    chunk_rate = (pool_rate if chunk == 1
                  else _time_tokens(lambda: run_pool(chunk), total_new))
    seq_rate = _time_tokens(run_sequential, total_new)
    print('{"leg": "continuous", "tokens_per_s": %.1f, '
          '"chunked_tokens_per_s": %.1f, "chunk": %d, '
          '"sequential_tokens_per_s": %.1f, "slots": %d, "jobs": %d}'
          % (pool_rate, chunk_rate, chunk, seq_rate, slots, n_jobs),
          flush=True)

    # --- mixed arrivals: requests trickle in (one becomes available
    # every other decode step) instead of a pre-filled queue, so the
    # pool runs partially occupied with admissions landing mid-decode —
    # the continuous-batching regime a static-batch server can't serve
    def run_mixed_arrival():
        # chunked scheduling: arrivals land at chunk boundaries (the
        # multi-step-scheduling trade measured here end to end)
        srv = ContinuousBatcher(params, cfg, max_batch=slots,
                                chunk_size=chunk)
        waiting, arr_i, step_i = [], 0, 0
        while arr_i < len(jobs) or waiting or srv.active_count:
            if arr_i < len(jobs) and step_i % 2 == 0:
                # arrival stamp: queue-wait / TTFT cover lane waits
                waiting.append((jobs[arr_i], time.perf_counter_ns()))
                arr_i += 1
            while waiting and srv.has_capacity:
                (p, n), enq = waiting.pop(0)
                srv.admit(p, n, enqueued_ns=enq)
            srv.step()
            step_i += 1

    rate = _time_tokens(run_mixed_arrival, total_new)
    print('{"leg": "continuous_mixed_arrival", "tokens_per_s": %.1f, '
          '"chunk": %d, "slots": %d, "jobs": %d, '
          '"arrival_every_steps": 2}'
          % (rate, chunk, slots, n_jobs), flush=True)

    # --- request-level latency distributions: TTFT/ITL/e2e/queue-wait
    # percentiles from one instrumented run of each pool leg (the
    # timed legs above stay uninstrumented) ---
    reports = [
        _latency_report(lambda: run_pool(chunk), "continuous",
                        chunk=chunk, slots=slots, backend=backend),
        _latency_report(run_mixed_arrival, "continuous_mixed_arrival",
                        chunk=chunk, slots=slots, backend=backend),
    ]
    _write_artifact(_json_arg(), reports)


if __name__ == "__main__":
    _spec = _spec_k_arg()
    if _spec is not None:
        spec_ab(_spec)
    elif "--paged" in sys.argv[1:]:
        paged_ab()
    elif "--megakernel" in sys.argv[1:]:
        megakernel_ab()
    elif "--overload" in sys.argv[1:]:
        overload_ab()
    elif "--mem-pressure" in sys.argv[1:]:
        mem_pressure_ab()
    elif "--journal" in sys.argv[1:]:
        journal_ab()
    else:
        main()
