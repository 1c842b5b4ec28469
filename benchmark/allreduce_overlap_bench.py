"""Bucketed vs per-key gradient all-reduce microbench.

Extends the kvstore busbw leg (tools/bandwidth.py; 52.4 GB/s in
PERF.md "Chip numbers of 2026-08-01", `bandwidth`) with the
dispatch-count story behind the gradient fusion layer (parallel/fusion.py): a per-key push pays one collective
dispatch per parameter, a bucketed push pays one per ~25 MB bucket
lane, and inside a jitted step the bucketed form lets XLA overlap each
bucket's collective with remaining backward compute.

Runs anywhere: on a TPU-less host the mesh is virtual
(``--xla_force_host_platform_device_count``, set below before jax
loads). Two parameter-size distributions are measured:

* ``resnet50`` — the real ResNet-50 v1 parameter list (161 arrays,
  ~25.5 M params: a few fat convs + a long tail of BN vectors);
* ``lm`` — a transformer LM parameter list (d=256, 16 layers + tied
  embedding: many small LN/bias vectors per layer), the distribution
  where per-key dispatch overhead dominates small-tensor busbw.

Reported per distribution: collective dispatch counts (from
``kv.dispatch_stats``), wall time, algorithm and bus bandwidth
(nccl-tests convention, x 2(N-1)/N). ``--shard-update`` adds the
reduce-scatter -> sharded-update -> all-gather leg and reports the
per-replica optimizer-state bytes cut ((N-1)/N, PAPERS.md).

Usage:
    python benchmark/allreduce_overlap_bench.py [--devices 8]
        [--dist lm resnet50] [--iters 5] [--shard-update]
        [--inject-straggler RANK:MS]

``--inject-straggler 1:50`` feeds the measured bucketed all-reduce
time, with rank 1 slowed by 50 ms, through the cross-rank straggler
detector (observability/dist.py) and prints the skew table + warning —
a reproducible demo of what a real multi-host straggler report looks
like.
"""

import argparse
import json
import os
import sys
import time

# the virtual mesh must exist before jax initializes
_FLAG = "--xla_force_host_platform_device_count"


def _pre_jax_setup(n):
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        os.environ["XLA_FLAGS"] = ("%s %s=%d" % (flags, _FLAG, n)).strip()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


# ------------------------------------------------ size distributions --

def resnet50_shapes():
    """The ResNet-50 v1 parameter list: conv/fc weights + BN vectors."""
    shapes = [(64, 3, 7, 7), (64,), (64,)]
    in_c = 64
    for width, blocks in ((256, 3), (512, 4), (1024, 6), (2048, 3)):
        mid = width // 4
        for b in range(blocks):
            shapes += [(mid, in_c, 1, 1), (mid,), (mid,),
                       (mid, mid, 3, 3), (mid,), (mid,),
                       (width, mid, 1, 1), (width,), (width,)]
            if b == 0:
                shapes += [(width, in_c, 1, 1), (width,), (width,)]
            in_c = width
    shapes += [(1000, 2048), (1000,)]
    return shapes


def lm_shapes(d=256, layers=16, vocab=8192, ffn_mult=4):
    """Transformer-LM parameter list: per layer 4 attention mats, 2 MLP
    mats, 2 LayerNorms (gamma+beta) and biases — a long tail of
    d-sized vectors around a few d x 4d mats."""
    shapes = [(vocab, d)]
    for _ in range(layers):
        shapes += [(d,), (d,)]                       # ln1
        shapes += [(d, d), (d,)] * 4                 # q,k,v,out + biases
        shapes += [(d,), (d,)]                       # ln2
        shapes += [(d, ffn_mult * d), (ffn_mult * d,),
                   (ffn_mult * d, d), (d,)]          # mlp
    shapes += [(d,), (d,)]                           # final ln
    return shapes


DISTRIBUTIONS = {"resnet50": resnet50_shapes, "lm": lm_shapes}


# -------------------------------------------------------------- bench --

def _busbw(total_bytes, dt, n):
    alg = total_bytes / dt / 1e9
    return alg, (alg if n <= 1 else alg * 2 * (n - 1) / n)


def bench_dist(name, shapes, n_workers, iters, shard_update):
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kvs
    from mxnet_tpu.parallel import fusion
    from benchmark.common import fetch_barrier

    rng = np.random.RandomState(42)
    keys = list(range(len(shapes)))
    grads = [[mx.nd.array(rng.uniform(-1, 1, s).astype(np.float32))
              for _ in range(n_workers)] for s in shapes]
    outs = [mx.nd.empty(s) for s in shapes]
    total_bytes = sum(int(np.prod(s)) for s in shapes) * 4
    small_bytes = sum(int(np.prod(s)) for s in shapes
                      if int(np.prod(s)) < (1 << 16)) * 4
    results = []

    def timed(tag, fn, kv):
        fn()                                   # warmup / compile
        for o in outs:
            o.wait_to_read()
        kv.reset_dispatch_stats()
        t0 = time.time()
        for _ in range(iters):
            fn()
        fetch_barrier(outs[-1]._data)
        for o in outs:
            o.wait_to_read()
        dt = (time.time() - t0) / iters
        stats = dict(kv.dispatch_stats)
        stats["collectives"] //= iters
        stats["keys"] //= iters
        stats["buckets"] //= iters
        alg, bus = _busbw(total_bytes, dt, n_workers)
        row = {"metric": "allreduce_%s_%s" % (name, tag),
               "dispatches": stats["collectives"], "sec_per_iter": round(dt, 4),
               "algbw_gb_s": round(alg, 3), "busbw_gb_s": round(bus, 3),
               "keys": stats["keys"], "buckets": stats["buckets"],
               "payload_mb": round(total_bytes / 1e6, 1),
               "small_tensor_mb": round(small_bytes / 1e6, 2),
               "workers": n_workers}
        print(json.dumps(row))
        from benchmark.common import record_bench_profile
        record_bench_profile(
            "allreduce_%s_%s" % (name, tag), value=row["busbw_gb_s"],
            unit="GB/s", dispatches=row["dispatches"],
            sec_per_iter=row["sec_per_iter"], workers=n_workers)
        return row

    # --- per-key: one collective dispatch per parameter ---------------
    kv = kvs.create("dist_tpu_sync")
    for k, s in zip(keys, shapes):
        kv.init(k, mx.nd.zeros(s))
    per_key = timed("per_key", lambda: (kv.push(keys, grads),
                                        kv.pull(keys, out=outs)), kv)

    # --- bucketed: one dispatch per ~25 MB bucket lane ----------------
    kv2 = kvs.create("dist_tpu_sync")
    for k, s in zip(keys, shapes):
        kv2.init(k, mx.nd.zeros(s))
    order = keys[::-1]                          # priority order
    g_rev = grads[::-1]
    o_rev = outs[::-1]
    bucketed = timed(
        "bucketed",
        lambda: kv2.pushpull_fused(order, g_rev, out=o_rev), kv2)

    ratio = per_key["dispatches"] / max(bucketed["dispatches"], 1)
    speedup = per_key["sec_per_iter"] / max(bucketed["sec_per_iter"], 1e-9)
    print(json.dumps({
        "metric": "allreduce_%s_summary" % name,
        "dispatch_reduction_x": round(ratio, 1),
        "busbw_gain_x": round(speedup, 2),
        "bucket_bytes": fusion.bucket_bytes()}))
    results += [per_key, bucketed]

    # --- small tensors only: the dispatch-bound regime the fusion
    # exists for (the long tail of LN/bias/BN vectors) --------------
    small_idx = [i for i, s in enumerate(shapes)
                 if int(np.prod(s)) < (1 << 16)]
    if len(small_idx) >= 2:
        s_shapes = [shapes[i] for i in small_idx]
        s_bytes = sum(int(np.prod(s)) for s in s_shapes) * 4
        kv4 = kvs.create("dist_tpu_sync")
        for i in small_idx:
            kv4.init(keys[i], mx.nd.zeros(shapes[i]))
        s_keys = [keys[i] for i in small_idx]
        s_grads = [grads[i] for i in small_idx]
        s_outs = [outs[i] for i in small_idx]

        def leg(tag, fn):
            fn()
            for o in s_outs:
                o.wait_to_read()
            kv4.reset_dispatch_stats()
            t0 = time.time()
            for _ in range(iters):
                fn()
            fetch_barrier(s_outs[-1]._data)
            for o in s_outs:
                o.wait_to_read()
            dt = (time.time() - t0) / iters
            alg, bus = _busbw(s_bytes, dt, n_workers)
            row = {"metric": "allreduce_%s_small_%s" % (name, tag),
                   "dispatches": kv4.dispatch_stats["collectives"] // iters,
                   "sec_per_iter": round(dt, 4),
                   "busbw_gb_s": round(bus, 4),
                   "payload_mb": round(s_bytes / 1e6, 2),
                   "n_tensors": len(s_keys), "workers": n_workers}
            print(json.dumps(row))
            return row

        sp = leg("per_key", lambda: (kv4.push(s_keys, s_grads),
                                     kv4.pull(s_keys, out=s_outs)))
        sb = leg("bucketed",
                 lambda: kv4.pushpull_fused(s_keys[::-1], s_grads[::-1],
                                            out=s_outs[::-1]))
        print(json.dumps({
            "metric": "allreduce_%s_small_summary" % name,
            "dispatch_reduction_x": round(
                sp["dispatches"] / max(sb["dispatches"], 1), 1),
            "busbw_gain_x": round(
                sp["sec_per_iter"] / max(sb["sec_per_iter"], 1e-9), 2)}))

    # --- sharded weight update (reduce-scatter -> update -> gather) ---
    if shard_update:
        os.environ["MXNET_KVSTORE_SHARD_UPDATE"] = "1"
        try:
            kv3 = kvs.create("dist_tpu_sync")
            for k, s in zip(keys, shapes):
                kv3.init(k, mx.nd.zeros(s))
            kv3.set_optimizer(mx.optimizer.create(
                "sgd", learning_rate=0.01, momentum=0.9))
            kv3.pushpull_fused(order, g_rev)    # builds the shard slots
            kv3.reset_dispatch_stats()
            t0 = time.time()
            for _ in range(iters):
                kv3.pushpull_fused(order, g_rev)
            fetch_barrier(kv3._store[str(keys[0])]._data)
            dt = (time.time() - t0) / iters
            state_total = sum(s.state_bytes_total
                              for s in kv3._shard_slots.values())
            state_replica = sum(s.state_bytes_per_replica
                                for s in kv3._shard_slots.values())
            alg, bus = _busbw(total_bytes, dt, n_workers)
            print(json.dumps({
                "metric": "allreduce_%s_shard_update" % name,
                "dispatches": kv3.dispatch_stats["collectives"] // iters,
                "sec_per_iter": round(dt, 4),
                "busbw_gb_s": round(bus, 3),
                "opt_state_bytes_replicated": state_total,
                "opt_state_bytes_per_replica": state_replica,
                "state_cut": round(1 - state_replica / state_total, 4),
                "workers": n_workers}))
        finally:
            del os.environ["MXNET_KVSTORE_SHARD_UPDATE"]
    return results


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--devices", type=int, default=8,
                   help="virtual CPU mesh width (ignored on real TPU)")
    p.add_argument("--dist", nargs="+", default=["lm", "resnet50"],
                   choices=sorted(DISTRIBUTIONS))
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--shard-update", action="store_true",
                   help="also run the sharded-weight-update leg")
    p.add_argument("--obs", action="store_true",
                   help="run with MXNET_OBS=1 and print the aggregate-"
                        "stats phase table after the legs")
    p.add_argument("--obs-ops", action="store_true",
                   help="also print the per-operator attribution table "
                        "(per-scope flops/bytes of the registered "
                        "bucketed-reduce programs)")
    p.add_argument("--inject-straggler", metavar="RANK:MS", default=None,
                   help="demo the cross-rank straggler detector: build "
                        "a per-rank phase table from the measured "
                        "bucketed all-reduce time, slow RANK down by "
                        "MS ms, and print the skew table + warning "
                        "(docs/OBSERVABILITY.md)")
    args = p.parse_args()
    if args.obs or args.obs_ops:
        os.environ["MXNET_OBS"] = "1"
    _pre_jax_setup(args.devices)

    import jax
    from mxnet_tpu.chip import use_compile_cache
    use_compile_cache()
    n = jax.device_count()
    print("[allreduce_overlap_bench] CPU structure check by design: "
          "JAX_PLATFORMS=%s (pinned by this script when unset); dispatch "
          "counts and bytes are the result — the *_gb_s columns are XLA's "
          "%s backend on a virtual mesh, never a device number"
          % (os.environ["JAX_PLATFORMS"], jax.default_backend()),
          flush=True)
    print(json.dumps({"metric": "allreduce_bench_mesh", "devices": n,
                      "backend": jax.default_backend()}))
    rows = []
    for name in args.dist:
        rows += bench_dist(name, DISTRIBUTIONS[name](), n, args.iters,
                           args.shard_update)
    if args.inject_straggler:
        straggler_demo(args.inject_straggler, n, rows)
    # --obs-ops enables MXNET_OBS, and the aggregate table appends the
    # per-operator attribution section itself — one print covers both
    from benchmark.common import print_obs_table
    print_obs_table()


def straggler_demo(spec, n_workers, rows):
    """Reproducible straggler-detector demo: a NOMINAL per-rank phase
    table (fixed millisecond baselines, so the verdict is the same on
    any host) with the injected rank slowed by +MS on allreduce, run
    through the same detect/format path the cross-rank skew exchange
    uses — the table and warning here look exactly like a real
    multi-host straggler report. The measured bucketed time rides
    along in the JSON row for context."""
    import warnings
    from mxnet_tpu.observability import dist as obs_dist

    try:
        rank_s, ms_s = spec.split(":")
        rank, ms = int(rank_s), float(ms_s)
    except ValueError:
        raise SystemExit("--inject-straggler expects RANK:MS, got %r"
                         % spec)
    if not 0 <= rank < n_workers:
        raise SystemExit("--inject-straggler rank %d outside 0..%d"
                         % (rank, n_workers - 1))
    bucketed = [r for r in rows if r["metric"].endswith("_bucketed")]
    measured_ms = bucketed[-1]["sec_per_iter"] * 1000.0 if bucketed \
        else None
    base_ms = 5.0                       # nominal allreduce baseline
    table = {"forward": [2.0 * base_ms] * n_workers,
             "backward": [4.0 * base_ms] * n_workers,
             "allreduce": [base_ms] * n_workers,
             "update": [0.5 * base_ms] * n_workers}
    table["allreduce"][rank] += ms
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        summary = obs_dist.detect_stragglers(table)
        for s in summary["stragglers"]:
            warnings.warn(
                "mxnet_tpu.observability: cross-rank straggler — rank "
                "%d %s %.2f ms vs across-rank median %.2f ms (x%.1f)"
                % (s["rank"], s["phase"], s["ms"], s["median_ms"],
                   s["ratio"]), RuntimeWarning)
    print("\n".join(obs_dist.format_skew_table(summary)))
    for w in caught:
        print("WARNING: %s" % w.message)
    print(json.dumps({
        "metric": "straggler_demo", "injected_rank": rank,
        "injected_ms": ms, "base_allreduce_ms": base_ms,
        "measured_bucketed_ms": None if measured_ms is None
        else round(measured_ms, 3),
        "flagged": [dict(s, ms=round(s["ms"], 3),
                         median_ms=round(s["median_ms"], 3),
                         ratio=round(s["ratio"], 2))
                    for s in summary["stragglers"]]}))


if __name__ == "__main__":
    main()
