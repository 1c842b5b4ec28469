"""KVStore bandwidth benchmark.

Port of tools/bandwidth/measure.py (named in BASELINE.md as a north-star
deliverable): pushes ResNet-sized gradient arrays through a kvstore and
reports aggregate all-reduce bandwidth.

TPU-native: the wire is the ICI/DCN mesh via XLA collectives rather than
PCIe/NCCL/ps-lite, so "bandwidth" here is the end-to-end push+pull rate
of the dist_tpu_sync collective path. Reports both algorithm bandwidth
(payload/time) and bus bandwidth (x 2(n-1)/n, the nccl-tests convention)
so numbers compare against the reference tool's GB/s output.

Usage:
    python tools/bandwidth.py [--kv-store dist_tpu_sync] [--num-batches 10]
        [--test-results 1] [--gc-type none|2bit]
"""

import argparse
import logging
import os
import sys
import time

import numpy as np

# runnable from a checkout without installation (as the reference tool is)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


# ResNet-152-ish parameter shapes (what the reference tool measures with
# --network resnet --num-layers 152): a long tail of small arrays plus a
# few large ones. Sizes in fp32 elements.
RESNET_LIKE_SHAPES = [
    (64, 3, 7, 7), (256, 64, 1, 1), (64, 64, 3, 3), (512, 256, 1, 1),
    (128, 128, 3, 3), (1024, 512, 1, 1), (256, 256, 3, 3),
    (2048, 1024, 1, 1), (512, 512, 3, 3), (1000, 2048),
] * 4


def parse_args():
    p = argparse.ArgumentParser(
        description="benchmark kvstore all-reduce bandwidth")
    p.add_argument("--kv-store", type=str, default="dist_tpu_sync")
    p.add_argument("--num-batches", type=int, default=10)
    p.add_argument("--disp-batches", type=int, default=1)
    p.add_argument("--test-results", type=int, default=1)
    p.add_argument("--gc-type", type=str, default="none")
    p.add_argument("--optimizer", type=str, default="None")
    p.add_argument("--num-workers", type=int, default=1,
                   help="cross-PROCESS mode: relaunch this tool under "
                        "tools/launch.py with N local worker processes "
                        "so the all-reduce crosses the multi-process "
                        "wire path (reference: measure.py under a "
                        "dist launcher)")
    return p.parse_args()


def run(kv_store="dist_tpu_sync", num_batches=10, disp_batches=1,
        test_results=1, gc_type="none", optimizer="None"):
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import kvstore as kvs

    rank = 0
    if os.environ.get("MXNET_TPU_NUM_PROC"):
        # launched under tools/launch.py: join the process group first
        # so the kvstore collective spans every worker process
        from mxnet_tpu import parallel
        parallel.init_distributed()
        rank = int(os.environ.get("MXNET_TPU_PROC_ID", "0"))

    kv = kvs.create(kv_store)
    if gc_type != "none":
        kv.set_gradient_compression({"type": gc_type})
    if optimizer != "None":
        kv.set_optimizer(mx.optimizer.create(optimizer))

    n_workers = jax.device_count()          # global collective width
    n_local = jax.local_device_count()      # this process contributes
    shapes = RESNET_LIKE_SHAPES
    keys = list(range(len(shapes)))
    total_bytes = sum(int(np.prod(s)) for s in shapes) * 4

    # per-RANK seeds: each process contributes distinct gradients, so a
    # collective that fails to cross the process boundary (e.g. scales
    # the local sum) cannot pass the verification below
    n_proc = int(os.environ.get("MXNET_TPU_NUM_PROC", "1"))

    def rank_draws(r):
        rr = np.random.RandomState(1000 + r)
        return [[rr.uniform(-1, 1, s).astype(np.float32)
                 for _ in range(n_local)] for s in shapes]

    mine = rank_draws(rank)
    grads = [[mx.nd.array(a) for a in row] for row in mine]
    all_rows = [rank_draws(r) for r in range(n_proc)]
    expected = [sum(a for row in all_rows for a in row[i])
                for i in range(len(shapes))]
    outs = [mx.nd.empty(s) for s in shapes]

    for k, s in zip(keys, shapes):
        kv.init(k, mx.nd.zeros(s))

    # warmup (compile the collective programs)
    kv.push(keys, grads)
    kv.pull(keys, out=outs)
    for o in outs:
        o.wait_to_read()

    times = []
    for b in range(num_batches):
        t0 = time.time()
        kv.push(keys, grads)
        kv.pull(keys, out=outs)
        for o in outs:
            o.wait_to_read()
        dt = time.time() - t0
        times.append(dt)
        if rank == 0 and (b + 1) % disp_batches == 0:
            algbw = total_bytes / dt / 1e9
            busbw = algbw * 2 * (n_workers - 1) / max(n_workers, 1)
            logging.info("batch %3d: %.3f s, algbw %6.2f GB/s, "
                         "busbw %6.2f GB/s", b, dt, algbw, busbw)

    if test_results and optimizer == "None" and gc_type == "none":
        # atol covers fp32 reassociation on near-zero sums of many
        # distinct per-rank terms
        for o, e in zip(outs, expected):
            np.testing.assert_allclose(o.asnumpy(), e, rtol=1e-4,
                                       atol=1e-5)
        if rank == 0:
            logging.info("results verified: pulled aggregate == exact "
                         "sum over %d workers", n_workers)

    best = min(times)
    algbw = total_bytes / best / 1e9
    # bus bandwidth degenerates to 0 at n=1; report the copy rate then
    busbw = algbw if n_workers == 1 else \
        algbw * 2 * (n_workers - 1) / n_workers
    if rank == 0:
        n_proc = int(os.environ.get("MXNET_TPU_NUM_PROC", "1"))
        print('{"metric": "kvstore_allreduce_busbw", "value": %.3f, '
              '"unit": "GB/s", "payload_mb": %.1f, "workers": %d, '
              '"processes": %d, "kv_store": "%s"}'
              % (busbw, total_bytes / 1e6, n_workers, n_proc, kv_store))
    return busbw


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(message)s")
    args = parse_args()
    if args.num_workers > 1 and os.environ.get("MXNET_TPU_NUM_PROC"):
        n_env = os.environ["MXNET_TPU_NUM_PROC"]
        if n_env != str(args.num_workers):
            raise SystemExit(
                "--num-workers %d conflicts with MXNET_TPU_NUM_PROC=%s "
                "already in the environment (a stale export from a "
                "previous launch?); unset it or match the values"
                % (args.num_workers, n_env))
    if args.num_workers > 1 and not os.environ.get("MXNET_TPU_NUM_PROC"):
        # relaunch ourselves as N local worker processes (the reference
        # runs measure.py under its dist launcher the same way)
        import subprocess
        root = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..")
        argv = [sys.executable, os.path.join(root, "tools", "launch.py"),
                "-n", str(args.num_workers), "--launcher", "local",
                sys.executable, os.path.abspath(__file__),
                "--kv-store", args.kv_store,
                "--num-batches", str(args.num_batches),
                "--disp-batches", str(args.disp_batches),
                "--test-results", str(args.test_results),
                "--gc-type", args.gc_type,
                "--optimizer", args.optimizer]
        sys.exit(subprocess.call(argv, cwd=root))
    run(kv_store=args.kv_store, num_batches=args.num_batches,
        disp_batches=args.disp_batches, test_results=args.test_results,
        gc_type=args.gc_type, optimizer=args.optimizer)
