"""Per-operator attribution CLI: the "where did the bytes go" command.

Prints the per-scope top-K table (instruction count / GFLOP / HBM MB /
arithmetic intensity / roofline bound / time share / MFU share) for
every compiled executable the attribution layer has registered
(docs/OBSERVABILITY.md "Per-operator attribution"), and can persist the
underlying summary as JSON — the artifact ``tools/obs_regression.py``
diffs against a committed baseline.

Three ways to get a summary in front of it:

    # 1. built-in deterministic workload (the CI smoke: a two-block
    #    conv+dense Gluon model trained for 2 steps on the attached
    #    backend; explicit prefixes, so scope names never depend on
    #    process-global naming counters)
    MXNET_OBS=1 JAX_PLATFORMS=cpu python tools/obs_ops.py
    python tools/obs_ops.py --json /tmp/ops.json     # + write summary

    # 2. a summary JSON some other run saved (--json above, or any
    #    caller of observability.ops_summary())
    python tools/obs_ops.py --summary /tmp/ops.json

    # 3. from inside a training script: run your steps with MXNET_OBS=1
    #    and call observability.format_ops_table() / ops_summary() —
    #    profiler.dumps(aggregate=True) appends the same table.

The flops/bytes columns are shape-derived estimates from the optimized
HLO (observability/hlo.py docstring spells out the accounting model);
``--topk`` / MXNET_OBS_OPS_TOPK controls table depth; the roofline is
the chip's published peaks by device_kind (mxnet_tpu/chip.py — in this
CPU-pinned tool the modelled v5e).
"""

import argparse
import json
import os
import sys

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

os.environ.setdefault("MXNET_OBS", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# the smoke workload's shapes: conv dominates flops (acceptance: the
# top-K table must rank the conv block first), dense dominates params
BATCH, CHANNELS, IMG, CONV_FILTERS, DENSE_UNITS = 4, 3, 32, 16, 8


def build_workload_net():
    """The two-block conv+dense model with DETERMINISTIC scope names
    (explicit prefixes bypass the process-global naming counters, so
    baseline scope keys survive test ordering and reruns)."""
    from mxnet_tpu.gluon import nn
    net = nn.HybridSequential(prefix="obsops_")
    with net.name_scope():
        net.add(nn.Conv2D(CONV_FILTERS, kernel_size=3, padding=1,
                          activation="relu", prefix="conv_"))
        net.add(nn.Flatten(prefix="flatten_"))
        net.add(nn.Dense(DENSE_UNITS, prefix="dense_"))
    return net


def run_workload(steps=2):
    """Train the smoke model for ``steps`` and return the attribution
    summary. Requires telemetry on (MXNET_OBS=1) at call time — scope
    names only reach the HLO if the program is traced with it on."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.observability import attribution

    net = build_workload_net()
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    loss_fn = gluon.loss.L2Loss(prefix="obsops_loss_")
    x = mx.nd.random.uniform(shape=(BATCH, CHANNELS, IMG, IMG))
    y = mx.nd.random.uniform(shape=(BATCH, DENSE_UNITS))
    for _ in range(steps):
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(BATCH)
    return attribution.summary()


def run_kernel_workload():
    """Deterministic paged decode + spec-verify serving run with the
    Pallas megakernel FORCED on (interpret mode on CPU — the same
    kernel code the chip compiles), returning the attribution summary
    for just this workload. The ``paged_decode_kernel`` /
    ``paged_verify_kernel`` scope rows are the PR 16 numbers
    ``tools/obs_regression.py --kernels`` guards against
    ``ci/obs_baseline.json``."""
    import numpy as np
    import jax.numpy as jnp
    from mxnet_tpu.models import transformer as tf
    from mxnet_tpu.models.serving import ContinuousBatcher
    from mxnet_tpu.observability import attribution

    prev = os.environ.get("MXNET_PAGED_DECODE_PALLAS")
    os.environ["MXNET_PAGED_DECODE_PALLAS"] = "1"
    attribution.reset()     # only THIS workload's programs/scopes
    try:
        cfg = tf.TransformerConfig(vocab_size=97, d_model=16,
                                   n_heads=2, n_layers=1, d_ff=32,
                                   max_len=48, dtype=jnp.float32)
        params = tf.init_params(cfg, seed=0)
        rng = np.random.RandomState(0)
        jobs = [(list(rng.randint(1, 97, 5)), 6) for _ in range(3)]
        # spec run -> paged_verify_kernel; plain paged run ->
        # paged_decode_kernel (the spec path replaces the decode
        # pipeline, so both dispatches are needed for both scopes)
        srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                block_size=8, spec_k=2)
        results, order = srv.run(jobs)
        assert len(results) == len(jobs)
        srv = ContinuousBatcher(params, cfg, max_batch=2, paged=True,
                                block_size=8)
        results, order = srv.run(jobs)
        assert len(results) == len(jobs)
        return attribution.summary()
    finally:
        if prev is None:
            os.environ.pop("MXNET_PAGED_DECODE_PALLAS", None)
        else:
            os.environ["MXNET_PAGED_DECODE_PALLAS"] = prev


def main(argv=None):
    print("[obs_ops] CPU structure check by design: JAX_PLATFORMS=%s "
          "(pinned by this script when unset); counts, bytes and "
          "orderings only — no time or rate below is a device number"
          % os.environ["JAX_PLATFORMS"], flush=True)
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--summary", metavar="JSON", default=None,
                   help="print the table from a saved summary instead "
                        "of running the built-in workload")
    p.add_argument("--json", metavar="OUT", default=None,
                   help="write the summary JSON (the obs_regression "
                        "artifact) after printing the table")
    p.add_argument("--topk", type=int, default=None,
                   help="table depth (default MXNET_OBS_OPS_TOPK=10)")
    p.add_argument("--profile-dir", default=None,
                   help="performance archive to calibrate against "
                        "(default MXNET_OBS_PROFILE_DIR); adds "
                        "predicted_ms/measured_ms/calib_err per scope "
                        "to the table and the --json artifact")
    p.add_argument("--max-calib-err", type=float, default=None,
                   metavar="FRAC",
                   help="exit 3 when any archived scope's calibration "
                        "error exceeds FRAC (the autotuner pre-flight "
                        "gate; also fails when the archive is empty)")
    args = p.parse_args(argv)

    if args.summary:
        with open(args.summary) as f:
            doc = json.load(f)
        summ = doc.get("summary", doc)   # bare or baseline-wrapped
    else:
        summ = run_workload()

    from mxnet_tpu.observability import attribution
    lines = attribution.format_ops_table(summ, k=args.topk)
    if not lines:
        print("[obs_ops] no compiled program registered — is MXNET_OBS "
              "set, and did the workload trace a jit?")
        return 1
    print("\n".join(lines).lstrip("\n"))

    # cost-model calibration against the performance archive (ISSUE
    # 18): predicted vs measured per scope, worst-calibrated named
    calib_rows = []
    pdir = args.profile_dir or os.environ.get("MXNET_OBS_PROFILE_DIR")
    if pdir:
        from mxnet_tpu.observability import costmodel
        try:
            calib_rows = costmodel.calibration_report(dirpath=pdir)
        except Exception:
            calib_rows = []
        table = costmodel.format_calibration_table(dirpath=pdir)
        if table:
            print("\n".join(table))

    if args.json:
        doc = {"summary": summ}
        if calib_rows:
            doc["calibration"] = {
                r["scope"]: {"predicted_ms": r["predicted_ms"],
                             "measured_ms": r["measured_ms"],
                             "calib_err": r["calib_err"]}
                for r in calib_rows}
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        print("\n[obs_ops] summary -> %s" % args.json)

    if args.max_calib_err is not None:
        if not calib_rows:
            print("[obs_ops] FAIL: --max-calib-err set but the "
                  "performance archive holds no calibrated scopes "
                  "(is MXNET_OBS_PROFILE_DIR populated?)")
            return 3
        bad = [r for r in calib_rows
               if r["calib_err"] > args.max_calib_err]
        if bad:
            print("[obs_ops] FAIL: %d scope(s) past calibration "
                  "error %.0f%%: %s"
                  % (len(bad), 100 * args.max_calib_err,
                     ", ".join("%s (%.0f%%)"
                               % (r["scope"], 100 * r["calib_err"])
                               for r in bad)))
            return 3
        print("[obs_ops] calibration within %.0f%% across %d scope(s)"
              % (100 * args.max_calib_err, len(calib_rows)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
