"""Headline benchmark: ResNet-50 training throughput (img/s).

Baseline row (BASELINE.md): ResNet-50 training, fp32, bs=128 on 1x V100
= 363.69 img/s (reference docs/faq/perf.md:241). Here the single TPU
chip runs the TPU-idiomatic equivalent: bf16 compute with fp32 master
weights (AMP), whole train step as ONE donated-buffer XLA computation.

Prints one JSON line: {"metric", "value", "unit", "vs_baseline",
"platform", "device_kind", "device_count"}. Needs an accelerator: with
none it exits non-zero with one line on stderr and prints no row, and a
non-finite loss is a failure, not a warning.

``--real-data`` (or MXNET_BENCH_REAL_DATA=1) measures the END-TO-END
leg instead: the same train step fed by the real ``ImageRecordIter``
pipeline (RecordIO file on disk, threaded-decode/crop/mirror path —
the reference's iter_image_recordio_2.cc role) rather than resident
synthetic tensors. The JSON row carries both the fed rate and the
same-session synthetic step rate, so the host-input-bound gap is the
measurement, not a footnote — on a 1-core build host the feed is
expected to bind long before the chip does (VERDICT r5 item 6).
"""

import json
import os
import sys
import time

import numpy as np

BASELINE_IMG_S = 363.69
# Throughput is flat in batch (HBM-bound step, PERF.md: 1815 img/s at
# bs=128 vs 1799 at bs=256 pre-BN-fix), so default to the batch that
# compiles fastest. MXNET_BENCH_BATCH overrides (bs=256 measured 2136
# img/s post-BN-fix, PERF.md "Chip numbers of 2026-08-01").
BATCH = int(os.environ.get("MXNET_BENCH_BATCH", "128"))


def build_train_step(batch, image_size=224, classes=1000, lr=0.1):
    import os
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.utils import functionalize_block

    net = vision.resnet50_v1(classes=classes)
    net.initialize(mx.init.Xavier())
    x0 = mx.nd.zeros((batch, 3, image_size, image_size))
    graph_fn, data_names, args, aux = functionalize_block(
        net, x0, is_train=True)
    key = jax.random.PRNGKey(0)
    # MXNET_FOLD_CAST: the reference's multi-precision-SGD layout
    # (mp_sgd_update) — the graph consumes PERSISTENT bf16 weights and
    # the fp32->bf16 cast happens once inside the optimizer update,
    # instead of re-casting every master weight at the top of each
    # forward (and transposing that cast in backward). Numerically
    # identical trajectories (tests). Default ON since a chip A/B of
    # 2152.3 vs 2097.1 img/s (+2.6%, outside the headline's 5-repeat
    # spread) — PERF.md "Chip numbers of 2026-08-01", a claim until
    # re-measured.
    fold_cast = os.environ.get("MXNET_FOLD_CAST", "1").lower() in (
        "1", "true")

    def loss_of(net_args, aux, x, y):
        # AMP: bf16 compute, fp32 master weights / loss
        if not fold_cast:
            net_args = jax.tree.map(lambda a: a.astype(jnp.bfloat16),
                                    net_args)
        inputs = dict(net_args)
        inputs[data_names[0]] = x.astype(jnp.bfloat16)
        aux_bf16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16), aux)
        outs, aux_up = graph_fn(inputs, aux_bf16, key)
        logits = outs[0].astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
        aux_up = jax.tree.map(lambda a: a.astype(jnp.float32), aux_up)
        return nll.mean(), aux_up

    if fold_cast:
        def step(state, mom, aux, x, y):
            args_f32, args_bf16 = state
            (loss, aux_up), grads = jax.value_and_grad(
                loss_of, has_aux=True)(args_bf16, aux, x, y)
            mom = jax.tree.map(
                lambda m, g: 0.9 * m + g.astype(jnp.float32), mom, grads)
            args_f32 = jax.tree.map(lambda p, m: p - lr * m, args_f32,
                                    mom)
            args_bf16 = jax.tree.map(
                lambda p: p.astype(jnp.bfloat16), args_f32)
            return (args_f32, args_bf16), mom, aux_up, loss

        jitted = jax.jit(step, donate_argnums=(0, 1, 2))
        state = (args, jax.tree.map(
            lambda a: jnp.asarray(a).astype(jnp.bfloat16), args))
        mom = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), args)
        return jitted, state, mom, aux

    def step(args, mom, aux, x, y):
        (loss, aux_up), grads = jax.value_and_grad(
            loss_of, has_aux=True)(args, aux, x, y)
        mom = jax.tree.map(
            lambda m, g: 0.9 * m + g.astype(jnp.float32), mom, grads)
        args = jax.tree.map(lambda p, m: p - lr * m, args, mom)
        return args, mom, aux_up, loss

    jitted = jax.jit(step, donate_argnums=(0, 1, 2))
    mom = jax.tree.map(lambda p: np.zeros(p.shape, np.float32), args)
    return jitted, args, mom, aux


def _make_record_dataset(n_records, size, seed=0):
    """Write a synthetic RecordIO image dataset (npy-payload records —
    the decode path ImageRecordIter exercises without a PIL/cv2
    dependency) and return (rec_path, idx_path). Images are generated
    a margin larger than the crop target so rand_crop does real
    work."""
    import tempfile
    from mxnet_tpu import recordio
    d = tempfile.mkdtemp(prefix="bench_realdata_")
    rec = os.path.join(d, "train.rec")
    idx = os.path.join(d, "train.idx")
    rng = np.random.RandomState(seed)
    w = recordio.MXIndexedRecordIO(idx, rec, "w")
    edge = size + 32
    for i in range(n_records):
        img = rng.randint(0, 255, (edge, edge, 3)).astype(np.uint8)
        w.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(rng.randint(0, 1000)), i, 0),
            img, img_fmt=".npy"))
    w.close()
    return rec, idx


def real_data_main():
    """--real-data: train through the real input pipeline and report
    fed img/s next to the same-session synthetic step rate."""
    import jax.numpy as jnp
    dev = _start()
    batch, size, steps = BATCH, 224, 20
    n_records = max(batch * 4, 64)

    from mxnet_tpu import io as mx_io
    rec, idx = _make_record_dataset(n_records, size)
    it = mx_io.ImageRecordIter(
        path_imgrec=rec, path_imgidx=idx, data_shape=(3, size, size),
        batch_size=batch, shuffle=True, rand_crop=True,
        rand_mirror=True)

    step, args, mom, aux = build_train_step(batch, size)

    def batches():
        while True:
            try:
                yield next(it)
            except StopIteration:
                it.reset()

    feed = batches()

    def fed_step(args, mom, aux):
        b = next(feed)
        x = jnp.asarray(b.data[0].asnumpy().astype(np.float32))
        y = jnp.asarray(b.label[0].asnumpy().astype(np.int32))
        return step(args, mom, aux, x, y)

    # compile + warm on a real batch
    args, mom, aux, loss = fed_step(args, mom, aux)
    float(loss)
    args, mom, aux, loss = fed_step(args, mom, aux)
    float(loss)

    t0 = time.time()
    for _ in range(steps):
        args, mom, aux, loss = fed_step(args, mom, aux)
    loss = float(loss)                       # full barrier
    fed_rate = batch * steps / (time.time() - t0)

    # same-session synthetic rate = the step-only bound the feed is
    # measured against (identical compiled program, resident tensors)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, size, size).astype("float32"))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
    args, mom, aux, l2 = step(args, mom, aux, x, y)
    float(l2)
    t0 = time.time()
    for _ in range(steps):
        args, mom, aux, l2 = step(args, mom, aux, x, y)
    float(l2)
    syn_rate = batch * steps / (time.time() - t0)

    _finish(dict({
        "metric": "resnet50_train_real_data_img_per_sec_bs%d_%s"
                  % (batch, dev["platform"]),
        "value": round(fed_rate, 2), "unit": "img/s",
        "feed": "ImageRecordIter", "records": n_records,
        "image_size": size, "steps": steps,
        "synthetic_img_per_sec": round(syn_rate, 2),
        "feed_bound_fraction": round(1.0 - fed_rate / syn_rate, 3),
    }, **_device_keys(dev)), loss)


def _start():
    """The device row every result carries, or exit 2 with one line when
    jax found no accelerator (a CPU run of this script would time XLA's
    CPU backend under a device metric's name). Places the compile cache
    (mxnet_tpu.chip.use_compile_cache)."""
    from mxnet_tpu import chip
    from mxnet_tpu.base import MXNetError
    try:
        dev = chip.require_accelerator("bench.py")
    except MXNetError as exc:
        print("bench: %s" % exc, file=sys.stderr)
        sys.exit(2)
    chip.use_compile_cache()
    return dev


def _device_keys(dev):
    return {"platform": dev["platform"], "device_kind": dev["kind"],
            "device_count": dev["count"]}


def _finish(result, loss):
    """Print the row — unless the loss went non-finite, which fails the
    run with no row."""
    if not np.isfinite(loss):
        print("bench: non-finite loss %r; no row printed" % (loss,),
              file=sys.stderr)
        sys.exit(1)
    print(json.dumps(result))


def _vs_baseline(img_s, batch):
    """The 363.69 img/s baseline row is bs=128; at any other effective
    batch (env override) the ratio would
    conflate batch-size effect with framework speedup, so it is
    reported as None with a note instead."""
    if batch == 128:
        return round(img_s / BASELINE_IMG_S, 3), None
    return None, ("baseline row is bs=128 (363.69 img/s); ratio "
                  "suppressed at bs=%d to keep the comparison "
                  "apples-to-apples" % batch)


def main():
    repeats = int(os.environ.get("MXNET_BENCH_REPEATS", "1"))
    dev = _start()
    batch, size, steps = BATCH, 224, 20

    import jax.numpy as jnp
    step, args, mom, aux = build_train_step(batch, size)
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(batch, 3, size, size).astype("float32"))
    y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)

    # compile + warmup; the float() fetch is the barrier
    args, mom, aux, loss = step(args, mom, aux, x, y)
    float(loss)
    args, mom, aux, loss = step(args, mom, aux, x, y)
    float(loss)

    rates = []
    for _ in range(max(1, repeats)):
        t0 = time.time()
        for _ in range(steps):
            args, mom, aux, loss = step(args, mom, aux, x, y)
        loss = float(loss)
        dt = time.time() - t0
        rates.append(batch * steps / dt)

    img_s = rates[0] if repeats <= 1 else float(np.median(rates))
    ratio, note = _vs_baseline(img_s, batch)
    result = {
        "metric": "resnet50_train_img_per_sec_bs%d_%s"
                  % (batch, dev["platform"]),
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": ratio,
    }
    result.update(_device_keys(dev))
    if note:
        result["baseline_note"] = note
    if repeats > 1:
        # repeatability data (MXNET_BENCH_REPEATS=N): median headline,
        # spread recorded so a single measurement session is auditable
        result["repeats"] = repeats
        result["min"] = round(min(rates), 2)
        result["max"] = round(max(rates), 2)
        result["std"] = round(float(np.std(rates)), 2)
    _finish(result, loss)


if __name__ == "__main__":
    if "--real-data" in sys.argv[1:] \
            or os.environ.get("MXNET_BENCH_REAL_DATA"):
        real_data_main()
    else:
        main()
