"""Measure inference throughput of the model-zoo networks.

Parity target: example/image-classification/benchmark_score.py — for
each (network, batch size) pair, time the hybridized forward pass on
synthetic data and print images/sec.

    python examples/image_classification/benchmark_score.py \
        --networks resnet50_v1,mobilenet1.0 --batch-sizes 1,32,128
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.gluon.model_zoo import vision


def score(network, batch_size, image_shape=(3, 224, 224), steps=10,
          dtype="float32", fold_bn=False):
    net = vision.get_model(network, classes=1000)
    net.initialize(mx.init.Xavier())
    if dtype != "float32":
        net.cast(dtype)
    net.hybridize()
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (batch_size,) + image_shape)
                 .astype(dtype))
    if fold_bn:
        # deployment path: trace + export + fold in one call
        # (contrib.fold_bn.fold_block), then time the folded block
        from mxnet_tpu.contrib.fold_bn import fold_block
        folded = fold_block(net, x)
        run = lambda: folded(x)
    else:
        run = lambda: net(x)
    # compile + warmup; the scalar fetch forces device completion
    float(run().asnumpy().ravel()[0])
    float(run().asnumpy().ravel()[0])
    tic = time.time()
    for _ in range(steps):
        out = run()
    float(out.asnumpy().ravel()[0])
    return batch_size * steps / (time.time() - tic)


def main():
    parser = argparse.ArgumentParser(
        description="benchmark model-zoo inference",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--networks", type=str,
                        default="alexnet,resnet50_v1,mobilenet1.0")
    parser.add_argument("--batch-sizes", type=str, default="1,32")
    parser.add_argument("--image-shape", type=str, default="3,224,224")
    parser.add_argument("--dtype", type=str, default="float32")
    parser.add_argument("--steps", type=int, default=10)
    parser.add_argument("--fold-bn", action="store_true",
                        help="fold Conv+BN pairs into conv weights "
                             "(contrib.fold_bn deployment path)")
    args = parser.parse_args()

    # the device is part of the record: it is printed with the rows,
    # not discovered from the timings
    import jax
    dev = jax.devices()[0]
    print("backend: %s (%s x%d)" % (dev.platform, dev.device_kind,
                                    len(jax.devices())), flush=True)

    shape = tuple(int(d) for d in args.image_shape.split(","))
    for network in args.networks.split(","):
        for bs in (int(b) for b in args.batch_sizes.split(",")):
            speed = score(network, bs, shape, args.steps, args.dtype,
                          fold_bn=args.fold_bn)
            print("network: %-16s batch: %-4d  %.1f img/s%s"
                  % (network, bs, speed,
                     "  (bn-folded)" if args.fold_bn else ""))


if __name__ == "__main__":
    main()
