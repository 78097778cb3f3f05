#!/usr/bin/env python
"""Train an ImageNet-class CNN — the reference's headline driver.

Reference: example/image-classification/train_imagenet.py + common/fit.py
(perf.md's training numbers are measured through this script with
--benchmark 1, which feeds synthetic data so the result is compute-bound).

TPU rebuild: the hot path is `mxnet_tpu.parallel.TrainStep` — forward +
loss + backward + SGD fused into ONE XLA executable (the reference's
bulked GraphExecutor + kvstore update, as a single compiled program).
``--benchmark 1`` reproduces the reference protocol (synthetic data,
img/s printed per batch window); bench.py imports `build_train_step` /
`benchmark_rate` from here, so the recorded benchmark IS this driver.
Without --benchmark, feeds ImageRecordIter batches from --data-train
(.rec) through the same step.
"""
from __future__ import annotations

import argparse
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def build_net(network, num_classes, **net_kwargs):
    from mxnet_tpu.gluon.model_zoo import vision

    factory = {
        "resnet18": vision.resnet18_v1, "resnet34": vision.resnet34_v1,
        "resnet50": vision.resnet50_v1, "resnet101": vision.resnet101_v1,
        "alexnet": vision.alexnet, "vgg16": vision.vgg16,
        "inception-v3": vision.inception_v3,
        "mobilenet": vision.mobilenet1_0,
    }[network]
    net = factory(classes=num_classes, **net_kwargs)
    net.initialize()
    return net


def build_train_step(network="resnet50", num_classes=1000, dtype=None,
                     devices=None, lr=0.1, momentum=0.9, wd=1e-4,
                     **net_kwargs):
    """The compiled training step bench.py and chip_smoke.py drive:
    data-parallel over `devices` (default: every device JAX reports,
    so a four-chip host trains on four chips, not on the first)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.parallel import TrainStep, make_mesh

    net = build_net(network, num_classes, **net_kwargs)
    return TrainStep(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                     optimizer="sgd",
                     optimizer_params={"learning_rate": lr,
                                       "momentum": momentum, "wd": wd},
                     mesh=make_mesh({"dp": -1}, devices=devices),
                     dtype=dtype)


def benchmark_rate(network="resnet50", batch=32, dtype=None, devices=None,
                   image_shape=(3, 224, 224), iters=10, windows=5,
                   warmup=3, num_classes=1000, lr=0.1, momentum=0.9,
                   wd=1e-4):
    """img/s, median over windows; each window closed by a host readback
    (see bench.py measurement discipline)."""
    import jax
    import jax.numpy as jnp

    step = build_train_step(network, num_classes, dtype, devices,
                            lr=lr, momentum=momentum, wd=wd)
    rng = np.random.RandomState(0)
    x = rng.rand(batch, *image_shape).astype(np.float32)
    y = rng.randint(0, num_classes, batch).astype(np.float32)
    step(x, y)                                   # materialize + compile
    x = jax.device_put(jnp.asarray(x), step._data_sharding)
    y = jax.device_put(jnp.asarray(y), step._data_sharding)
    for _ in range(warmup):
        loss = step(x, y)
    if warmup:
        float(loss)                              # drain the warmup chain
    rates = []
    for _ in range(windows):
        t0 = time.perf_counter()
        for _ in range(iters):
            loss = step(x, y)
        float(loss)                              # completion proof
        rates.append(batch * iters / (time.perf_counter() - t0))
    return sorted(rates)[len(rates) // 2]


def main():
    parser = argparse.ArgumentParser(description="train imagenet",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("--network", default="resnet50")
    parser.add_argument("--device", default=os.environ.get(
        "MXNET_DEVICE", "auto"), choices=["auto", "cpu", "tpu"],
        help="pins the backend in-process; 'tpu' fails where there "
             "is no TPU")
    parser.add_argument("--num-classes", type=int, default=1000)
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--image-shape", default="3,224,224")
    parser.add_argument("--num-epochs", type=int, default=1)
    parser.add_argument("--lr", type=float, default=0.1)
    parser.add_argument("--mom", type=float, default=0.9)
    parser.add_argument("--wd", type=float, default=1e-4)
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    parser.add_argument("--kv-store", default="device")
    parser.add_argument("--benchmark", type=int, default=0,
                        help="1: synthetic data, print img/s (the "
                        "reference's measurement mode)")
    parser.add_argument("--max-batches", type=int, default=0,
                        help="stop an epoch early (0 = full epoch)")
    parser.add_argument("--data-train", default=None,
                        help=".rec file for real training data")
    args = parser.parse_args()
    from mxnet_tpu.compile import enable_jax_cache
    from mxnet_tpu.util import pin_platform

    pin_platform(args.device)
    enable_jax_cache()
    logging.basicConfig(level=logging.INFO)
    shape = tuple(int(v) for v in args.image_shape.split(","))
    dtype = None if args.dtype == "float32" else args.dtype

    if args.benchmark:
        rate = benchmark_rate(args.network, args.batch_size, dtype,
                              image_shape=shape,
                              num_classes=args.num_classes, lr=args.lr,
                              momentum=args.mom, wd=args.wd)
        print("benchmark: %s b%d %s: %.2f img/s"
              % (args.network, args.batch_size, args.dtype, rate))
        return rate

    import mxnet_tpu as mx

    step = build_train_step(args.network, args.num_classes, dtype,
                            lr=args.lr, momentum=args.mom, wd=args.wd)
    if args.data_train:
        idx_path = os.path.splitext(args.data_train)[0] + ".idx"
        if not os.path.exists(idx_path):
            logging.warning("no %s: shuffle is a no-op without the index "
                            "(regenerate with tools/im2rec.py)", idx_path)
        it = mx.io.ImageRecordIter(
            path_imgrec=args.data_train,
            path_imgidx=idx_path if os.path.exists(idx_path) else None,
            batch_size=args.batch_size, data_shape=shape, shuffle=True)
    else:
        raise SystemExit("provide --data-train <file.rec> or --benchmark 1")
    loss = None
    for epoch in range(args.num_epochs):
        it.reset()
        t0 = time.perf_counter()
        n = 0
        for i, batch in enumerate(it):
            loss = step(batch.data[0], batch.label[0])
            n += args.batch_size
            if args.max_batches and i + 1 >= args.max_batches:
                break
        if loss is None:
            raise SystemExit("no batches in %s (batch size %d too large?)"
                             % (args.data_train, args.batch_size))
        logging.info("epoch %d: loss %.4f, %.1f img/s", epoch,
                     float(loss), n / (time.perf_counter() - t0))
    step.sync_to_net()
    return float(loss)


if __name__ == "__main__":
    main()
