#!/usr/bin/env python
"""Train an MLP or LeNet on MNIST with the Module API.

Reference: example/image-classification/train_mnist.py (+ common/fit.py)
— the canonical symbolic training driver: build symbol, create kvstore,
Module.fit with metric/speedometer callbacks. Runs distributed with
``tools/launch.py -n N python examples/train_mnist.py --kv-store
dist_sync`` exactly like the reference.

With ``--synthetic`` the driver generates an MNIST-shaped synthetic
classification set (zero-egress environments have no dataset downloads).
"""
from __future__ import annotations

import argparse
import logging
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import mxnet_tpu as mx


def get_mlp():
    """(reference train_mnist.py:get_mlp)."""
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=128, name="fc1")
    net = mx.sym.Activation(net, act_type="relu", name="relu1")
    net = mx.sym.FullyConnected(net, num_hidden=64, name="fc2")
    net = mx.sym.Activation(net, act_type="relu", name="relu2")
    net = mx.sym.FullyConnected(net, num_hidden=10, name="fc3")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def get_lenet():
    """(reference train_mnist.py:get_lenet)."""
    data = mx.sym.var("data")
    c1 = mx.sym.Convolution(data, kernel=(5, 5), num_filter=20)
    a1 = mx.sym.Activation(c1, act_type="tanh")
    p1 = mx.sym.Pooling(a1, pool_type="max", kernel=(2, 2), stride=(2, 2))
    c2 = mx.sym.Convolution(p1, kernel=(5, 5), num_filter=50)
    a2 = mx.sym.Activation(c2, act_type="tanh")
    p2 = mx.sym.Pooling(a2, pool_type="max", kernel=(2, 2), stride=(2, 2))
    f1 = mx.sym.FullyConnected(p2, num_hidden=500)
    a3 = mx.sym.Activation(f1, act_type="tanh")
    f2 = mx.sym.FullyConnected(a3, num_hidden=10)
    return mx.sym.SoftmaxOutput(f2, name="softmax")


def synthetic_iters(args, flat, rank=0, num_workers=1):
    """MNIST-shaped synthetic digits: class = argmax row-band energy.
    Sharded across dist workers (reference drivers pass num_parts/
    part_index so each worker sees its own slice)."""
    rng = np.random.RandomState(42)
    n = args.num_examples
    X = (rng.rand(n, 1, 28, 28) * 0.25).astype(np.float32)
    y = rng.randint(0, 10, n)
    for i in range(n):
        r = y[i] * 2 + 4
        X[i, 0, r:r + 3, 6:22] += 1.0
    if flat:
        X = X.reshape(n, 784)
    cut = int(n * 0.9)
    Xt, yt = X[:cut], y[:cut].astype(np.float32)
    if num_workers > 1:
        part = len(Xt) // num_workers
        Xt = Xt[rank * part:(rank + 1) * part]
        yt = yt[rank * part:(rank + 1) * part]
    train = mx.io.NDArrayIter(Xt, yt,
                              batch_size=args.batch_size, shuffle=True,
                              label_name="softmax_label")
    val = mx.io.NDArrayIter(X[cut:], y[cut:].astype(np.float32),
                            batch_size=args.batch_size,
                            label_name="softmax_label")
    return train, val


def mnist_iters(args, flat, rank=0, num_workers=1):
    prefix = args.data_dir
    train = mx.io.MNISTIter(
        image=os.path.join(prefix, "train-images-idx3-ubyte"),
        label=os.path.join(prefix, "train-labels-idx1-ubyte"),
        batch_size=args.batch_size, shuffle=True, flat=flat,
        num_parts=num_workers, part_index=rank)
    val = mx.io.MNISTIter(
        image=os.path.join(prefix, "t10k-images-idx3-ubyte"),
        label=os.path.join(prefix, "t10k-labels-idx1-ubyte"),
        batch_size=args.batch_size, shuffle=False, flat=flat)
    return train, val


def main():
    parser = argparse.ArgumentParser(description="train mnist")
    parser.add_argument("--network", default="mlp",
                        choices=["mlp", "lenet"])
    parser.add_argument("--device", default=os.environ.get(
        "MXNET_DEVICE", "auto"), choices=["auto", "cpu", "tpu"],
        help="pins the backend in-process; 'tpu' fails where there "
        "is no TPU")
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=0.05)
    parser.add_argument("--num-epochs", type=int, default=5)
    parser.add_argument("--kv-store", default="local")
    parser.add_argument("--optimizer", default="sgd")
    parser.add_argument("--gpus", default=None,
                        help="e.g. '0' — maps to TPU chips")
    parser.add_argument("--synthetic", action="store_true")
    parser.add_argument("--num-examples", type=int, default=5000)
    parser.add_argument("--data-dir", default="data")
    parser.add_argument("--disp-batches", type=int, default=50)
    parser.add_argument("--model-prefix", default=None)
    args = parser.parse_args()
    mx.util.pin_platform(args.device)

    logging.basicConfig(level=logging.INFO)
    flat = args.network == "mlp"
    net = get_mlp() if args.network == "mlp" else get_lenet()

    kv = mx.kv.create(args.kv_store)
    have_mnist = os.path.exists(os.path.join(
        args.data_dir, "train-images-idx3-ubyte"))
    rank = getattr(kv, "rank", 0)
    num_workers = getattr(kv, "num_workers", 1)
    if args.synthetic or not have_mnist:
        train, val = synthetic_iters(args, flat, rank, num_workers)
    else:
        train, val = mnist_iters(args, flat, rank, num_workers)

    if args.device == "cpu":
        ctx = mx.cpu()
    elif args.device == "tpu":
        ctx = mx.tpu(0)            # raises if no chip is reachable
    elif args.gpus:
        ctx = [mx.gpu(int(i)) for i in args.gpus.split(",")]
    else:
        ctx = mx.tpu(0) if mx.num_tpus() else mx.cpu()

    mod = mx.mod.Module(net, context=ctx, label_names=["softmax_label"])
    checkpoint = (mx.callback.do_checkpoint(args.model_prefix)
                  if args.model_prefix else None)
    mod.fit(train, eval_data=val, num_epoch=args.num_epochs,
            optimizer=args.optimizer,
            optimizer_params={"learning_rate": args.lr},
            initializer=mx.init.Xavier(magnitude=2.0),
            kvstore=kv, eval_metric="acc",
            batch_end_callback=mx.callback.Speedometer(
                args.batch_size, args.disp_batches),
            epoch_end_callback=checkpoint)
    val.reset()
    acc = mod.score(val, mx.metric.Accuracy())[0][1]
    logging.info("final validation accuracy: %.4f", acc)
    if getattr(kv, "rank", 0) == 0:
        print("final-accuracy %.4f" % acc)
    if hasattr(kv, "close"):
        kv.close()
    return acc


if __name__ == "__main__":
    main()
