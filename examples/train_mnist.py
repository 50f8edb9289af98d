#!/usr/bin/env python
"""LeNet-style MNIST training (parity: reference example/image-classification
/train_mnist.py, gluon flavor).

Runs on the host CPU unless told otherwise: ``--tpus 0`` trains on that
TPU chip (the reference's ``--gpus``; a chip that is not there is an
error, not a CPU run).  Uses the real MNIST files when --data-dir has
them (idx format, as mx.test_utils expects); otherwise generates a
synthetic separable dataset so the example is runnable in zero-egress
environments.

Usage: python examples/train_mnist.py [--epochs 3] [--batch-size 64]
                                      [--tpus 0]
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def load_data(data_dir, n_synth=2048):
    img = os.path.join(data_dir or "", "train-images-idx3-ubyte")
    if data_dir and os.path.exists(img):
        with open(img, "rb") as f:
            _, n, h, w = np.frombuffer(f.read(16), ">i4")
            x = np.frombuffer(f.read(), np.uint8).reshape(n, 1, h, w)
        with open(os.path.join(data_dir, "train-labels-idx1-ubyte"),
                  "rb") as f:
            f.read(8)
            y = np.frombuffer(f.read(), np.uint8)
        return x.astype(np.float32) / 255.0, y.astype(np.float32)
    # synthetic fallback: 10 gaussian blobs in pixel space
    rng = np.random.RandomState(0)
    y = rng.randint(0, 10, n_synth)
    protos = rng.rand(10, 1, 28, 28).astype(np.float32)
    x = protos[y] + rng.randn(n_synth, 1, 28, 28).astype(np.float32) * 0.3
    return x, y.astype(np.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--batch-size", type=int, default=64)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--data-dir", default=None)
    ap.add_argument("--tpus", default="",
                    help="TPU chip to train on, e.g. 0; empty means the "
                         "host CPU (parity: reference --gpus)")
    args = ap.parse_args()

    import mxnet_tpu as mx
    ctx = mx.tpu(int(args.tpus.split(",")[0])) if args.tpus else mx.cpu()
    print(f"training on {ctx} ({ctx.jax_device})")
    with ctx:  # the default context: data, parameters and outputs follow
        train(args, ctx)


def train(args, ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon, io as mxio
    from mxnet_tpu.gluon import nn

    x, y = load_data(args.data_dir)
    split = int(len(x) * 0.9)
    train_it = mxio.NDArrayIter(mx.nd.array(x[:split]),
                                mx.nd.array(y[:split]),
                                batch_size=args.batch_size, shuffle=True)
    val_it = mxio.NDArrayIter(mx.nd.array(x[split:]),
                              mx.nd.array(y[split:]),
                              batch_size=args.batch_size)

    net = gluon.nn.HybridSequential()
    net.add(nn.Conv2D(8, kernel_size=3, activation="relu"),
            nn.MaxPool2D(pool_size=2, strides=2),
            nn.Conv2D(16, kernel_size=3, activation="relu"),
            nn.MaxPool2D(pool_size=2, strides=2),
            nn.Flatten(),
            nn.Dense(64, activation="relu"),
            nn.Dense(10))
    net.initialize(mx.initializer.Xavier(), ctx=ctx)
    net.hybridize()

    from mxnet_tpu.gluon.contrib.estimator import Estimator
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": args.lr, "momentum": 0.9})
    est = Estimator(net, metrics=mx.metric.create("acc"), trainer=trainer)
    import logging
    logging.basicConfig(level=logging.INFO)
    est.fit(train_it, val_data=val_it, epochs=args.epochs,
            batch_size=args.batch_size)
    print("final train metrics:", est.metric_values())


if __name__ == "__main__":
    main()
