#!/usr/bin/env python
"""ImageNet-class training example (parity: reference example/
image-classification/train_imagenet.py + benchmark_score.py).

Two modes:

* ``--benchmark 1`` (default when no --data-rec): synthetic data, prints
  a host-clock rate — the reference benchmark_score.py / train_imagenet.py
  --benchmark flow.
* ``--data-rec path.rec``: trains from an ImageRecordIter RecordIO file
  (tools/im2rec.py builds one).

Where it runs: on the host CPU unless told otherwise.  ``--tpus 0``
trains on that TPU chip (the reference's ``--gpus``; a chip that is not
there is an error, not a CPU run).  ``--num-devices N`` shards the batch
over the first N devices of jax's default backend through
parallel.spmd.TrainStep (dp axis, XLA collectives): the chips of a TPU
host, or the virtual CPU mesh under JAX_PLATFORMS=cpu
XLA_FLAGS=--xla_force_host_platform_device_count=8.

The whole train step (fwd+bwd+update) is one XLA program via gluon
Trainer + hybridize.

Examples:
  python examples/train_imagenet.py --network resnet50_v1 --batch-size 32 \\
      --tpus 0
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      python examples/train_imagenet.py --network resnet18_v1 \\
      --image-shape 3,32,32 --batch-size 64 --num-devices 8
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def parse_args():
    ap = argparse.ArgumentParser(
        description="train an image-classification network "
                    "(reference train_imagenet.py parity)")
    ap.add_argument("--network", default="resnet18_v1",
                    help="model_zoo.vision model name (resnet50_v1, "
                         "mobilenet1_0, vgg16, ...)")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--image-shape", default="3,224,224")
    ap.add_argument("--num-classes", type=int, default=1000)
    ap.add_argument("--num-epochs", type=int, default=1)
    ap.add_argument("--num-batches", type=int, default=30,
                    help="batches per epoch in benchmark mode")
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--wd", type=float, default=1e-4)
    ap.add_argument("--momentum", type=float, default=0.9)
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16", "float16"])
    ap.add_argument("--benchmark", type=int, default=None,
                    help="1 = synthetic data (default without --data-rec)")
    ap.add_argument("--data-rec", default=None,
                    help="RecordIO file for real training")
    ap.add_argument("--tpus", default="",
                    help="TPU chip to train on, e.g. 0; empty means the "
                         "host CPU (parity: reference --gpus)")
    ap.add_argument("--num-devices", type=int, default=1,
                    help=">1 shards the batch data-parallel over a Mesh of "
                         "the default backend's first N devices")
    ap.add_argument("--kvstore", default="device")
    return ap.parse_args()


def synthetic_iter(batch_size, image_shape, num_classes, num_batches):
    from mxnet_tpu import io as mxio, nd
    shape = (batch_size * num_batches,) + image_shape
    rng = np.random.RandomState(0)
    x = rng.uniform(-1, 1, size=shape).astype(np.float32)
    y = rng.randint(0, num_classes, shape[0]).astype(np.float32)
    return mxio.NDArrayIter(nd.array(x), nd.array(y),
                            batch_size=batch_size, shuffle=False)


def main():
    args = parse_args()
    image_shape = tuple(int(x) for x in args.image_shape.split(","))

    import mxnet_tpu as mx
    ctx = mx.tpu(int(args.tpus.split(",")[0])) if args.tpus else mx.cpu()
    if args.num_devices == 1:
        print(f"training on {ctx} ({ctx.jax_device})")
    with ctx:  # the default context: data, parameters and outputs follow
        train(args, image_shape, ctx)


def train(args, image_shape, ctx):
    import mxnet_tpu as mx
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon.model_zoo import vision

    if args.dtype == "bfloat16":
        from mxnet_tpu import amp
        amp.init(target_dtype="bfloat16")

    net = vision.get_model(args.network, classes=args.num_classes)
    net.initialize(mx.initializer.Xavier(magnitude=2.0), ctx=ctx)
    net.hybridize()

    if args.data_rec:
        from mxnet_tpu import io as mxio
        train_iter = mxio.ImageRecordIter(
            path_imgrec=args.data_rec, batch_size=args.batch_size,
            data_shape=image_shape, shuffle=True, rand_mirror=True)
    else:
        train_iter = synthetic_iter(args.batch_size, image_shape,
                                    args.num_classes, args.num_batches)

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    if args.num_devices > 1:
        run_spmd(args, net, train_iter, loss_fn)
        return

    trainer = gluon.Trainer(
        net.collect_params(), "sgd",
        {"learning_rate": args.lr, "wd": args.wd,
         "momentum": args.momentum}, kvstore=args.kvstore)
    metric = mx.metric.Accuracy()

    for epoch in range(args.num_epochs):
        train_iter.reset()
        metric.reset()
        tic = time.time()
        n_img = 0
        for i, batch in enumerate(train_iter):
            x, y = batch.data[0], batch.label[0]
            if args.dtype != "float32":
                x = x.astype(args.dtype)
            with mx.autograd.record():
                out = net(x)
                loss = loss_fn(out, y)
            loss.backward()
            trainer.step(x.shape[0])
            metric.update(y, out.astype("float32"))
            n_img += x.shape[0]
        mx.waitall()
        dt = time.time() - tic
        name, acc = metric.get()
        print(f"epoch {epoch}: {n_img / dt:.1f} img/s  {name}={acc:.4f}  "
              f"({dt:.1f}s)", flush=True)


def run_spmd(args, net, train_iter, loss_fn):
    """Data-parallel over a device Mesh via parallel.spmd.TrainStep."""
    import mxnet_tpu as mx
    from mxnet_tpu.parallel.mesh import DeviceMesh
    from mxnet_tpu.parallel.spmd import TrainStep

    mesh = DeviceMesh({"dp": args.num_devices})
    first = next(iter(train_iter))
    x_ex, y_ex = first.data[0], first.label[0]
    step = TrainStep(net, loss_fn, "sgd",
                     {"learning_rate": args.lr, "wd": args.wd,
                      "momentum": args.momentum},
                     mesh, example_batch=(x_ex, y_ex))
    for epoch in range(args.num_epochs):
        train_iter.reset()
        tic = time.time()
        n_img = 0
        loss_v = None
        for batch in train_iter:
            x, y = batch.data[0], batch.label[0]
            if args.dtype != "float32":
                x = x.astype(args.dtype)
            loss_v = step(x, y)
            n_img += x.shape[0]
        loss_f = float(np.asarray(loss_v).mean())  # sync point
        dt = time.time() - tic
        print(f"epoch {epoch}: {n_img / dt:.1f} img/s over "
              f"{args.num_devices} devices  loss={loss_f:.4f}  "
              f"({dt:.1f}s)", flush=True)


if __name__ == "__main__":
    main()
