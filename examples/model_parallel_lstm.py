#!/usr/bin/env python
"""Model-parallel stacked LSTM (parity: reference example/model-parallel/
lstm + docs/faq/model_parallel_lstm.md).

Each LSTM layer lives in its own ``ctx_group``; ``bind(group2ctx=...)``
places every layer's compute on its own device with automatic
cross-device activation copies — the reference's group2ctx model
parallelism (graph_executor.cc:1876/AssignContext:985) on a TPU/CPU
device list. With layers on different chips, layer i works on step t
while layer i+1 works on step t-1 (the pipelining the reference doc
describes).

Synthetic copy-task data (predict the previous input token — needs the
LSTM state); loss dropping proves the placed graph trains.

Run (CPU mesh, <2 min):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/model_parallel_lstm.py --num-layers 4
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--num-layers", type=int, default=4)
    ap.add_argument("--seq-len", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--vocab", type=int, default=24)
    ap.add_argument("--num-hidden", type=int, default=48)
    ap.add_argument("--epochs", type=int, default=6)
    ap.add_argument("--lr", type=float, default=0.01)
    args = ap.parse_args()

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu.ops._op_nn import rnn_param_size

    L, T, N, H, V = (args.num_layers, args.seq_len, args.batch_size,
                     args.num_hidden, args.vocab)

    # -- symbol: one RNN op per layer, each in its own ctx group ------------
    data = mx.sym.Variable("data")                       # (N, T) tokens
    label = mx.sym.Variable("softmax_label")
    with mx.AttrScope(ctx_group="layer0"):
        emb = mx.sym.Embedding(data, input_dim=V, output_dim=H,
                               name="embed")
        x = mx.sym.transpose(emb, axes=(1, 0, 2))        # time-major
    for i in range(L):
        with mx.AttrScope(ctx_group=f"layer{i}"):
            x = mx.sym.RNN(x, mx.sym.Variable(f"l{i}_weight"),
                           mx.sym.Variable(f"l{i}_init_state"),
                           mx.sym.Variable(f"l{i}_init_cell"),
                           state_size=H, num_layers=1, mode="lstm",
                           state_outputs=False, name=f"lstm{i}")
    with mx.AttrScope(ctx_group=f"layer{L - 1}"):
        out = mx.sym.Reshape(mx.sym.transpose(x, axes=(1, 0, 2)),
                             shape=(-1, H))
        pred = mx.sym.FullyConnected(out, num_hidden=V, name="pred")
        net = mx.sym.SoftmaxOutput(pred, mx.sym.Reshape(label, shape=(-1,)),
                                   name="softmax")

    # -- placement: layers round-robin over available devices ---------------
    devs = jax.devices()
    group2ctx = {f"layer{i}": mx.Context(devs[0].platform, i % len(devs))
                 for i in range(L)}
    print(f"{L} layers over {len(devs)} {devs[0].platform} device(s): "
          + ", ".join(f"layer{i}->dev{i % len(devs)}" for i in range(L)))

    # -- params / executor ---------------------------------------------------
    rng = np.random.RandomState(0)
    arg_vals = {"embed_weight": rng.randn(V, H).astype(np.float32) * 0.1,
                "pred_weight": rng.randn(V, H).astype(np.float32) * 0.1,
                "pred_bias": np.zeros(V, np.float32)}
    for i in range(L):
        psz = rnn_param_size("lstm", 1, H, H, False)
        arg_vals[f"l{i}_weight"] = (rng.rand(psz).astype(np.float32)
                                    - 0.5) * 0.2
    states = {f"l{i}_{k}": np.zeros((1, N, H), np.float32)
              for i in range(L) for k in ("init_state", "init_cell")}

    args_nd = {k: mx.nd.array(v) for k, v in {**arg_vals, **states}.items()}
    args_nd["data"] = mx.nd.zeros((N, T), dtype=np.int32)
    args_nd["softmax_label"] = mx.nd.zeros((N, T))
    grads = {k: mx.nd.zeros(v.shape) for k, v in arg_vals.items()}
    reqs = {k: ("write" if k in grads else "null") for k in args_nd}
    ex = net.bind(mx.Context("cpu", 0) if devs[0].platform == "cpu"
                  else mx.tpu(0),
                  args_nd, args_grad=grads, grad_req=reqs,
                  group2ctx=group2ctx)

    # -- copy task: y_t = x_{t-1} (needs one step of memory) ----------------
    def batch():
        xs = rng.randint(0, V, (N, T))
        ys = np.roll(xs, 1, axis=1)
        ys[:, :1] = 0
        return xs, ys

    # SoftmaxOutput grads are summed over the N*T rows; rescale like
    # Module.fit does (rescale_grad = 1/batch) or the step size explodes
    opt = mx.optimizer.Adam(learning_rate=args.lr,
                            rescale_grad=1.0 / N)
    opt_states = {}
    first = last = None
    for epoch in range(args.epochs):
        tot, nb = 0.0, 0
        for _ in range(20):
            xs, ys = batch()
            args_nd["data"][:] = mx.nd.array(xs.astype(np.int32))
            args_nd["softmax_label"][:] = mx.nd.array(
                ys.astype(np.float32))
            prob = ex.forward(is_train=True)[0]
            ex.backward()
            for j, (k, g) in enumerate(sorted(grads.items())):
                if j not in opt_states:
                    opt_states[j] = opt.create_state(j, args_nd[k])
                opt.update(j, args_nd[k], g, opt_states[j])
            p = prob.asnumpy().reshape(N, T, V)
            nll = -np.log(np.maximum(
                p[np.arange(N)[:, None], np.arange(T)[None], ys], 1e-8))
            tot += float(nll[:, 1:].mean())
            nb += 1
        avg = tot / nb
        if first is None:
            first = avg
        last = avg
        print(f"epoch {epoch}: nll {avg:.4f}")
    assert last < first * 0.7, (first, last)
    print("model-parallel LSTM trained OK")


if __name__ == "__main__":
    main()
