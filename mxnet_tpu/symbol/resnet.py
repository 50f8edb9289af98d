"""Symbolic ResNet v1 for the Module API (parity: reference
example/image-classification/symbols/resnet.py ``get_symbol``).

The gluon model zoo (``gluon.model_zoo.vision.resnet50_v1``) serves
``gluon.Trainer`` and ``parallel.spmd.TrainStep``; ``Module`` binds a
Symbol, and this is the same bottleneck network written as one.
"""
from __future__ import annotations


def resnet_v1(units=(3, 4, 6, 3), filters=(256, 512, 1024, 2048),
              num_classes=1000, thumbnail=False):
    """Bottleneck ResNet v1 ending in ``SoftmaxOutput`` named "softmax"
    (inputs ``data`` NCHW and ``softmax_label``; logits are the internal
    ``fc1_output``).  The defaults are ResNet-50 at its published width.
    ``thumbnail`` swaps the 7x7/2 stem and max-pool for one 3x3/1
    convolution (CIFAR-sized inputs, and the CPU dry runs)."""
    from .. import symbol as sym

    def conv_bn(x, f, k, s, p, name, act=True):
        x = sym.Convolution(x, num_filter=f, kernel=(k, k), stride=(s, s),
                            pad=(p, p), no_bias=True, name=name + "_conv")
        x = sym.BatchNorm(x, fix_gamma=False, name=name + "_bn")
        return sym.Activation(x, act_type="relu") if act else x

    def bottleneck(x, f, stride, dim_match, name):
        body = conv_bn(x, f // 4, 1, 1, 0, name + "_a")
        body = conv_bn(body, f // 4, 3, stride, 1, name + "_b")
        body = conv_bn(body, f, 1, 1, 0, name + "_c", act=False)
        if dim_match:
            sc = x
        else:
            sc = sym.Convolution(x, num_filter=f, kernel=(1, 1),
                                 stride=(stride, stride), no_bias=True,
                                 name=name + "_sc_conv")
            sc = sym.BatchNorm(sc, fix_gamma=False, name=name + "_sc_bn")
        return sym.Activation(body + sc, act_type="relu")

    data = sym.Variable("data")
    if thumbnail:
        body = conv_bn(data, filters[0] // 4, 3, 1, 1, "stem")
    else:
        body = conv_bn(data, 64, 7, 2, 3, "stem")
        body = sym.Pooling(body, kernel=(3, 3), stride=(2, 2), pad=(1, 1),
                           pool_type="max")
    for st, (n_units, f) in enumerate(zip(units, filters)):
        for u in range(n_units):
            stride = 2 if (st > 0 and u == 0) else 1
            body = bottleneck(body, f, stride, u != 0, f"s{st}_u{u}")
    pool = sym.Pooling(body, global_pool=True, pool_type="avg",
                       kernel=(7, 7))
    fc = sym.FullyConnected(sym.Flatten(pool), num_hidden=num_classes,
                            name="fc1")
    return sym.SoftmaxOutput(fc, name="softmax")
