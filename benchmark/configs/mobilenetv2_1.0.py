"""MobileNetV2 at width 1.0 (Sandler et al., arXiv:1801.04381, Table 2):
how the program builds it, the shapes of its layers, and its plain
reference.  One build, the model zoo's.  Canonical parameter names:

    stem.conv.w  stem.bn.*   b<i>.<expand|dw|project>.conv.w  ....bn.*
    head.conv.w  head.bn.*   pred.w
"""
from __future__ import annotations

import benchref as R

_PARTS = ("gamma", "beta", "mean", "var")


def _blocks(cfg):
    """(in, out, t, stride) of every inverted-residual block, from the
    paper's table (t, c, n, s): the first block of a row strides."""
    m = float(cfg["width_multiplier"])
    cin = int(cfg["stem_filters"] * m)
    rows = []
    for t, c, n, s in cfg["blocks_t_c_n_s"]:
        for i in range(n):
            rows.append((cin, int(c * m), t, s if i == 0 else 1))
            cin = int(c * m)
    return rows


def _head(cfg):
    m = float(cfg["width_multiplier"])
    return int(cfg["head_filters"] * m) if m > 1.0 else cfg["head_filters"]


def build(cfg, which):
    if which != "zoo":
        raise ValueError(f"mobilenetv2_1.0 has no build {which!r}")
    from mxnet_tpu.gluon.model_zoo.vision import mobilenet as zoo
    return zoo.MobileNetV2(float(cfg["width_multiplier"]),
                           classes=cfg["num_classes"])


def canonical(cfg, which, net=None):
    names = {}

    def put(conv, bn, ours):
        names[conv.weight.name] = ours + ".conv.w"
        for part, attr in zip(_PARTS, (bn.gamma, bn.beta, bn.running_mean,
                                       bn.running_var)):
            names[attr.name] = f"{ours}.bn.{part}"
    feats = net.features
    put(feats[0], feats[1], "stem")
    n = len(_blocks(cfg))
    for i in range(n):
        out = feats[3 + i].out
        put(out[0], out[1], f"b{i}.expand")
        put(out[3], out[4], f"b{i}.dw")
        put(out[6], out[7], f"b{i}.project")
    put(feats[3 + n], feats[4 + n], "head")
    names[net.output[0].weight.name] = "pred.w"
    return names


def param_shapes(cfg, which):
    shapes = {}

    def put(name, cout, cin, k):
        shapes[name + ".conv.w"] = (cout, cin, k, k)
        for part in _PARTS:
            shapes[f"{name}.bn.{part}"] = (cout,)
    blocks = _blocks(cfg)
    put("stem", blocks[0][0], cfg["image"][0], 3)
    for i, (cin, cout, t, _s) in enumerate(blocks):
        put(f"b{i}.expand", cin * t, cin, 1)
        put(f"b{i}.dw", cin * t, 1, 3)
        put(f"b{i}.project", cout, cin * t, 1)
    put("head", _head(cfg), blocks[-1][1], 1)
    shapes["pred.w"] = (cfg["num_classes"], _head(cfg), 1, 1)
    return shapes


def reference(cfg, which):
    """``forward(params, x, train, tally=None) -> logits``."""
    eps = float(cfg["builds"][which]["bn_eps"])
    blocks = _blocks(cfg)

    def forward(p, x, train, tally=None):
        def conv_bn(x, name, stride=1, pad=0, groups=1, relu6=True):
            x = R.conv(x, p[name + ".conv.w"], stride, pad, groups,
                       tally=tally)
            x = R.batch_norm(x, [p[f"{name}.bn.{k}"] for k in _PARTS],
                             train, eps)
            return R.jnp.clip(x, 0, 6) if relu6 else x

        x = conv_bn(x, "stem", 2, 1)
        for i, (cin, cout, t, stride) in enumerate(blocks):
            y = conv_bn(x, f"b{i}.expand")
            y = conv_bn(y, f"b{i}.dw", stride, 1, groups=cin * t)
            y = conv_bn(y, f"b{i}.project", relu6=False)
            x = y + x if (stride == 1 and cin == cout) else y
        x = conv_bn(x, "head")
        x = R.jnp.mean(x, axis=(2, 3), keepdims=True)
        return R.conv(x, p["pred.w"], tally=tally).reshape(x.shape[0], -1)

    return forward


def macs_per_image(cfg, which):
    return R.count_macs(reference(cfg, which), param_shapes(cfg, which),
                        cfg["image"])
