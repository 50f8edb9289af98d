"""ResNet-50 v1 (He et al., arXiv:1512.03385, Table 1): how the program
builds it, the shapes of its layers, and its plain reference.

The program has two builds of it (``builds`` in the JSON beside this
file): the Symbol that ``Module`` binds and the model zoo's HybridBlock.
They differ in where a stage's first unit strides, in convolution biases
and in BatchNorm's epsilon; the reference follows whichever it is given.
Parameters reach the reference under canonical names:

    stem.conv.w  stem.bn.{gamma,beta,mean,var}
    s<stage>.u<unit>.<a|b|c|sc>.conv.{w,b}   ....bn.{gamma,beta,mean,var}
    fc.w  fc.b
"""
from __future__ import annotations

import benchref as R

_BN = (("gamma", "gamma"), ("beta", "beta"), ("mean", "moving_mean"),
       ("var", "moving_var"))


def _sizes(cfg):
    return (list(cfg["units"]), list(cfg["filters"]),
            int(cfg["stem_filters"]), bool(cfg.get("thumbnail")))


# -- the program's builds ------------------------------------------------------
def build(cfg, which):
    units, filters, stem, thumb = _sizes(cfg)
    if which == "symbol":
        from mxnet_tpu.symbol.resnet import resnet_v1
        # resnet_v1's thumbnail stem has filters[0] // 4 channels
        return resnet_v1(units=tuple(units), filters=tuple(filters),
                         num_classes=cfg["num_classes"], thumbnail=thumb)
    if which == "zoo":
        from mxnet_tpu.gluon.model_zoo.vision import resnet as zoo
        return zoo.ResNetV1(zoo.BottleneckV1, units, [stem] + filters,
                            classes=cfg["num_classes"], thumbnail=thumb)
    raise ValueError(f"resnet50_v1 has no build {which!r}")


def canonical(cfg, which, net=None):
    """{the program's parameter name: canonical name}."""
    units, _filters, _stem, thumb = _sizes(cfg)
    names = {}
    if which == "symbol":
        def put(theirs, ours):
            names[theirs + "_conv_weight"] = ours + ".conv.w"
            for mine, mx_name in _BN:
                names[f"{theirs}_bn_{mx_name}"] = f"{ours}.bn.{mine}"
        put("stem", "stem")
        for s, n in enumerate(units):
            for u in range(n):
                for part in ("a", "b", "c") + (("sc",) if u == 0 else ()):
                    put(f"s{s}_u{u}_{part}", f"s{s}.u{u}.{part}")
        names["fc1_weight"], names["fc1_bias"] = "fc.w", "fc.b"
        return names

    def put(conv, bn, ours):
        names[conv.weight.name] = ours + ".conv.w"
        if conv.bias is not None:
            names[conv.bias.name] = ours + ".conv.b"
        if bn is not None:
            for mine, attr in zip(("gamma", "beta", "mean", "var"),
                                  (bn.gamma, bn.beta, bn.running_mean,
                                   bn.running_var)):
                names[attr.name] = f"{ours}.bn.{mine}"
    feats = net.features
    put(feats[0], None if thumb else feats[1], "stem")
    first = 1 if thumb else 4
    for s, n in enumerate(units):
        stage = feats[first + s]
        for u in range(n):
            blk = stage[u]
            put(blk.body[0], blk.body[1], f"s{s}.u{u}.a")
            put(blk.body[3], blk.body[4], f"s{s}.u{u}.b")
            put(blk.body[6], blk.body[7], f"s{s}.u{u}.c")
            if blk.downsample is not None:
                put(blk.downsample[0], blk.downsample[1], f"s{s}.u{u}.sc")
    names[net.output.weight.name] = "fc.w"
    names[net.output.bias.name] = "fc.b"
    return names


# -- the shapes of its layers --------------------------------------------------
def param_shapes(cfg, which):
    units, filters, stem, thumb = _sizes(cfg)
    zoo = which == "zoo"
    shapes = {}

    def put(name, cout, cin, k, bias=False, bn=True):
        shapes[name + ".conv.w"] = (cout, cin, k, k)
        if bias:
            shapes[name + ".conv.b"] = (cout,)
        if bn:
            for part in ("gamma", "beta", "mean", "var"):
                shapes[f"{name}.bn.{part}"] = (cout,)

    if thumb:
        cin = stem if zoo else filters[0] // 4
        put("stem", cin, cfg["image"][0], 3, bn=not zoo)
    else:
        cin = stem
        put("stem", stem, cfg["image"][0], 7)
    for s, (n, f) in enumerate(zip(units, filters)):
        for u in range(n):
            put(f"s{s}.u{u}.a", f // 4, cin, 1, bias=zoo)
            put(f"s{s}.u{u}.b", f // 4, f // 4, 3)
            put(f"s{s}.u{u}.c", f, f // 4, 1, bias=zoo)
            # the Symbol projects in every stage's first unit; the zoo
            # only where the width changes (the same at these widths)
            if u == 0 and (not zoo or f != cin):
                put(f"s{s}.u{u}.sc", f, cin, 1)
            cin = f
    shapes["fc.w"] = (cfg["num_classes"], filters[-1])
    shapes["fc.b"] = (cfg["num_classes"],)
    return shapes


# -- the plain reference -------------------------------------------------------
def reference(cfg, which):
    """``forward(params, x, train, tally=None) -> logits``."""
    units, _filters, _stem, thumb = _sizes(cfg)
    spec = cfg["builds"][which]
    eps, stride_in = float(spec["bn_eps"]), spec["stride_in"]

    def forward(p, x, train, tally=None):
        def conv_bn(x, name, stride=1, pad=0, relu=True):
            x = R.conv(x, p[name + ".conv.w"], stride, pad,
                       bias=p.get(name + ".conv.b"), tally=tally)
            if name + ".bn.gamma" not in p:
                return x     # the zoo's thumbnail stem is a bare convolution
            x = R.batch_norm(x, [p[f"{name}.bn.{k}"] for k in
                                 ("gamma", "beta", "mean", "var")],
                             train, eps)
            return R.jnp.maximum(x, 0) if relu else x

        if thumb:
            x = conv_bn(x, "stem", 1, 1)
        else:
            x = R.max_pool(conv_bn(x, "stem", 2, 3), 3, 2, 1)
        for s, n in enumerate(units):
            for u in range(n):
                stride = 2 if (s > 0 and u == 0) else 1
                name = f"s{s}.u{u}"
                sa, sb = (stride, 1) if stride_in == "a" else (1, stride)
                y = conv_bn(x, name + ".a", sa)
                y = conv_bn(y, name + ".b", sb, 1)
                y = conv_bn(y, name + ".c", relu=False)
                sc = x
                if name + ".sc.conv.w" in p:
                    sc = conv_bn(x, name + ".sc", stride, relu=False)
                x = R.jnp.maximum(y + sc, 0)
        x = R.jnp.mean(x, axis=(2, 3))
        return R.dense(x, p["fc.w"], p["fc.b"], tally=tally)

    return forward


def macs_per_image(cfg, which):
    return R.count_macs(reference(cfg, which), param_shapes(cfg, which),
                        cfg["image"])
