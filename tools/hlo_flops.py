#!/usr/bin/env python
"""Attribute per-op FLOPs in the compiled ResNet-50 train step.

XLA ``cost_analysis`` reported ~715 GF/step at bs32 where bench.py's analytic model cost said
~371 GF — this tool was written to find the "2x waste".  What it found
(bs8 decomposition, CPU-compiled HLO; the op set is platform-independent
pre-layout):

  weight-shaped conv outputs (wgrad, 53 ops)          61.7 GF  = 1.00x fwd
  activation-shaped convs+dots (fwd + stride-1 dgrad) 115.4 GF ~ 1.9x fwd
  lhs-dilated convs (stride-2 dgrad, 6 ops)            24.7 GF  = 4x their fwd
  total                                               201.8 GF

i.e. the compiled step does EXACTLY the expected 3x-forward work — the
"2x" was bench.py's constant: 3.86e9 is gluon resnet50_v1's MAC count
(3.86 GMACs; torchvision's 4.09 is v1.5), and model FLOPs = 2*MACs =
7.72e9/img.  The only real overcount is the stride-2 backward-data
convs, which XLA charges (and executes) over the zero-inserted dilated
input: 4x their forward cost, ~18.5 GF/step = ~10% of the program.

FLOP convention per HLO op (matches xla::HloCostAnalysis):
  convolution: 2 * out_elements * (Cin/groups) * prod(kernel_spatial)
  dot:         2 * batch * M * N * K

Usage: JAX_PLATFORMS=cpu python tools/hlo_flops.py [--batch 32] [--json out]
       python tools/hlo_flops.py --from-hlo dump.hlo --batch 8
"""
import argparse
import collections
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_train_step(batch, dtype="bfloat16", loss_mode="fused"):
    """The EXACT bench.py train step (imported, not copied): returns
    (step_fn, example_args) ready to lower.  loss_mode defaults to
    "fused" — bench.py's default — so the analysis is of the program
    being timed; pass "onehot" to reproduce the r2-r4 loss for A/B."""
    import jax.numpy as jnp
    import bench
    from mxnet_tpu import random as _random

    step, (tparams_h, aparams_h), _n = bench.build_train_step(
        batch, dtype, use_remat=False, loss_mode=loss_mode)
    compute_dtype = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    tparams = tuple(jnp.asarray(p) for p in tparams_h)
    aparams = tuple(jnp.asarray(p) for p in aparams_h)
    moms = tuple(jnp.zeros_like(p) for p in tparams)
    x = jnp.zeros((batch, 3, 224, 224), compute_dtype)
    y = jnp.zeros((batch,), jnp.float32)
    key = _random.next_key()
    return step, (key, tparams, aparams, moms, x, y)


_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|u32|s8|u8|pred|f64|s64)\[([\d,]*)\]")


def _parse_shape(text):
    m = _SHAPE_RE.search(text)
    if not m:
        return None, None
    dims = [int(d) for d in m.group(2).split(",") if d] if m.group(2) else []
    return m.group(1), dims


def analyze_hlo(hlo_text):
    """Bucket conv/dot FLOPs out of optimized HLO text.

    Two passes: first a symbol table name -> (dtype, dims) from every
    instruction's left-hand side (optimized dumps usually print operands
    as bare %names, so shapes must be resolved by definition), then the
    conv/dot walk using inline shapes when present and the table when not.
    """
    table = {}
    for line in hlo_text.splitlines():
        s = line.strip()
        if "= " not in s:
            continue
        name = s.split("= ", 1)[0].strip().lstrip("%")
        dt, dims = _parse_shape(s.split("= ", 1)[1])
        if dt is not None and name not in table:
            table[name] = (dt, dims)

    def operand_shapes(opstr):
        """Shapes of the operand list, inline or via the symbol table."""
        depth, args, cur = 0, [], ""
        for ch in opstr:
            if ch in "([{":
                depth += 1
            elif ch in ")]}":
                if depth == 0:
                    break
                depth -= 1
            if ch == "," and depth == 0:
                args.append(cur)
                cur = ""
            else:
                cur += ch
        if cur.strip():
            args.append(cur)
        out = []
        for a in args:
            dt, dims = _parse_shape(a)
            if dims is None:
                mn = re.search(r"%([\w.\-_]+)", a)
                if mn and mn.group(1) in table:
                    dt, dims = table[mn.group(1)]
            out.append((dt, dims))
        return out

    convs, dots, notes = [], [], collections.Counter()
    for line in hlo_text.splitlines():
        s = line.strip()
        if "= " not in s:
            continue
        # HLO form: %name = dtype[dims]{layout} opcode(operands), attrs
        rhs = s.split("= ", 1)[1]
        mop = re.match(r"(?:\([^)]*\)|\S+)\s+([\w-]+)", rhs)
        notes[mop.group(1) if mop else "?"] += 1
        if "convolution(" in rhs:
            out_dt, out_dims = _parse_shape(rhs.split("convolution(")[0])
            if out_dims is None:
                continue
            # window + dim_labels tell us kernel spatial size & feature dims
            mw = re.search(r"window=\{size=([\dx]+)[^}]*\}", s)
            kdims = [int(k) for k in mw.group(1).split("x")] if mw else []
            ml = re.search(r"dim_labels=([\w?]+)_(\w+)->(\w+)", s)
            mg = re.search(r"feature_group_count=(\d+)", s)
            groups = int(mg.group(1)) if mg else 1
            shapes = operand_shapes(s.split("convolution(")[1])
            if len(shapes) < 2 or not ml or shapes[1][1] is None:
                continue
            rhs_dims = shapes[1][1]
            rhs_labels = ml.group(2)
            cin_per_g = rhs_dims[rhs_labels.index("i")]
            out_el = math.prod(out_dims) if out_dims else 1
            fl = 2.0 * out_el * cin_per_g * math.prod(kdims or [1])
            lhs_dil = re.search(r"lhs_dilate=[\dx]+", s)
            convs.append({
                "flops": fl, "out": out_dims, "kernel": kdims,
                "groups": groups, "dtype": out_dt,
                "lhs_dilated": bool(lhs_dil),
                "window": (mw.group(0) if mw else ""),
                "line": s[:240],
            })
        elif " dot(" in rhs or rhs.startswith("dot("):
            out_dt, out_dims = _parse_shape(rhs.split("dot(")[0])
            shapes = operand_shapes(s.split("dot(")[1])
            if len(shapes) < 1 or out_dims is None or shapes[0][1] is None:
                continue
            lhs = shapes[0][1]
            mc = re.search(r"lhs_contracting_dims=\{([\d,]+)\}", s)
            k = 1
            if mc:
                for ci in mc.group(1).split(","):
                    k *= lhs[int(ci)]
            fl = 2.0 * math.prod(out_dims or [1]) * k
            dots.append({"flops": fl, "out": out_dims, "k": k,
                         "dtype": out_dt, "line": s[:240]})
    return convs, dots, notes


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--loss", default="fused", choices=["fused", "onehot"],
                    help="loss path; 'fused' matches bench.py's default")
    ap.add_argument("--json", default=None)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--dump-hlo", default=None, help="write optimized HLO here")
    ap.add_argument("--from-hlo", default=None,
                    help="analyze an existing HLO dump instead of compiling")
    args = ap.parse_args()

    ca_flops = None
    if args.from_hlo:
        with open(args.from_hlo) as f:
            hlo = f.read()
    else:
        # HLO op structure is platform-independent pre-layout: compile
        # for the host and take no chip
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax
        step, step_args = build_train_step(args.batch, args.dtype,
                                           loss_mode=args.loss)
        print("lowering + compiling ...", file=sys.stderr, flush=True)
        compiled = jax.jit(step).lower(*step_args).compile()
        hlo = compiled.as_text()
        if args.dump_hlo:
            tmp = f"{args.dump_hlo}.tmp-{os.getpid()}"
            with open(tmp, "w") as f:
                f.write(hlo)
            os.replace(tmp, args.dump_hlo)
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            ca_flops = float(ca.get("flops", 0.0))
        except Exception:
            ca_flops = None

    convs, dots, notes = analyze_hlo(hlo)
    total_conv = sum(c["flops"] for c in convs)
    total_dot = sum(d["flops"] for d in dots)
    # model FLOPs = 2*MACs; gluon resnet50_v1 = 3.86 GMACs -> 7.72 GF/img
    analytic = 7.72e9 * 3 * args.batch
    fwd_analytic = 7.72e9 * args.batch

    b = args.batch
    # ResNet-50 activation conv outputs are [b, H, W, C] (or NCHW): batch
    # leading, a feature-map spatial size present, AND a channel count
    # present.  Wgrad outputs are weight-shaped — [Cin, kh, kw, Cout] etc.
    # — which can collide with b on the leading dim (b=64/128/256/512) and
    # with the spatial set via 7x7 kernels ([64,3,7,7] at b=64), but never
    # carry a {spatial, channel} pair like an activation does (the only
    # 3-channel tensor is the input itself, which is not a conv output).
    spatial = {7, 14, 28, 56, 112, 224}
    channels = {3, 64, 128, 256, 512, 1024, 2048}

    def is_act_conv(c):
        dims = c["out"]
        return (dims[0] == b
                and any(d in spatial for d in dims[1:])
                and any(d in channels for d in dims[1:]))

    dil = [c for c in convs if c["lhs_dilated"]]
    fwd_c = [c for c in convs if not c["lhs_dilated"] and is_act_conv(c)]
    wg_c = [c for c in convs if not c["lhs_dilated"] and not is_act_conv(c)]
    # activation dots have batch * spatial-extent leading rows, where the
    # spatial extent is one of ResNet-50's feature-map sizes (1 for the
    # FC fwd [b,1000] / dgrad [b,2048]).  FC wgrad [2048,1000] has
    # weight-shaped rows (2048/b is not a feature-map size) -> weight-out.
    spatial_sizes = {1, 7 * 7, 14 * 14, 28 * 28, 56 * 56, 112 * 112,
                     224 * 224}

    def is_act_dot(d):
        rows = d["out"][0]
        return rows % b == 0 and rows // b in spatial_sizes
    fwd_d = [d for d in dots if is_act_dot(d)]
    wg_d = [d for d in dots if not is_act_dot(d)]
    gf = lambda xs: sum(x["flops"] for x in xs) / 1e9

    print(f"batch={args.batch} dtype={args.dtype}")
    print(f"analytic train FLOPs (3x fwd, 2*MAC convention): "
          f"{analytic/1e9:.1f} GF (fwd {fwd_analytic/1e9:.1f})")
    if ca_flops:
        print(f"cost_analysis flops: {ca_flops/1e9:.1f} GF "
              f"({ca_flops/analytic:.2f}x analytic)")
    print(f"parsed conv+dot = {(total_conv+total_dot)/1e9:.1f} GF "
          f"= {(total_conv+total_dot)/analytic:.2f}x analytic")
    print("decomposition:")
    print(f"  act-out convs+dots (fwd + stride-1 dgrad): "
          f"{gf(fwd_c)+gf(fwd_d):7.2f} GF n={len(fwd_c)+len(fwd_d)}")
    print(f"  weight-out convs+dots (wgrad):             "
          f"{gf(wg_c)+gf(wg_d):7.2f} GF n={len(wg_c)+len(wg_d)}")
    print(f"  lhs-dilated convs (stride-2 dgrad, 4x fwd):"
          f"{gf(dil):7.2f} GF n={len(dil)}")
    print(f"\ntop {args.top} FLOP ops:")
    every = ([("conv", c) for c in convs] + [("dot", d) for d in dots])
    every.sort(key=lambda t: -t[1]["flops"])
    for kind, op in every[:args.top]:
        tag = " LHS-DILATED" if op.get("lhs_dilated") else ""
        print(f"  {op['flops']/1e9:8.2f} GF  {kind}{tag}  out={op.get('out')} "
              f"k={op.get('kernel', op.get('k'))} {op['dtype']}")
    if args.json:
        tmp = f"{args.json}.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump({"batch": args.batch, "analytic": analytic,
                       "cost_analysis": ca_flops, "conv_total": total_conv,
                       "dot_total": total_dot,
                       "lhs_dilated_total": sum(c["flops"] for c in dil),
                       "convs": convs, "dots": dots}, f, indent=1)
        os.replace(tmp, args.json)
    print("\nop histogram:", dict(notes.most_common(20)))


if __name__ == "__main__":
    main()
