#!/usr/bin/env python
"""graftlint — project-native static analysis for the mxnet_tpu repo.

Rules encode invariants this codebase has already paid to learn (see
docs/lint.md): lock-discipline races, torn writes of durable artifacts,
device->host syncs in hot loops, tracer leaks in jit code, swallowed
errors, env-knob drift against config.py — plus the whole-program flow
rules the v2 call-graph engine runs: collective-divergence (the SPMD
deadlock shape), lock-order-cycle (AB/BA across the threaded
subsystems), and trace-host-escape (host work reachable from donated
jit/shard_map/scan bodies).

Usage:
  python tools/graftlint.py                      # lint default paths
  python tools/graftlint.py --fail-on-new        # CI gate (baseline diff)
  python tools/graftlint.py --write-baseline     # accept current findings
  python tools/graftlint.py --changed-only       # findings in files
                                                 # touched vs merge-base
  python tools/graftlint.py --timings            # per-rule wall-time table
  python tools/graftlint.py --json path/to.py    # machine-readable
  python tools/graftlint.py --sarif out.sarif    # SARIF 2.1.0 for CI
  python tools/graftlint.py --explain <rule>     # rule catalog entry
  python tools/graftlint.py --list-rules

Exit codes: 0 clean (or only baselined findings with --fail-on-new),
1 gate failure, 2 usage/internal error.

The analysis package is loaded straight from its directory so that
linting never imports mxnet_tpu itself (no jax/numpy import cost).
Note the whole tree is ALWAYS analyzed (the call graph needs every
summary); --changed-only only restricts which findings are reported.
"""
from __future__ import annotations

import argparse
import importlib.util
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_PATHS = ("mxnet_tpu", "tools", "__graft_entry__.py")
DEFAULT_BASELINE = os.path.join("ci", "graftlint_baseline.json")


def _load_analysis():
    pkg_dir = os.path.join(REPO, "mxnet_tpu", "analysis")
    spec = importlib.util.spec_from_file_location(
        "graftlint_analysis", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["graftlint_analysis"] = mod
    spec.loader.exec_module(mod)
    return mod


def _changed_files(base_ref="main"):
    """Repo-relative ``.py`` paths touched (committed or working tree)
    since ``git merge-base HEAD <base_ref>`` — or None when git cannot
    answer (not a repo, unknown ref): the caller falls back to
    full-tree reporting with a warning."""
    try:
        base = subprocess.run(
            ["git", "merge-base", "HEAD", base_ref], cwd=REPO,
            capture_output=True, text=True, timeout=30)
        if base.returncode != 0:
            return None
        diff = subprocess.run(
            ["git", "diff", "--name-only", base.stdout.strip()],
            cwd=REPO, capture_output=True, text=True, timeout=30)
        if diff.returncode != 0:
            return None
    except (OSError, subprocess.SubprocessError):
        return None
    return {ln.strip().replace(os.sep, "/")
            for ln in diff.stdout.splitlines()
            if ln.strip().endswith(".py")}


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help=f"files/dirs to lint (default: {DEFAULT_PATHS})")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (schema v2: findings "
                         "+ call_graph stats + optional timings)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="baseline JSON path (repo-relative)")
    ap.add_argument("--fail-on-new", action="store_true",
                    help="exit 1 when findings exceed the baseline")
    ap.add_argument("--write-baseline", action="store_true",
                    help="commit current findings as the baseline")
    ap.add_argument("--changed-only", action="store_true",
                    help="report findings only in files touched vs "
                         "`git merge-base HEAD main` (the whole tree "
                         "is still analyzed for the call graph)")
    ap.add_argument("--diff-base", default="main",
                    help="ref --changed-only diffs against "
                         "(default: main)")
    ap.add_argument("--timings", action="store_true",
                    help="print a per-rule wall-time table (where "
                         "lint time goes)")
    ap.add_argument("--sarif", default="", metavar="PATH",
                    help="also write findings as SARIF 2.1.0 to PATH "
                         "(rule metadata from the catalog, graftlint "
                         "fingerprints as partialFingerprints)")
    ap.add_argument("--explain", default="", metavar="RULE",
                    help="print RULE's catalog entry (description, "
                         "origin bug, flag + near-miss examples) and "
                         "exit — the same source of truth docs/lint.md "
                         "embeds")
    ap.add_argument("--select", default="",
                    help="comma-separated rule ids to run exclusively")
    ap.add_argument("--disable", default="",
                    help="comma-separated rule ids to skip")
    ap.add_argument("--list-rules", action="store_true")
    args = ap.parse_args(argv)

    an = _load_analysis()

    if args.explain:
        block = an.catalog.explain(args.explain)
        if block is None:
            known = sorted(set(an.all_rules()) | set(an.all_graph_rules()))
            print(f"graftlint: unknown rule {args.explain!r} "
                  f"(known: {', '.join(known)})", file=sys.stderr)
            return 2
        print(block, end="")
        return 0

    if args.list_rules:
        catalog = dict(an.all_rules())
        catalog.update(an.all_graph_rules())
        for rid, cls in sorted(catalog.items()):
            print(f"{rid:<24} [{cls.severity}] {cls.doc}")
        return 0

    select = [r for r in args.select.split(",") if r]
    disable = [r for r in args.disable.split(",") if r]
    known = set(an.all_rules()) | set(an.all_graph_rules())
    unknown = (set(select) | set(disable)) - known
    if unknown:
        print(f"graftlint: unknown rules: {sorted(unknown)}",
              file=sys.stderr)
        return 2
    lex_ids = set(an.all_rules())
    lex_disable = [r for r in disable if r in lex_ids]
    if select:
        lex_select = [r for r in select if r in lex_ids]
        rules = an.make_rules(select=lex_select,
                              disable=lex_disable) if lex_select else []
    else:
        rules = an.make_rules(disable=lex_disable)
    graph_rules = an.make_graph_rules(
        select=select or None, disable=disable)

    paths = args.paths or [os.path.join(REPO, p) for p in DEFAULT_PATHS]
    res = an.analyze_project(paths, rules=rules,
                             graph_rules=graph_rules, root=REPO,
                             timings=args.timings)
    findings, errors = res.findings, res.errors

    if args.changed_only:
        changed = _changed_files(args.diff_base)
        if changed is None:
            print("graftlint: --changed-only: git diff against "
                  f"{args.diff_base!r} unavailable; reporting the "
                  "full tree", file=sys.stderr)
        else:
            findings = [f for f in findings if f.path in changed]
            errors = [(p, m) for p, m in errors if p in changed]

    if args.sarif:
        import json as _json
        sarif_path = (args.sarif if os.path.isabs(args.sarif)
                      else os.path.join(os.getcwd(), args.sarif))
        tmp = f"{sarif_path}.tmp-{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as fh:
            _json.dump(an.render_sarif(findings), fh, indent=2,
                       sort_keys=True)
            fh.write("\n")
        os.replace(tmp, sarif_path)
        print(f"graftlint: SARIF written to {args.sarif} "
              f"({len(findings)} result(s))", file=sys.stderr)

    baseline_path = (args.baseline if os.path.isabs(args.baseline)
                     else os.path.join(REPO, args.baseline))

    if args.write_baseline:
        an.write_baseline(baseline_path, findings)
        print(f"graftlint: baseline written to "
              f"{os.path.relpath(baseline_path, REPO)} "
              f"({len(findings)} finding(s))")
        if args.timings and res.timings:
            print(an.render_timings(res.timings))
        return 0

    stats = res.program.stats()
    if args.fail_on_new:
        baseline = an.load_baseline(baseline_path)
        new, old = an.diff_baseline(findings, baseline)
        # under --changed-only the unfiltered debt is out of view, so
        # the baseline legitimately "over-counts" — no stale note
        stale = 0 if args.changed_only else \
            sum(baseline.values()) - len(old)
        if args.json:
            print(an.render_json(new, errors, call_graph=stats,
                                 timings=res.timings))
        else:
            print(an.render_text(
                new, errors,
                title=f"graftlint --fail-on-new ({len(old)} baselined, "
                      f"{stale} baseline entr{'y' if stale == 1 else 'ies'} "
                      "now stale)"))
            if stale > 0:
                print("graftlint: note: the baseline over-counts — "
                      "shrink it with --write-baseline")
            if args.timings and res.timings:
                print(an.render_timings(res.timings))
        if new or errors:
            return 1
        return 0

    if args.json:
        print(an.render_json(findings, errors, call_graph=stats,
                             timings=res.timings))
    else:
        print(an.render_text(findings, errors))
        if args.timings and res.timings:
            print(an.render_timings(res.timings))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
