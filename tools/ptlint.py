#!/usr/bin/env python
"""ptlint CLI — run the paddle_tpu invariant linter over the tree.

    python tools/ptlint.py [paths...]            # lint (default paths
                                                 # from [tool.ptlint])
    python tools/ptlint.py --json                # JSON report on stdout
    python tools/ptlint.py --out report.json     # JSON artifact
    python tools/ptlint.py --write-baseline      # re-grandfather the
                                                 # current flag/trace/
                                                 # thread findings
    python tools/ptlint.py --rules clock,metric  # subset of passes

Exit codes: 0 = clean (fresh findings all grandfathered, no stale
baseline entries), 1 = fresh findings or stale baseline, 2 = usage.

Config lives in ``[tool.ptlint]`` in pyproject.toml (paths, exclude,
baseline path, per-pass tables) so CI needs no flags. Stdlib-only:
runs on a bare worker without jax/numpy.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

if "paddle_tpu" not in sys.modules:
    # paddle_tpu/__init__.py imports jax; the analysis subpackage is
    # pure stdlib. Register a stub parent so a bare CI worker (no jax)
    # can still run the lint row.
    import types

    _pkg = types.ModuleType("paddle_tpu")
    _pkg.__path__ = [os.path.join(_REPO, "paddle_tpu")]
    sys.modules["paddle_tpu"] = _pkg

from paddle_tpu.analysis import (  # noqa: E402
    Baseline, Project, load_config, render_json, render_text, run)
from paddle_tpu.analysis.runner import (  # noqa: E402
    BASELINE_ELIGIBLE, RULES)


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="ptlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="*",
                    help="dirs/files to lint (default: [tool.ptlint] "
                         "paths, else 'paddle_tpu tools')")
    ap.add_argument("--root", default=None,
                    help="repo root (default: the tools/ parent)")
    ap.add_argument("--rules", default=None,
                    help="comma-separated subset of: %s"
                         % ",".join(RULES))
    ap.add_argument("--json", action="store_true",
                    help="JSON report on stdout instead of text")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default from [tool.ptlint])")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline (report ALL findings "
                         "as fresh)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current %s findings as the new "
                         "baseline and exit 0"
                         % "/".join(BASELINE_ELIGIBLE))
    args = ap.parse_args(argv)

    root = os.path.abspath(args.root or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".."))
    config = load_config(root)
    if args.paths:
        # Resolve CLI paths against root first, then CWD; a path that
        # exists in neither is a usage error — silently scanning zero
        # files would make a typo'd invocation report "clean".
        paths = []
        for p in args.paths:
            if os.path.exists(os.path.join(root, p)):
                paths.append(p)
                continue
            cand = os.path.abspath(p)
            if not os.path.exists(cand):
                ap.error("path %r not found under root %s or cwd"
                         % (p, root))
            rel = os.path.relpath(cand, root)
            if rel.split(os.sep)[0] == os.pardir:
                ap.error("path %r is outside root %s — pass --root"
                         % (p, root))
            paths.append(rel)
    else:
        paths = config.get("paths") or ["paddle_tpu", "tools"]
    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in rules if r not in RULES]
        if unknown:
            ap.error("unknown rule(s) %s (have: %s)"
                     % (unknown, ",".join(RULES)))
    project = Project(root, paths=paths,
                      exclude=tuple(config.get("exclude", ())),
                      config=config)
    baseline_path = args.baseline or config.get("baseline")
    if baseline_path and not os.path.isabs(baseline_path):
        baseline_path = os.path.join(root, baseline_path)

    if args.write_baseline:
        if rules is not None:
            ap.error("--write-baseline cannot be combined with "
                     "--rules: the baseline is written whole, and a "
                     "subset run would silently drop every other "
                     "rule's grandfathered entries")
        findings, _, _ = run(project, rules=rules, baseline=None)
        keep = [f for f in findings if f.rule in BASELINE_ELIGIBLE]
        if not baseline_path:
            ap.error("--write-baseline needs a baseline path "
                     "(--baseline or [tool.ptlint] baseline)")
        Baseline.from_findings(keep).write(baseline_path)
        dropped = len(findings) - len(keep)
        print("ptlint: wrote %d grandfathered finding(s) to %s"
              % (len(keep), os.path.relpath(baseline_path, root)))
        if dropped:
            print("ptlint: %d finding(s) in non-grandfatherable rules "
                  "(clock/metric/silent-except) NOT written — fix or "
                  "pragma them" % dropped)
        return 0

    baseline = None
    if baseline_path and not args.no_baseline:
        baseline = Baseline.load(baseline_path)
    findings, stale, counts = run(project, rules=rules,
                                  baseline=baseline)
    report = render_json(
        findings, stale, counts,
        meta={"root": root, "paths": list(paths),
              "rules": rules or list(RULES),
              "baseline": (os.path.relpath(baseline_path, root)
                           if baseline_path else None),
              "files_scanned": len(project.files)})
    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1)
            f.write("\n")
    if args.json:
        json.dump(report, sys.stdout, indent=1)
        sys.stdout.write("\n")
    else:
        print(render_text(findings, stale, counts))
    fresh = [f for f in findings if not f.grandfathered]
    return 1 if (fresh or stale) else 0


if __name__ == "__main__":
    sys.exit(main())
