#!/usr/bin/env python
"""pthlo CLI — compiled-graph static analysis over the repo's fixtures.

    python tools/pthlo.py                  # --check: lower every
                                           # registered fixture, run the
                                           # graph passes, verify the
                                           # collective contract
    python tools/pthlo.py --json           # JSON report on stdout
    python tools/pthlo.py --out report.json  # artifact (the battery row
                                           # commits tools/graph_report.json)
    python tools/pthlo.py --write-contract # regenerate
                                           # tools/graph_contract.json
                                           # (review the diff!)
    python tools/pthlo.py --fixtures serving_chunked,llama_train
    python tools/pthlo.py --list           # registered fixtures

Exit codes: 0 = clean (no findings, contract matches), 1 = findings or
contract drift, 2 = usage.

Passes (paddle_tpu/analysis/graph): donation/aliasing audit,
collective-schedule extraction + contract, host-transfer & f64 lint,
per-param-class sharding report. Config shares ptlint's surface:
``[tool.ptlint.graph]`` in pyproject.toml (fixtures, thresholds,
contract path).

Host-only by design: the run is forced onto 8 virtual CPU devices (the
tests/conftest.py harness) BEFORE jax loads, so it never needs the
chip. The properties checked are lowering-structural, not timing.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
_xla_flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in _xla_flags:
    os.environ["XLA_FLAGS"] = (
        _xla_flags + " --xla_force_host_platform_device_count=8").strip()

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

from paddle_tpu.analysis import load_config  # noqa: E402
from paddle_tpu.analysis.graph import (  # noqa: E402
    GRAPH_FIXTURES, render_graph_text, run_graph)
from paddle_tpu.analysis.graph import contract as contract_mod  # noqa: E402
from paddle_tpu.analysis.graph.runner import graph_config  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="pthlo", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--root", default=_REPO,
                    help="repo root (default: the tools/ parent)")
    ap.add_argument("--check", action="store_true",
                    help="run passes + contract check (the default)")
    ap.add_argument("--write-contract", action="store_true",
                    help="regenerate the contract file from this run; "
                         "drift is superseded by the new file, but "
                         "donation/host/dtype findings still exit 1")
    ap.add_argument("--fixtures", default=None,
                    help="comma-separated subset of registered "
                         "fixtures")
    ap.add_argument("--list", action="store_true",
                    help="list registered fixtures and exit")
    ap.add_argument("--json", action="store_true",
                    help="JSON report on stdout instead of text")
    ap.add_argument("--out", default=None,
                    help="also write the JSON report to this path")
    ap.add_argument("--contract", default=None,
                    help="contract file (default from "
                         "[tool.ptlint.graph], else "
                         "tools/graph_contract.json)")
    ap.add_argument("--no-contract", action="store_true",
                    help="skip the contract comparison")
    args = ap.parse_args(argv)

    if args.list:
        for name in sorted(GRAPH_FIXTURES):
            fx = GRAPH_FIXTURES[name]
            print("%-26s devices>=%d %s%s" % (
                name, fx.needs_devices,
                "hot " if fx.hot else "", fx.doc))
        return 0
    if args.write_contract and args.no_contract:
        ap.error("--write-contract with --no-contract makes no sense")

    root = os.path.abspath(args.root)
    config = load_config(root)
    if args.contract:
        config.setdefault("graph", {})["contract"] = args.contract
    fixtures = None
    if args.fixtures:
        fixtures = [f.strip() for f in args.fixtures.split(",")
                    if f.strip()]
        unknown = [f for f in fixtures if f not in GRAPH_FIXTURES]
        if unknown:
            ap.error("unknown fixture(s) %s (have: %s)"
                     % (unknown, ",".join(sorted(GRAPH_FIXTURES))))
    if args.write_contract and fixtures:
        ap.error("--write-contract cannot be combined with "
                 "--fixtures: the contract is written whole, and a "
                 "subset run would silently drop every other "
                 "fixture's rows")

    report, findings = run_graph(
        root, config=config, fixtures=fixtures,
        check_contract=not (args.no_contract or args.write_contract))

    if args.write_contract:
        path = graph_config(config)["contract"]
        if not os.path.isabs(path):
            path = os.path.join(root, path)
        contract_mod.write(path, contract_mod.from_report(
            report["fixtures"]))
        print("pthlo: wrote contract for %d fixture(s) to %s"
              % (sum(1 for f in report["fixtures"].values()
                     if not f.get("skipped")),
                 os.path.relpath(path, root)))

    if args.out:
        with open(args.out, "w", encoding="utf-8") as f:
            json.dump(report, f, indent=1, sort_keys=True)
            f.write("\n")
    if args.json:
        json.dump(report, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        print(render_graph_text(report))
    if args.write_contract:
        # the refreshed contract supersedes drift; build/lint findings
        # — including the collectives pass's self-expectations
        # (collective-expectation) — still gate
        findings = [f for f in findings
                    if f.rule != contract_mod.RULE]
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
