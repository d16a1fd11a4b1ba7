"""``python -m repro.analysis`` — run every checker, print findings,
exit nonzero when any survive suppression.

Zero third-party dependencies beyond what the repo already ships: the
AST rules are pure stdlib; the kernel contract checker imports jax (to
abstractly drive the Pallas seams) only when the kernel sources are in
scope and ``--no-kernel-checks`` is not given.
"""
from __future__ import annotations

import argparse
import ast
import json
import os
import sys

from repro.analysis import jitgeo, tracelint
from repro.analysis.astutil import iter_py_files
from repro.analysis.findings import (
    RULES,
    Finding,
    apply_suppressions,
    scan_suppressions,
)

DEFAULT_PATHS = ("src", "benchmarks", "examples")
_KERNEL_SOURCE = os.path.join("kernels", "dpp_greedy", "tiled.py")


def run_analysis(
    paths: list[str], kernel_checks: bool = True
) -> tuple[list[Finding], dict]:
    """Run all checkers over ``paths``.  Returns (findings after
    suppression, summary dict)."""
    files = list(iter_py_files(paths))
    findings: list[Finding] = []
    suppressions: dict[str, dict[int, set[str]]] = {}
    geometry_summaries: list[dict] = []
    session_summaries: list[dict] = []
    skipped: list[str] = []

    for path in files:
        with open(path, encoding="utf-8") as fh:
            text = fh.read()
        supp, bad = scan_suppressions(path, text)
        suppressions[path] = supp
        findings.extend(bad)
        try:
            tree = ast.parse(text, filename=path)
        except SyntaxError:
            skipped.append(path)
            continue
        findings.extend(tracelint.check_module(path, tree))
        findings.extend(jitgeo.check_module(path, tree))
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                summary = jitgeo.router_geometry_summary(node)
                if summary is not None:
                    summary["path"] = path
                    geometry_summaries.append(summary)
                summary = jitgeo.session_geometry_summary(node)
                if summary is not None:
                    summary["path"] = path
                    session_summaries.append(summary)

    kernel_summary: dict | None = None
    if kernel_checks and any(p.endswith(_KERNEL_SOURCE) for p in files):
        from repro.analysis.kernels import check_kernel_contracts

        kernel_findings, kernel_summary = check_kernel_contracts()
        findings.extend(kernel_findings)

    findings = apply_suppressions(findings, suppressions)
    summary = {
        "files": len(files),
        "skipped_syntax": skipped,
        "router_geometry": geometry_summaries,
        "session_geometry": session_summaries,
        "kernel_contracts": kernel_summary,
        "findings": len(findings),
    }
    return sorted(set(findings)), summary


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="Static checks: Pallas kernel contracts, jit "
                    "geometry, trace safety.",
    )
    parser.add_argument(
        "paths", nargs="*",
        help=f"files/directories to check (default: "
             f"{' '.join(DEFAULT_PATHS)})",
    )
    parser.add_argument(
        "--error-on-findings", action="store_true",
        help="exit 1 when findings survive suppression (this is the "
             "default behaviour; the flag exists so CI lanes state "
             "their gate explicitly)",
    )
    parser.add_argument(
        "--no-kernel-checks", action="store_true",
        help="skip the dynamic Pallas contract checker (AST rules only)",
    )
    parser.add_argument("--json", action="store_true",
                        help="machine-readable output")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalog and exit")
    parser.add_argument("--verbose", action="store_true",
                        help="print the geometry/coverage summaries")
    args = parser.parse_args(argv)

    if args.list_rules:
        for rule in sorted(RULES):
            print(f"{rule}: {RULES[rule]}")
        return 0

    paths = args.paths or [p for p in DEFAULT_PATHS if os.path.exists(p)]
    if not paths:
        print("no paths to check", file=sys.stderr)
        return 2

    findings, summary = run_analysis(
        paths, kernel_checks=not args.no_kernel_checks
    )

    if args.json:
        print(json.dumps({
            "findings": [dataclass_dict(f) for f in findings],
            "summary": summary,
        }, indent=2, default=str))
    else:
        for f in findings:
            print(f.format())
        tail = (f"{summary['files']} files checked, "
                f"{len(findings)} finding(s)")
        if summary["kernel_contracts"]:
            kc = summary["kernel_contracts"]
            tail += (f"; kernel contracts: {kc['geometries']} geometries "
                     f"across {len(kc['families'])} families")
        for geo in summary["router_geometry"]:
            if geo.get("reachable_geometries") == 1:
                tail += (f"; {geo['class']}: 1 reachable compiled "
                         f"geometry ({geo['launch_sites']} launch site)")
        for geo in summary["session_geometry"]:
            if geo.get("reachable_geometries") == 1:
                fams = sum(1 for n in geo["launch_sites"].values() if n)
                tail += (f"; {geo['class']}: 1 reachable compiled "
                         f"geometry per (shape, chunk) "
                         f"({fams} launch families)")
        print(tail)
        if args.verbose:
            print(json.dumps(summary, indent=2, default=str))

    return 1 if findings else 0


def dataclass_dict(f: Finding) -> dict:
    return {"path": f.path, "line": f.line, "rule": f.rule,
            "message": f.message}


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
