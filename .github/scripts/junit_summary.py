"""Render pytest --junitxml reports as a GitHub step-summary table.

Usage (inside a workflow step, after pytest wrote the report):

    python .github/scripts/junit_summary.py --title "tier1 (jnp)" \
        junit-*.xml

Appends one pass/fail table (plus the names of any failed tests) to
``$GITHUB_STEP_SUMMARY``; prints to stdout when the variable is unset so
the script is locally runnable. Missing report files are reported as a
row rather than crashing — a leg that died before pytest could write its
report should still produce a readable summary line.
"""
from __future__ import annotations

import argparse
import glob
import os
import sys
import xml.etree.ElementTree as ET


def collect(path: str) -> dict:
    root = ET.parse(path).getroot()
    # pytest writes <testsuites><testsuite .../></testsuites> (or a bare
    # <testsuite> on old versions) — aggregate over all suites.
    suites = [root] if root.tag == "testsuite" else root.findall("testsuite")
    agg = {"tests": 0, "failures": 0, "errors": 0, "skipped": 0,
           "time": 0.0, "failed_names": []}
    for s in suites:
        agg["tests"] += int(s.get("tests", 0))
        agg["failures"] += int(s.get("failures", 0))
        agg["errors"] += int(s.get("errors", 0))
        agg["skipped"] += int(s.get("skipped", 0))
        agg["time"] += float(s.get("time", 0.0))
        for case in s.iter("testcase"):
            if case.find("failure") is not None or case.find("error") is not None:
                agg["failed_names"].append(
                    f"{case.get('classname', '?')}::{case.get('name', '?')}")
    return agg


def render(title: str, reports: list[str]) -> tuple[str, bool]:
    lines = [f"### {title}", "",
             "| report | passed | failed | errors | skipped | time |",
             "|---|---:|---:|---:|---:|---:|"]
    failed_names, ok = [], True
    found = []
    for pattern in reports:
        found.extend(sorted(glob.glob(pattern)))
    if not found:
        lines.append("| _no junit report written_ | — | ❌ | — | — | — |")
        ok = False
    for path in found:
        try:
            a = collect(path)
        except ET.ParseError as exc:
            lines.append(f"| `{path}` (unparseable: {exc}) | — | ❌ | — | — | — |")
            ok = False
            continue
        passed = a["tests"] - a["failures"] - a["errors"] - a["skipped"]
        bad = a["failures"] + a["errors"]
        ok = ok and bad == 0
        lines.append(
            f"| `{os.path.basename(path)}` | {passed} "
            f"| {a['failures']}{' ❌' if a['failures'] else ''} "
            f"| {a['errors']}{' ❌' if a['errors'] else ''} "
            f"| {a['skipped']} | {a['time']:.1f}s |")
        failed_names.extend(a["failed_names"])
    if failed_names:
        lines += ["", "**Failed:**"] + [f"- `{n}`" for n in failed_names]
    lines.append("")
    return "\n".join(lines) + "\n", ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--title", default="test results")
    ap.add_argument("reports", nargs="+",
                    help="junit xml files (globs allowed)")
    args = ap.parse_args(argv)
    text, ok = render(args.title, args.reports)
    out = os.environ.get("GITHUB_STEP_SUMMARY")
    if out:
        with open(out, "a") as f:
            f.write(text)
    else:
        print(text, end="")
    # Informational: the pytest step's own exit code is the gate; a
    # summary renderer that failed the job again would double-report.
    return 0


if __name__ == "__main__":
    sys.exit(main())
