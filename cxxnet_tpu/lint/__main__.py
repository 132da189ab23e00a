"""CLI: ``python -m cxxnet_tpu.lint [paths...]``.

Exit codes: 0 clean, 1 findings, 2 usage error (argparse owns 2)."""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

from .core import (LintError, all_checks, render_human, render_json,
                   run_lint, write_baseline)

_DEFAULT_BASELINE = os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "baseline.json")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m cxxnet_tpu.lint",
        description="cxxlint: framework-aware static analysis "
                    "(doc/static_analysis.md)")
    p.add_argument("paths", nargs="*", default=None,
                   help="files/directories to scan (default: "
                        "cxxnet_tpu/ and tools/ under the cwd)")
    p.add_argument("--format", choices=("human", "json"),
                   default="human")
    p.add_argument("--select", default=None, metavar="CODES",
                   help="comma list of check codes to run "
                        "(e.g. CXL002,CXL006)")
    p.add_argument("--doc-dir", default="doc",
                   help="markdown reference pages for the config-drift "
                        "check (default: ./doc; skipped if absent)")
    p.add_argument("--baseline", default=None, metavar="PATH",
                   help="baseline file of grandfathered findings "
                        "(default: the committed "
                        "cxxnet_tpu/lint/baseline.json)")
    p.add_argument("--no-baseline", action="store_true",
                   help="ignore any baseline file")
    p.add_argument("--write-baseline", action="store_true",
                   help="write the current findings to the baseline "
                        "file and exit 0")
    p.add_argument("--list-checks", action="store_true",
                   help="describe the registered checks and exit")
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = build_parser().parse_args(argv)
    if args.list_checks:
        for c in all_checks():
            print("%s  %-18s %s" % (c.code, c.name,
                                    c.doc.splitlines()[0] if c.doc
                                    else ""))
        return 0
    paths = args.paths or [p for p in ("cxxnet_tpu", "tools")
                           if os.path.isdir(p)]
    if not paths:
        print("cxxlint: no paths given and no default targets found "
              "in the cwd", file=sys.stderr)
        return 2
    baseline = None
    if not args.no_baseline and not args.write_baseline:
        baseline = args.baseline or (
            _DEFAULT_BASELINE if os.path.isfile(_DEFAULT_BASELINE)
            else None)
    select = None
    if args.select:
        select = [c.strip() for c in args.select.split(",")
                  if c.strip()]
    doc_dir = args.doc_dir if os.path.isdir(args.doc_dir) else None
    try:
        result = run_lint(paths, doc_dir=doc_dir,
                          baseline_path=baseline, select=select)
    except LintError as e:
        print("cxxlint: %s" % e, file=sys.stderr)
        return 2
    if args.write_baseline:
        path = args.baseline or _DEFAULT_BASELINE
        write_baseline(path, result.findings)
        print("cxxlint: wrote %d finding(s) to %s"
              % (len(result.findings), path))
        return 0
    out = render_json(result) if args.format == "json" \
        else render_human(result)
    print(out)
    return 1 if result.findings else 0


if __name__ == "__main__":
    sys.exit(main())
