"""CLI for tpuml-lint: ``python -m tpuml_lint <paths>``.

Exit status: 0 when every finding is baselined (target: the committed
baseline is empty), 1 when new findings exist, 2 on usage errors.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List

from . import ALL_RULES, __version__, run
from .core import apply_baseline, load_baseline, write_baseline
from .envinfo import repo_root_from

_DEFAULT_BASELINE = os.path.join(os.path.dirname(__file__), "baseline.json")


def main(argv: List[str] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tpuml_lint",
        description="AST-based invariant checker for spark-tpu-ml "
                    "(rule catalog: docs/static_analysis.md)",
    )
    ap.add_argument("paths", nargs="*", help="files or directories to lint")
    ap.add_argument(
        "--baseline", default=_DEFAULT_BASELINE,
        help="grandfathered-findings file (default: the committed "
             "tpuml_lint/baseline.json)",
    )
    ap.add_argument(
        "--no-baseline", action="store_true",
        help="report every finding, ignoring the baseline",
    )
    ap.add_argument(
        "--write-baseline", action="store_true",
        help="rewrite the baseline file from the current findings and "
             "exit 0 (use only when intentionally grandfathering)",
    )
    ap.add_argument(
        "--rule", action="append", default=[], metavar="TPU00N",
        help="restrict to the given rule code (repeatable)",
    )
    ap.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit",
    )
    ap.add_argument("--version", action="version", version=__version__)
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule in ALL_RULES:
            doc = (rule.__doc__ or "").strip().splitlines()[0]
            print(f"{rule.CODE}  {rule.NAME:<16} {doc}")
        return 0

    if not args.paths:
        ap.error("no paths given (try: python -m tpuml_lint "
                 "spark_rapids_ml_tpu tests benchmark_runner.py)")

    repo_root = repo_root_from(os.getcwd()) or repo_root_from(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    if repo_root is None:
        print("tpuml_lint: cannot locate the repo root "
              "(spark_rapids_ml_tpu/runtime/envspec.py not found)",
              file=sys.stderr)
        return 2

    findings, _ = run(args.paths, repo_root, rules=args.rule)

    if args.write_baseline:
        write_baseline(args.baseline, findings)
        print(f"wrote {len(findings)} finding(s) to {args.baseline}")
        return 0

    baseline = [] if args.no_baseline else load_baseline(args.baseline)
    new, stale = apply_baseline(findings, baseline)

    for f in new:
        print(f.render())
    for path, rule, context in stale:
        print(f"note: stale baseline entry ({rule} {path}: {context!r}) — "
              f"remove it from {os.path.relpath(args.baseline, repo_root)}")

    n_base = len(findings) - len(new)
    if new:
        print(f"\ntpuml_lint: {len(new)} new finding(s)"
              + (f", {n_base} baselined" if n_base else ""))
        return 1
    print(f"tpuml_lint: ok ({len(findings)} finding(s), all baselined)"
          if findings else "tpuml_lint: ok")
    return 0


if __name__ == "__main__":
    sys.exit(main())
