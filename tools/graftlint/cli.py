"""graftlint driver: ``python -m tools.graftlint [paths...]``.

Runs every pass (or a ``--rule`` subset) over the scanned tree,
filters ``# graftlint: disable=`` sites and the baseline file, prints
text or ``--json`` and exits 0 (clean) / 1 (findings) / 2 (usage).

Baseline: ``tools/graftlint/baseline.json`` (or ``--baseline PATH``)
holds accepted finding fingerprints — rule + path + message, no line
number, so unrelated edits don't churn it.  The shipped baseline is
EMPTY on purpose: every violation the passes found on this tree was
fixed, not suppressed; the mechanism exists so a future PR that
inherits a violation it cannot fix in-scope can land without turning
the lint off (``--write-baseline`` regenerates it, and the diff shows
reviewers exactly what debt was accepted).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

from . import RULES, run_lint
from .core import REPO_ROOT, ScanContext, indexed_fingerprints


def _default_baseline(root: str) -> Optional[str]:
    p = os.path.join(root, "tools", "graftlint", "baseline.json")
    return p if os.path.exists(p) else None


def load_baseline(path: Optional[str]) -> set:
    if path is None or not os.path.exists(path):
        return set()
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return set(data.get("suppressed", []))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.graftlint",
        description="AST-only static analysis for the serving stack's "
                    "hand-maintained invariants")
    ap.add_argument("paths", nargs="*",
                    help="files/directories to scan (default: "
                         "paddle_tpu tools, under the repo root)")
    ap.add_argument("--root", default=None,
                    help="tree root for path resolution and display "
                         "(default: the repo root)")
    ap.add_argument("--rule", action="append", dest="rules",
                    metavar="RULE", choices=sorted(RULES),
                    help="run only this rule (repeatable)")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output")
    ap.add_argument("--list-rules", action="store_true",
                    help="print each rule and its invariant, then exit")
    ap.add_argument("--baseline", default=None, metavar="FILE",
                    help="findings baseline (default: "
                         "tools/graftlint/baseline.json when present)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write the current findings to the baseline "
                         "file and exit 0")
    try:
        args = ap.parse_args(argv)
    except SystemExit as e:
        return 0 if e.code in (0, None) else 2

    if args.list_rules:
        if args.json:
            print(json.dumps({"rules": [
                {"rule": k, "invariant": v[1]}
                for k, v in sorted(RULES.items())]}, indent=2))
        else:
            for k, (_fn, desc) in sorted(RULES.items()):
                print(f"{k:14s} {desc}")
        return 0

    root = os.path.abspath(args.root) if args.root else REPO_ROOT
    ctx = ScanContext(root, args.paths or None)
    findings = run_lint(ctx=ctx, rules=args.rules)

    baseline_path = args.baseline or _default_baseline(root)
    if args.write_baseline:
        path = args.baseline or os.path.join(
            root, "tools", "graftlint", "baseline.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 1,
                       "suppressed": sorted(
                           indexed_fingerprints(findings))},
                      f, indent=2)
            f.write("\n")
        print(f"graftlint: wrote {len(findings)} fingerprint(s) to "
              f"{path}")
        return 0

    suppressed = load_baseline(baseline_path)
    kept = [x for x, fp in zip(findings, indexed_fingerprints(findings))
            if fp not in suppressed]
    n_sup = len(findings) - len(kept)

    if args.json:
        print(json.dumps({
            "version": 1,
            "root": root,
            "rules": sorted(args.rules or RULES),
            "files": len(ctx.files),
            "suppressed": n_sup,
            "findings": [x.as_dict() for x in kept]}, indent=2))
    else:
        for x in kept:
            print(x.render())
        tail = f", {n_sup} suppressed by baseline" if n_sup else ""
        if kept:
            print(f"graftlint: {len(kept)} finding(s) over "
                  f"{len(ctx.files)} file(s){tail}")
        else:
            print(f"graftlint: OK ({len(ctx.files)} files, "
                  f"{len(args.rules or RULES)} rule(s){tail})")
    return 1 if kept else 0


if __name__ == "__main__":
    sys.exit(main())
