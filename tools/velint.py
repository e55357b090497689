#!/usr/bin/env python
"""velint — the project static gate (analysis passes 3-5;
docs/ANALYSIS.md).

Default run lints `veles_tpu/` + `tools/` — the per-file
AST rules (pass 3), the whole-program concurrency pass (pass 4:
shared-state races, lock-order cycles, wait-under-lock) and the
protocol pass (pass 5: HTTP endpoint token/body contracts, thread-owner
stop() teardown) — and exits nonzero on ANY unsuppressed finding. `--ci` is the ratchet gate: it compares against
the checked-in baseline (`tools/velint_baseline.json`) and fails only on
NEW findings, so a legacy finding never blocks an unrelated PR while a
fresh one always does. `--write-baseline` regenerates the baseline from
the current tree (do this only when deliberately accepting findings).

    tools/velint.py                 # lint, fail on any finding
    tools/velint.py --ci            # CI gate: fail on NEW findings only
    tools/velint.py --json          # machine-readable findings
    tools/velint.py path/to/file.py # lint specific files/dirs

Pure stdlib + veles_tpu.analysis.lint (no jax import): fast enough to
run on every commit.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO_ROOT)

from veles_tpu.analysis import concurrency  # noqa: E402
from veles_tpu.analysis import lint  # noqa: E402
from veles_tpu.analysis import protocol  # noqa: E402

#: the gate's passes: the per-file AST lint plus the whole-program
#: concurrency (shared-state races, lock order) and protocol (endpoint
#: contracts, thread-owner teardown) passes — ONE findings stream, one
#: ratchet baseline, one suppression syntax
PASSES = ("lint", "concurrency", "protocol")

DEFAULT_PATHS = ("veles_tpu", "tools")
DEFAULT_BASELINE = os.path.join(_REPO_ROOT, "tools",
                                "velint_baseline.json")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="velint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("paths", nargs="*",
                   help="files/directories to lint (default: "
                        "veles_tpu/ + tools/)")
    p.add_argument("--ci", action="store_true",
                   help="ratchet gate: fail only on findings NEW vs the "
                        "baseline")
    p.add_argument("--baseline", default=DEFAULT_BASELINE,
                   help="baseline file for --ci / --write-baseline")
    p.add_argument("--write-baseline", action="store_true",
                   help="accept the current findings as the new "
                        "baseline and exit 0")
    p.add_argument("--json", action="store_true",
                   help="emit findings as JSON instead of text")
    args = p.parse_args(argv)

    paths = args.paths or [os.path.join(_REPO_ROOT, d)
                           for d in DEFAULT_PATHS]
    findings = lint.lint_paths(paths, root=_REPO_ROOT)
    findings += concurrency.analyze_paths(paths, root=_REPO_ROOT)
    findings += protocol.analyze_paths(paths, root=_REPO_ROOT)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))

    if args.write_baseline:
        lint.write_baseline(args.baseline, findings)
        print(f"velint: baseline written to {args.baseline} "
              f"({len(findings)} finding(s))")
        return 0

    if args.ci:
        baseline = lint.load_baseline(args.baseline)
        fresh, over = lint.new_findings(findings, baseline)
        reported, label = fresh, "new "
    else:
        reported, label = findings, ""

    if args.json:
        print(json.dumps({"findings": [f.as_dict() for f in reported],
                          "total": len(findings),
                          "passes": list(PASSES),
                          "new": len(reported) if args.ci else None}))
    else:
        for f in reported:
            print(f.format())
        print(f"velint: {len(reported)} {label}finding(s)"
              + (f" ({len(findings)} total incl. baselined)"
                 if args.ci and len(findings) != len(reported) else ""))
    return 1 if reported else 0


if __name__ == "__main__":
    sys.exit(main())
