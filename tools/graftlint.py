#!/usr/bin/env python
"""graftlint CLI — TPU/JAX static analysis over this repo's bug history.

Runs the rule catalog in ``dalle_pytorch_tpu.lint`` (ENV001 env-truthiness,
SEED001 hash()-seeds, BACKEND001 import-time backend queries, DOT001
missing accumulation contracts, TRACE001 host syncs in traced code, EXC001
swallowed XLA errors) over the given files/directories.  Pure AST — no
backend init, no device calls, milliseconds per file once imported — so it
gates in CI and before any chip call without costing chip time.

Usage:
    python tools/graftlint.py dalle_pytorch_tpu tools chip_smoke.py \
        train_dalle.py genrank.py
    python tools/graftlint.py --select ENV001 --fix dalle_pytorch_tpu
    python tools/graftlint.py --write-baseline ...   # grandfather findings
    python tools/graftlint.py --format json --output lint.json ...  # CI
    python tools/graftlint.py --prune-baseline ...   # drop stale entries

Suppress a finding inline WITH a justification (enforced — a bare pragma
is itself an error):
    x = risky()  # graftlint: disable=RULE (why the rule does not apply)

Exit codes: 0 clean, 1 findings, 2 usage error.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.lint import (RULES, filter_baseline,  # noqa: E402
                                    findings_to_json, findings_to_sarif,
                                    fix_env001, iter_python_files,
                                    lint_paths, load_baseline, prune_baseline,
                                    stale_baseline_entries, write_baseline)

DEFAULT_BASELINE = REPO / ".graftlint-baseline.json"


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__.split("\n")[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    parser.add_argument("paths", nargs="*",
                        help="files/directories to lint")
    parser.add_argument("--select", type=str, default=None,
                        help="comma-separated rule subset (default: all)")
    parser.add_argument("--fix", action="store_true",
                        help="apply mechanical ENV001 rewrites "
                             "(os.environ.get truth-tests -> env_flag)")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="baseline file of grandfathered findings "
                             f"(default: {DEFAULT_BASELINE.name} at the "
                             "repo root, auto-loaded when present)")
    parser.add_argument("--write-baseline", action="store_true",
                        help="write the current findings to the baseline "
                             "file and exit 0")
    parser.add_argument("--prune-baseline", action="store_true",
                        help="drop baseline fingerprints matching no "
                             "current finding, then exit 0")
    parser.add_argument("--format", choices=("text", "json", "sarif"),
                        default="text",
                        help="findings output format (default: text; json "
                             "follows lint.FINDINGS_JSON_SCHEMA, sarif is "
                             "SARIF 2.1.0 for code-scanning UIs)")
    parser.add_argument("--output", type=Path, default=None,
                        help="write --format json/sarif document here "
                             "instead of stdout (text stays on stdout)")
    parser.add_argument("--list-rules", action="store_true")
    args = parser.parse_args(argv)

    if args.list_rules:
        for name, fn in RULES.items():
            doc = (fn.__doc__ or "").strip().split("\n")[0]
            print(f"{name}: {doc}")
        return 0
    if not args.paths:
        parser.error("no paths given")

    select = None
    if args.select:
        select = [r.strip().upper() for r in args.select.split(",")]
        unknown = [r for r in select if r not in RULES]
        if unknown:
            parser.error(f"unknown rule(s) {unknown}; known: {list(RULES)}")

    if args.fix:
        fixed_files = 0
        for f in iter_python_files(args.paths):
            src = f.read_text()
            new, n = fix_env001(src, path=str(f))
            if n:
                f.write_text(new)
                fixed_files += 1
                print(f"fixed {n} ENV001 site(s) in {f}")
        print(f"--fix: rewrote {fixed_files} file(s)")

    findings = lint_paths(args.paths, select=select)

    baseline_path = args.baseline or DEFAULT_BASELINE
    if args.write_baseline:
        write_baseline(findings, baseline_path)
        print(f"baseline: {len(findings)} finding(s) -> {baseline_path}")
        return 0
    if args.prune_baseline:
        stale = prune_baseline(findings, baseline_path)
        print(f"--prune-baseline: dropped {len(stale)} stale "
              f"fingerprint(s) from {baseline_path}")
        for fp in stale:
            print(f"  {fp}")
        return 0
    baseline = load_baseline(baseline_path)
    stale = stale_baseline_entries(findings, baseline)
    findings = filter_baseline(findings, baseline)

    n_files = len(iter_python_files(args.paths))
    if args.format != "text":
        doc = (findings_to_json(findings, files_scanned=n_files)
               if args.format == "json" else findings_to_sarif(findings))
        text = json.dumps(doc, indent=2) + "\n"
        if args.output:
            args.output.write_text(text)
            print(f"{args.format} findings -> {args.output}")
        else:
            sys.stdout.write(text)
    else:
        for f in findings:
            print(f.format())
    # stale entries warn (stderr — machine formats keep a clean stdout)
    # but don't fail the run: they mask nothing yet, they only risk
    # shadowing a future same-line regression
    for fp in stale:
        print(f"warning: stale baseline entry {fp} matches no current "
              "finding (prune with --prune-baseline)", file=sys.stderr)
    if findings:
        if args.format == "text":
            counts: dict = {}
            for f in findings:
                counts[f.rule] = counts.get(f.rule, 0) + 1
            summary = ", ".join(
                f"{r}: {n}" for r, n in sorted(counts.items()))
            print(f"\n{len(findings)} finding(s) ({summary})")
        return 1
    if args.format == "text":
        print(f"graftlint: clean ({n_files} files)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
