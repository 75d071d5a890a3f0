#!/usr/bin/env python
"""Collect perf_ab run logs into a markdown table for PERF.md.

A chip session leaves one perf_ab stdout log per A/B; this tool parses
each log's ``medians:`` block and emits one markdown table so
A/B results land in PERF.md in a uniform format:

    python tools/collect_ab.py /tmp/chip_ab_core.log /tmp/chip_ab_pallas.log

Logs that contain no medians block (failed/truncated stage) are reported
on stderr and skipped — partial evidence is still collected.
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

# perf_ab median lines: `  name   123.45 img/s  (spread 120.00-130.00)`
MEDIAN_RE = re.compile(
    r"^\s{2}(?P<name>\S+)\s+(?P<median>\d+(?:\.\d+)?)\s(?P<unit>\S+)\s+"
    r"\(spread (?P<lo>\d+(?:\.\d+)?)-(?P<hi>\d+(?:\.\d+)?)\)\s*$")


def parse_log(text: str) -> list[dict]:
    """Return the medians rows of one perf_ab log (empty if none)."""
    rows = []
    in_medians = False
    for line in text.splitlines():
        if line.strip() == "medians:":
            in_medians = True
            rows = []  # keep only the LAST medians block of the log
            continue
        if in_medians:
            m = MEDIAN_RE.match(line)
            if m:
                rows.append(m.groupdict())
            elif line.strip():
                in_medians = False
    return rows


def to_markdown(results: dict[str, list[dict]]) -> str:
    lines = ["| run | variant | median | spread |", "|---|---|---|---|"]
    for run, rows in results.items():
        for r in rows:
            lines.append(
                f"| {run} | {r['name']} | {r['median']} {r['unit']} "
                f"| {r['lo']}-{r['hi']} |")
    return "\n".join(lines)


def main(argv=None) -> int:
    paths = [Path(p) for p in (argv if argv is not None else sys.argv[1:])]
    if not paths:
        print(__doc__.strip(), file=sys.stderr)
        return 2
    results: dict[str, list[dict]] = {}
    for p in paths:
        if not p.exists():
            print(f"skip {p}: no such file", file=sys.stderr)
            continue
        rows = parse_log(p.read_text(errors="replace"))
        if not rows:
            print(f"skip {p.name}: no medians block (stage failed or "
                  "still running?)", file=sys.stderr)
            continue
        run = p.stem.removeprefix("chip_")
        while run in results:  # same-named logs from different runs: keep both
            run += "'"
        results[run] = rows
    if not results:
        print("no parsable results in any input", file=sys.stderr)
        return 1
    print(to_markdown(results))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
