#!/usr/bin/env python
"""graftscope run-report CLI: replay a telemetry stream into answers.

Reads one or more ``events.jsonl`` files (or stream directories — rotated
parts and per-host ``events-p{i}.jsonl`` files are merged) written by the
trainers / serve scheduler via ``dalle_pytorch_tpu.obs`` and renders:

* ``--format text`` (default) — the one-screen run report: step-time/MFU/
  stall trajectory + reservoir percentiles, health verdict timeline,
  checkpoint cadence/fallbacks/torn saves, serve p50/p99 per SLO class
  with attainment, injected faults, quarantines, torn spans.
* ``--format json``  — the same report as a machine-readable document
  (CI uploads this next to the crash-resume artifacts).
* ``--format trace`` — a Perfetto/Chrome trace (load in ui.perfetto.dev):
  spans from every thread of every host on one zoomable timeline.
* ``--tail N``       — just the last N records per host (the monitor
  uses this to carry a dead run's final moments into its own log).
* ``--merge DIR1 DIR2 …`` — the FLEET view: treat each path as one
  host's stream, solve the cross-host clock model from its beacons /
  matched step anchors (``obs/align.py``), rewrite every timestamp onto
  one fleet timebase, and render the merged result — text/json get the
  fleet report (per-lane offsets + residual bounds, global step
  timeline, straggler ranking, merged serve SLO attainment), trace gets
  one Perfetto document with one pid lane per host.

Stdlib + the jax-free ``obs`` package only: this tool must run without
claiming an accelerator, beside a run that is hung on one — that is
precisely when it is needed.

Usage:
    python tools/obs_report.py RUN_DIR [...]
    python tools/obs_report.py tel/ --format trace --output run.trace.json
    python tools/obs_report.py tel/ --tail 8

Exit codes: 0 report rendered, 2 no readable events.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dalle_pytorch_tpu.obs import (build_fleet_report,  # noqa: E402
                                   build_report, merge_streams, read_events,
                                   render_text, to_chrome_trace)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("paths", nargs="*", type=Path,
                        help="events.jsonl files or telemetry directories")
    parser.add_argument("--merge", nargs="+", type=Path, default=None,
                        metavar="DIR",
                        help="fleet mode: one telemetry dir per host — "
                             "align the streams onto one timebase "
                             "(obs/align.py clock solver) and render the "
                             "merged fleet report/trace")
    parser.add_argument("--format", choices=("text", "json", "trace"),
                        default="text")
    parser.add_argument("--output", type=Path, default=None,
                        help="write here instead of stdout")
    parser.add_argument("--tail", type=int, default=0,
                        help="print only the last N records per host "
                             "(one line each) instead of the report")
    args = parser.parse_args(argv)
    if not args.paths and not args.merge:
        parser.error("give stream paths, or --merge DIR1 DIR2 ...")

    clocks = None
    if args.merge:
        events, clocks = merge_streams(args.merge + args.paths)
    else:
        events = read_events(args.paths)
    if not events:
        srcs = [str(p) for p in (args.merge or []) + args.paths]
        print(f"no readable events under {srcs}", file=sys.stderr)
        return 2

    if args.tail > 0:
        hosts = sorted({(r.get("run"), r.get("host", 0)) for r in events})
        lines = []
        for run, host in hosts:
            tail = [r for r in events
                    if r.get("run") == run and r.get("host", 0) == host]
            for r in tail[-args.tail:]:
                extras = " ".join(
                    f"{k}={r[k]}" for k in ("step", "ph", "dur_s", "msg")
                    if r.get(k) is not None)
                lines.append(f"host {host} seq {r.get('seq')} "
                             f"[{r.get('kind')}.{r.get('name')}] {extras}")
        out = "\n".join(lines) + "\n"
    elif args.format == "trace":
        out = json.dumps(to_chrome_trace(events), indent=1)
    elif args.format == "json":
        rep = (build_fleet_report(events, clocks) if clocks is not None
               else build_report(events))
        out = json.dumps(rep, indent=1, default=str)
    else:
        rep = (build_fleet_report(events, clocks) if clocks is not None
               else build_report(events))
        out = render_text(rep)

    if args.output is not None:
        args.output.parent.mkdir(parents=True, exist_ok=True)
        args.output.write_text(out)
        print(f"wrote {args.output}")
    else:
        sys.stdout.write(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
