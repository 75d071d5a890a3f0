#!/usr/bin/env python3
"""Print what a profiler trace holds, for a look by hand before trusting the
reduction: planes, their lines, the number of events, and the first events of
each line with every stat.  With ``--record <file>`` also write
``trace_reduce.extract``'s lists, cut to the first ``--keep-ms`` of device
time, as the small recording ``benchmark/tests/data/`` keeps.

    python3 benchmark/tools/dump_trace.py <file.xplane.pb> [--events 3]
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("--events", type=int, default=3)
    ap.add_argument("--record")
    ap.add_argument("--keep-ms", type=float, default=200.0)
    ap.add_argument("--scopes", help="the run's <cell>.scopes.json, kept "
                    "with the recording")
    args = ap.parse_args(argv)

    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    data = ProfileData.from_file(args.xplane)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:args.events]:
                stats = {k: (v if not isinstance(v, str) else v[:300])
                         for k, v in e.stats}
                print(f"    {e.name[:120]!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {stats}")
    if args.record:
        raw = trace_reduce.extract(args.xplane)
        if raw["devices"]:
            lo = min(op[1] for d in raw["devices"] for op in d["ops"])
            hi = lo + int(args.keep_ms * 1e6)
            for d in raw["devices"]:
                d["ops"] = [op for op in d["ops"] if op[1] < hi]
                d["modules"] = [m for m in d["modules"] if m[1] < hi]
                d["collectives"] = [c for c in d["collectives"] if c[1] < hi]
            raw["host_spans"] = [s for s in raw["host_spans"] if s[1] < hi]
        if args.scopes:
            used = {(op[3], op[0]) for d in raw["devices"] for op in d["ops"]}
            with open(args.scopes) as f:
                raw["scopes"] = {
                    program: {k: v for k, v in table.items()
                              if (program, k) in used}
                    for program, table in json.load(f).items()}
        Path(args.record).parent.mkdir(parents=True, exist_ok=True)
        with open(args.record, "w") as f:
            json.dump(raw, f, separators=(",", ":"))
        print(f"recorded {sum(len(d['ops']) for d in raw['devices'])} device "
              f"ops to {args.record}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
