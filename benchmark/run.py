#!/usr/bin/env python3
"""Run one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic mix,
driver and per-layer metrics are files found by the names that entry gives
(``benchmark/README.md``).  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and,
traced, ``breakdown``.  Everything else goes to standard error and
``benchmark/out/``.

Without a TPU the run fails and prints no result, unless ``--rehearse`` is
given: that runs the cell's tiny twin on whatever jax finds and always prints
``"correct": false``, so that no CPU number can be taken for a metric.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny twin on any platform; never a result")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import harness, trace_reduce

    try:
        cell, devices, dalle_cfg, vae_cfg = harness.open_cell(
            args.workload, rehearse=args.rehearse)
    except harness.BenchError as e:
        log(f"cannot run: {e}")
        return 1
    peaks = (None if cell.rehearse and devices[0].platform != "tpu"
             else harness.load_peaks(devices[0].device_kind))
    tracer = harness.Tracer(bool(args.trace), cell.name)
    ready = {}

    def mark_ready(at=None):
        ready["setup_s"] = (time.perf_counter() if at is None else at) - T_START

    driver = harness.load_driver(cell)
    outcome = driver.run(cell, devices, dalle_cfg, vae_cfg, args.seed,
                         args.seconds, tracer, mark_ready)
    outcome.end_to_end["setup_s"] = ready["setup_s"]
    for note in outcome.notes:
        log(note)

    harness.OUT.mkdir(parents=True, exist_ok=True)
    reduced = None
    if tracer.on:
        xplane = tracer.xplane()
        raw = (trace_reduce.extract(xplane) if xplane is not None
               else {"devices": [], "host_spans": []})
        window = tracer.window[1] - tracer.window[0]
        scopes = {name: trace_reduce.scopes_of(program.as_text())
                  for name, program in outcome.programs.items()}
        reduced = trace_reduce.reduce(raw, window_s=window, scopes=scopes)
        with open(harness.OUT / f"{cell.name}.scopes.json", "w") as f:
            json.dump(scopes, f)
        if reduced is None:
            log("the trace holds no device operation")

    units = {m["name"]: m["unit"]
             for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if not tracer.on:
        for m in cell.end_to_end:
            value = outcome.end_to_end.get(m["name"])
            if value is not None:
                metrics[m["name"]] = value
    else:
        run = harness.Run(cell=cell, dalle_cfg=dalle_cfg, vae_cfg=vae_cfg,
                          devices=devices, peaks=peaks, outcome=outcome,
                          trace=reduced)
        for m in cell.per_layer:
            value = harness.load_reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = value

    result = {
        "correct": bool(outcome.correct) and not cell.rehearse,
        "attempted": int(outcome.attempted), "failed": int(outcome.failed),
        "metrics": {k: {"value": float(v), "unit": units[k]}
                    for k, v in metrics.items()},
        "device": harness.device_record(devices, reduced,
                                        outcome.memory_peak_bytes),
    }
    if reduced is not None:
        result["breakdown"] = reduced.breakdown
    detail = {"args": vars(args), "result": result, "host": outcome.host,
              "setup_s": ready["setup_s"],
              "wall_s": time.perf_counter() - T_START}
    if reduced is not None:
        detail["trace"] = {"scope_s": reduced.scope_s,
                           "program_s": reduced.program_s,
                           "busy_s": reduced.busy_s,
                           "window_s": reduced.window_s}
    with open(harness.OUT / f"{cell.name}.trace{args.trace}.json", "w") as f:
        json.dump(detail, f, indent=1, default=str)
    log(json.dumps(detail["host"], default=str))
    log(f"memory_stats {devices[0].memory_stats()}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
