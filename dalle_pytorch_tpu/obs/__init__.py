"""graftscope: unified run telemetry (DESIGN.md §14) + the fleet layer
(DESIGN.md §16).

``telemetry`` is the write side (run-scoped JSONL event stream + spans +
the module-level singleton every layer emits into), ``report`` and
``trace_export`` are the read side (run report, Perfetto/Chrome trace).
The fleet layer rides the same stream: ``align`` solves per-host clock
models from beacons and merges N streams onto one timebase, ``metrics``
exposes an in-process /metrics + /healthz endpoint fed by the emit path,
and ``alerts`` evaluates declarative threshold/burn-rate rules over
sliding windows, emitting ``alert`` events back into the stream.
Stdlib-only by design: every half of this must be writable and readable
without touching jax — a reader must never claim (or wait on) the
accelerator the run it inspects is using.
"""
from . import align, alerts, metrics, telemetry
from .align import LaneClock, merge_streams, solve_alignment
from .report import build_fleet_report, build_report, render_text
from .telemetry import (EVENT_SCHEMA, SCHEMA_VERSION, Telemetry,
                        clock_beacon_payload, emit, get, init, note,
                        read_events, shutdown, span)
from .trace_export import to_chrome_trace

__all__ = [
    "telemetry", "align", "alerts", "metrics", "Telemetry", "EVENT_SCHEMA",
    "SCHEMA_VERSION", "init", "get", "shutdown", "emit", "span", "note",
    "read_events", "clock_beacon_payload", "build_report",
    "build_fleet_report", "render_text", "to_chrome_trace", "LaneClock",
    "merge_streams", "solve_alignment",
]
