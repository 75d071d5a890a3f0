"""Perfetto export: the event stream as a Chrome trace (trace-event JSON).

``ui.perfetto.dev`` / ``chrome://tracing`` load the emitted document
directly, putting spans from every thread of every host on ONE zoomable
timeline — the step loop, the async checkpoint writer, and the serve
driver side by side, which is exactly the view the hung-run post-mortems
never had.

Mapping:

* trace ``pid``   = the record's ``host`` (process index); a process
  metadata event names it with the run id.
* trace ``tid``   = a stable small integer per (host, thread name), named
  by a thread metadata event — so "ckpt-async-700" and "MainThread" read
  as themselves.
* span B/E pairs  = one complete ``ph: "X"`` slice (ts from the B record's
  wall clock, dur from the E record's monotonic delta).  An UNPAIRED B —
  the kill-inside-a-span signature — becomes an instant marked
  ``(unfinished)`` so the death site is visible, not silent.
* other events    = thread-scoped instants (``ph: "i"``); ``step`` records
  additionally emit counter tracks (``ph: "C"``) for loss / step time /
  MFU / loader stall, so the perf trajectory is a plot over the same
  timeline.

Timestamps are wall-clock microseconds (``t``), the only clock comparable
across hosts; within a host, record ``seq`` already total-orders events
for readers that need causality tighter than clock resolution.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# counter tracks derived from step records: (field, track name)
_STEP_COUNTERS = (("loss", "loss"), ("step_time_s", "step_time_s"),
                  ("mfu", "mfu"), ("loader_stall_s", "loader_stall_s"))


def _payload(rec: dict) -> dict:
    """The record minus its envelope — what lands in the trace ``args``."""
    from .telemetry import ENVELOPE_KEYS

    skip = set(ENVELOPE_KEYS) | {"ph", "sid", "dur_s"}
    return {k: v for k, v in rec.items() if k not in skip}


def to_chrome_trace(events: List[dict]) -> dict:
    """Build the trace-event document from parsed records (the output of
    :func:`telemetry.read_events`)."""
    trace: List[dict] = []
    tids: Dict[Tuple[int, str], int] = {}
    named_pids: Dict[int, str] = {}

    def tid_for(host: int, thread: str) -> int:
        key = (host, thread)
        if key not in tids:
            tids[key] = len(tids) + 1
            trace.append({"ph": "M", "name": "thread_name", "pid": host,
                          "tid": tids[key], "args": {"name": thread}})
        return tids[key]

    # index span begins by (host, seq) so E records find their B
    begins: Dict[Tuple[int, int], dict] = {}
    for rec in events:
        if rec.get("ph") == "B" and rec.get("seq") is not None:
            begins[(rec.get("host", 0), rec["seq"])] = rec

    closed: set = set()
    for rec in events:
        host = rec.get("host", 0)
        if host not in named_pids:
            named_pids[host] = str(rec.get("run", ""))
            # aligned fleet merges relabel `host` to a unique lane index
            # (align.align_lane) and keep the stream's own index in
            # `orig_host` — name the pid lane with the original identity
            trace.append({"ph": "M", "name": "process_name", "pid": host,
                          "args": {"name": f"{rec.get('run', '')} "
                                           f"(host "
                                           f"{rec.get('orig_host', host)})"}})
        tid = tid_for(host, str(rec.get("thread", "?")))
        name = f"{rec.get('kind', '?')}.{rec.get('name', '?')}"
        ts = float(rec.get("t", 0.0)) * 1e6
        if rec.get("ph") == "E":
            b = begins.get((host, rec.get("sid", -1)))
            if b is not None:
                closed.add((host, rec["sid"]))
                trace.append({
                    "ph": "X", "name": name, "cat": str(rec.get("kind", "")),
                    "pid": host, "tid": tid_for(host, str(b.get("thread",
                                                               "?"))),
                    "ts": float(b.get("t", 0.0)) * 1e6,
                    "dur": max(float(rec.get("dur_s", 0.0)) * 1e6, 1.0),
                    "args": {**_payload(b), **_payload(rec)}})
            continue
        if rec.get("ph") == "B":
            continue  # emitted when its E arrives (or as unfinished below)
        trace.append({"ph": "i", "s": "t", "name": name,
                      "cat": str(rec.get("kind", "")), "pid": host,
                      "tid": tid, "ts": ts, "args": _payload(rec)})
        if rec.get("kind") == "step":
            for field, track in _STEP_COUNTERS:
                if rec.get(field) is not None:
                    trace.append({"ph": "C", "name": track, "pid": host,
                                  "tid": tid, "ts": ts,
                                  "args": {track: float(rec[field])}})

    # unpaired span begins: the process/thread died inside — surface it
    for (host, seq), b in begins.items():
        if (host, seq) in closed:
            continue
        name = f"{b.get('kind', '?')}.{b.get('name', '?')} (unfinished)"
        trace.append({"ph": "i", "s": "t", "name": name,
                      "cat": str(b.get("kind", "")), "pid": host,
                      "tid": tid_for(host, str(b.get("thread", "?"))),
                      "ts": float(b.get("t", 0.0)) * 1e6,
                      "args": _payload(b)})

    trace.sort(key=lambda e: (e["ph"] != "M", e.get("ts", 0.0)))
    return {"traceEvents": trace, "displayTimeUnit": "ms"}
