"""Shared by the readers of the serve loop (``benchmark/drivers/serve.py``).

Three sources.  ``run.trace`` gives the device time of the arena's programs
under the names ``SlotArena.programs()`` hands out (``jit_serve_tick``,
``jit_serve_prefill``, ``jit_serve_admit``).  ``run.outcome.host`` carries
the traced stretch's ``serve.tick`` and ``serve.admit`` records from the
telemetry stream.  And the scheduler's own spans, ``graft:serve.<phase>``
(``serve/scheduler.py`` through ``obs/telemetry.py::_Span``), sit in the host
plane of the traced run's xplane on the device's clock:
``trace_reduce.extract`` keeps ``bench:`` spans only, so :func:`idle_by_phase`
opens the file again for them.  Every function returns None where there is
nothing to read (a CPU has no device plane; an older program writes no such
span or record)."""
from __future__ import annotations

import statistics

from benchmark import harness, trace_reduce

TICK = "jit_serve_tick"
ADMISSION = ("jit_serve_prefill", "jit_serve_admit")
SPAN_PREFIX = "graft:serve."
#: the phases of a step in which the scheduler itself may leave the device
#: with nothing to run: the blocking read of a retirement, and an admission
STALL_PHASES = ("retire", "admit")


def tick_s(run):
    """Median device seconds of one ``jit_serve_tick`` call."""
    program = None if run.trace is None else run.trace.program(TICK)
    return None if program is None else program["median_s"]


def admission_share_pct(run):
    if run.trace is None:
        return None
    found = [p for p in map(run.trace.program, ADMISSION) if p is not None]
    if not found:
        return None
    return 100.0 * sum(p["seconds"] for p in found) / run.trace.busy_s


def phase_spans(xplane_path) -> dict:
    """``{phase: [[start_ns, end_ns]]}`` of the ``graft:serve.`` host events
    and ``"busy"``: the disjoint intervals in which device 0 ran an op."""
    from jax.profiler import ProfileData

    spans, busy = {}, []
    for plane in ProfileData.from_file(str(xplane_path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name[len(SPAN_PREFIX):], []).append(
                            [int(e.start_ns),
                             int(e.start_ns) + int(e.duration_ns)])
        elif plane.name.startswith("/device:TPU") and not busy:
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    busy = trace_reduce.union(
                        [int(e.start_ns), int(e.start_ns) + int(e.duration_ns)]
                        for e in line.events)
    spans["busy"] = busy
    return spans


def idle_under(spans: dict, phases) -> int:
    """Nanoseconds device 0 ran nothing while the host was inside a span of
    one of ``phases``."""
    inside = trace_reduce.union(iv for p in phases for iv in spans.get(p, []))
    return trace_reduce.length(trace_reduce.subtract(inside, spans["busy"]))


def idle_by_phase(run):
    """``{"retire", "admit", "step", "window"}`` in seconds: device 0's idle
    time under the scheduler's retire and admit spans, under any
    ``serve.step`` (those two, the tick's dispatch and the bookkeeping
    between), and the traced window they are shares of.  None without a
    device plane or without the spans."""
    if run.trace is None:
        return None
    xplane = harness.Tracer(True, run.cell.name).xplane()
    if xplane is None:
        return None
    spans = phase_spans(xplane)
    if not spans["busy"] or "step" not in spans:
        return None
    out = {p: idle_under(spans, [p]) / 1e9 for p in STALL_PHASES + ("step",)}
    out["window"] = run.trace.window_s
    return out


def stall_pct(run):
    idle = idle_by_phase(run)
    if idle is None:
        return None
    return 100.0 * sum(idle[p] for p in STALL_PHASES) / idle["window"]


def occupancy_pct(run):
    ticks = run.outcome.host.get("traced_ticks")
    if not ticks:
        return None
    return 100.0 * sum(r["active_sum"] for r in ticks) / (
        sum(r["ticks"] for r in ticks) * run.outcome.host["rows"])


def queue_wait_ms(run):
    admits = run.outcome.host.get("traced_admits")
    if not admits:
        return None
    return 1e3 * statistics.median(r["queue_wait_s"] for r in admits)
