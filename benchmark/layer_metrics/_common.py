"""Shared by the per-layer readers.  A reader is ``read(run) -> float | None``
with ``run`` a ``harness.Run``; None means there was nothing to read and the
metric is left out of the line.  One file per metric, named as the metric."""
from __future__ import annotations

from benchmark import rooflines


def pct(share):
    return None if share is None else 100.0 * share


def scope_share_pct(run, scope):
    return None if run.trace is None else pct(run.trace.scope_share(scope))


def unscoped_share_pct(run):
    from benchmark.trace_reduce import UNSCOPED

    return scope_share_pct(run, UNSCOPED)


def device_idle_pct(run):
    return None if run.trace is None else pct(run.trace.idle_share)


def hbm_planned_gb(run):
    """Arguments + temporaries + outputs the compiler plans for the cell's
    main program, less what it aliases: a compiler's count, not a
    measurement of the device."""
    program = run.outcome.programs.get(run.outcome.main_program)
    if program is None:
        return None
    m = program.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) / 1e9


def program_share_pct(run, name):
    if run.trace is None:
        return None
    program = run.trace.program(name)
    return None if program is None else pct(
        program["seconds"] / run.trace.busy_s)


def decode_tick_s(run, name, ticks_per_call):
    """Device seconds of one decode tick: the decode program's device time
    per call over the token steps a call makes."""
    if run.trace is None:
        return None
    program = run.trace.program(name)
    if program is None or not program["calls"]:
        return None
    return program["seconds"] / program["calls"] / ticks_per_call


def decode_roofline_pct(run, tick_s):
    if tick_s is None or run.peaks is None:
        return None
    least = rooflines.decode_tick_least_s(run.dalle_cfg,
                                          run.outcome.host["rows"], run.peaks)
    return pct(least["seconds"] / tick_s)
