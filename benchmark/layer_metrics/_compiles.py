"""Shared by the readers of the program's compile log
(``dalle_pytorch_tpu/obs/compiles.py``: one ``jax.monitoring`` listener that
``cli.enable_compilation_cache`` installs, so ``harness.setup_jax_cache``
switches it on before the cell's first jit).

The run's ``ready`` instant on ``time.perf_counter()`` is the runner's
``T_START`` plus the ``setup_s`` it stamped; the timed window ends
``host["window_s"]`` later.  ``before_ready`` and ``in_window`` return the
log's summary over those stretches (``{"phases": {phase: {"count",
"seconds"}}, "busy_s": {"trace_lower", "compile"}, ...}``), or None where the
program has no such log or never installed it (a checkout from before PR 24):
the metric is then left out of the line.  A count that reads 0 is a reading."""
from __future__ import annotations

import sys


def _log():
    try:
        from dalle_pytorch_tpu.obs import compiles
    except ImportError:
        return None
    return compiles if compiles.installed() else None


def _ready(run):
    for name in ("__main__", "benchmark.run"):
        t_start = getattr(sys.modules.get(name), "T_START", None)
        if t_start is not None:
            break
    setup_s = run.outcome.end_to_end.get("setup_s")
    return None if t_start is None or setup_s is None else t_start + setup_s


def before_ready(run):
    log, ready = _log(), _ready(run)
    return None if log is None or ready is None else log.snapshot(until=ready)


def in_window(run):
    log, ready = _log(), _ready(run)
    window_s = run.outcome.host.get("window_s")
    if log is None or ready is None or window_s is None:
        return None
    return log.snapshot(since=ready, until=ready + window_s)


def busy_s(summary, what):
    """Seconds the process was inside ``trace_lower`` or ``compile`` spans,
    overlapping spans (a nested jit's trace inside its caller's) once."""
    return None if summary is None else float(summary["busy_s"][what])


def count(summary, phase):
    return None if summary is None else float(
        summary["phases"][phase]["count"])
