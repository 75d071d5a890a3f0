"""graftscope compile log: what jax traced, lowered and compiled, and when.

``setup_s`` (process start to the first measured step or request) is a
third of every benchmark run and had nothing beneath it; "which program
recompiled inside the timed window" had no answer at all.  jax already
reports every jaxpr trace, MLIR lowering, backend compile and persistent
cache request / hit / miss to ``jax.monitoring`` listeners, with the
function's name — this module is the one listener, and the in-memory log
behind it.

* :func:`install` registers the listeners once per process.  It is called
  from ``cli.enable_compilation_cache()`` (which every entry point and the
  benchmark's harness call first) and from nowhere else: no flag, no
  environment variable.  A listener costs a dict, a lock and an append per
  compile event (microseconds; PERF.md section 6 has the reading) and
  nothing per step.
* Each event becomes one record in a bounded deque: ``phase`` (``trace`` /
  ``lower`` / ``compile`` / ``cache_request`` / ``cache_hit`` /
  ``cache_miss``), ``fun_name`` where jax gives one (the three timed phases),
  ``t`` on ``time.perf_counter()`` at receipt (the END of a timed phase) and
  ``dur_s`` for the timed phases.
* :func:`snapshot` gives counts and seconds per phase over a window of
  ``t``, the seconds the process was busy tracing-and-lowering and
  compiling-or-loading, and the ten functions with the most seconds;
  :func:`summarize` is the same over any record list, so it is tested
  without jax.
* Where a telemetry stream is active each record is also a ``compile``
  event (``name`` = phase, ``fun``, ``dur_s``), which ``obs/report.py``
  folds into the run report's ``compiles`` section; where a metrics
  registry is active it feeds ``graft_compile_requests_total``,
  ``graft_compile_cache_misses_total`` and ``graft_compile_seconds{phase}``.
  With neither active nothing is written anywhere.

What the phases cover (jax 0.9.0, read from ``jax/_src`` and checked on the
v5e, PR 24):

* ``trace``: ``pjit._create_pjit_jaxpr``, Python tracing of one jitted
  function to a jaxpr.  Every ``jnp`` function is itself a jit, so tracing
  one train step reports about 4,000 inner traces inside the outer one's
  time; the log keeps only a thread's outermost trace, so one ``trace``
  record is one (re)traced program, or one eager op met at a new shape.  A
  cache hit on the jaxpr (same function, same avals) reports nothing.
* ``lower``: jaxpr to MLIR module (``pxla.lower_sharding_computation``).
* ``compile``: ``pxla._cached_compilation`` around
  ``compiler.compile_or_get_cached``: **compile or load**.  On a persistent
  cache hit it is the time to read, decompress and deserialize the
  executable onto the device (3-4 ms for an eager op, 0.35-0.65 s for a
  decode scan, 1.6 s for the CUB train step), on a miss the XLA compile
  plus the cache write (19 s for that step).  The count is the number of
  programs the process asked the backend for, hit or miss.
* ``cache_request`` / ``cache_hit``: ``compile_or_get_cached`` with a cache
  key / an entry found.  ``cache_miss`` is recorded by
  ``compilation_cache.put_executable_and_time`` when the entry is
  *written*, so a program under ``jax_persistent_cache_min_compile_time_secs``
  misses without a record (the benchmark sets that threshold to 0; with the
  CLI's default, ``cache_request - cache_hit`` is the truer count).  None of
  the three carries a function name; the ``compile`` record that follows
  them does.

Stdlib-only at import like the rest of ``obs/``: jax is imported inside
:func:`install`.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Dict, Iterable, List, Optional

from ..utils import locks
from . import metrics, telemetry

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
TIMED_EVENTS = {
    TRACE_EVENT: "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
COUNTED_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_request",
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
PHASES = tuple(TIMED_EVENTS.values()) + tuple(COUNTED_EVENTS.values())

#: one start of a trainer makes a few hundred records (five per program); the
#: bound is for week-long serve processes that retrace now and then
MAX_RECORDS = 8192
TOP_FUNCTIONS = 10

_lock = locks.TracedLock("compiles")
_tls = threading.local()        # .depth: open trace spans of this thread
_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_installed = False


def _record(phase: str, fun_name: Optional[str],
            dur_s: Optional[float]) -> None:
    rec = {"phase": phase, "fun_name": fun_name, "t": time.perf_counter(),
           "dur_s": dur_s}
    with _lock:
        _records.append(rec)
    tel = telemetry.get()
    if tel is not None:
        fields = {} if fun_name is None else {"fun": fun_name}
        if dur_s is not None:
            fields["dur_s"] = dur_s
        tel.event("compile", phase, **fields)
    reg = metrics.active()
    if reg is not None:
        if phase == "compile":
            reg.counter("graft_compile_requests_total",
                        "programs asked of the backend (compiled or "
                        "loaded from the persistent cache)").inc()
        elif phase == "cache_miss":
            reg.counter("graft_compile_cache_misses_total",
                        "programs compiled and written to the persistent "
                        "cache").inc()
        if dur_s is not None:
            reg.histogram("graft_compile_seconds",
                          "seconds per jax trace / lower / compile-or-load",
                          phase=phase).observe(dur_s)


def _program(fun_name) -> Optional[str]:
    """jax names the trace ``f`` and its lowering and compile ``jit(f)``:
    one name for the three, so that a function's seconds add up."""
    if fun_name is None:
        return None
    name = str(fun_name)
    return name[4:-1] if name.startswith("jit(") and name.endswith(")") \
        else name


def _on_scalar(event: str, value, **kwargs) -> None:
    # jax records a timed event's start time as a scalar when the span opens
    if event == TRACE_EVENT:
        _tls.depth = getattr(_tls, "depth", 0) + 1


def _on_duration(event: str, duration_secs: float, **kwargs) -> None:
    phase = TIMED_EVENTS.get(event)
    if phase is None:
        return
    if phase == "trace":
        # every jnp function is a jit: tracing one program reports thousands
        # of inner traces, whose time lies inside the outer one's.  Only the
        # outermost trace of a thread is a (re)traced program; the rest are
        # dropped here.
        depth = _tls.depth = max(getattr(_tls, "depth", 1) - 1, 0)
        if depth:
            return
    _record(phase, _program(kwargs.get("fun_name")), float(duration_secs))


def _on_event(event: str, **kwargs) -> None:
    phase = COUNTED_EVENTS.get(event)
    if phase is not None:
        _record(phase, None, None)


def install() -> None:
    """Register the listeners with ``jax.monitoring``, once per process."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_scalar)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def installed() -> bool:
    return _installed  # graftrace: unguarded (one bool read; it only ever goes False -> True)


def records() -> List[dict]:
    """A copy of the log, oldest first."""
    with _lock:
        return list(_records)


def _union_s(intervals) -> float:
    """Seconds covered by ``[(start, end)]``, overlaps counted once."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end > reach:
            total += end - max(start, reach)
            reach = end
    return total


def summarize(recs: Iterable[dict], since: Optional[float] = None,
              until: Optional[float] = None) -> dict:
    """Over the records with ``since <= t < until`` (None = unbounded):
    ``phases`` (count and summed seconds per phase), ``busy_s`` (seconds the
    process spent in ``trace_lower`` and in ``compile``, each the union of
    its records' ``[t - dur_s, t]``: an eager op run while a program is
    traced lies inside that trace and is counted once) and ``top`` (the
    functions with the most summed seconds over the timed phases).  Pure."""
    phases: Dict[str, dict] = {p: {"count": 0, "seconds": 0.0}
                               for p in PHASES}
    by_fun: Dict[str, dict] = {}
    spans: Dict[str, list] = {"trace_lower": [], "compile": []}
    n = 0
    for r in recs:
        t = r["t"]
        if (since is not None and t < since) or \
                (until is not None and t >= until):
            continue
        n += 1
        # setdefault: a stream written by a later version may name more
        row = phases.setdefault(r["phase"], {"count": 0, "seconds": 0.0})
        row["count"] += 1
        dur = r.get("dur_s")
        if dur is None:
            continue
        row["seconds"] += dur
        start = t - dur if since is None else max(t - dur, since)
        spans["compile" if r["phase"] == "compile"
              else "trace_lower"].append((start, t))
        if r.get("fun_name") is not None:
            fun = by_fun.setdefault(r["fun_name"],
                                    {"fun_name": r["fun_name"],
                                     "count": 0, "seconds": 0.0})
            fun["count"] += 1
            fun["seconds"] += dur
    top = sorted(by_fun.values(),
                 key=lambda f: (-f["seconds"], f["fun_name"]))
    return {"records": n, "phases": phases,
            "busy_s": {k: _union_s(v) for k, v in spans.items()},
            "top": top[:TOP_FUNCTIONS]}


def snapshot(since: Optional[float] = None,
             until: Optional[float] = None) -> dict:
    """:func:`summarize` over this process's log; ``since``/``until`` are
    ``time.perf_counter()`` readings."""
    return summarize(records(), since, until)


def clear() -> None:
    """Drop the log (tests; the listeners stay registered)."""
    with _lock:
        _records.clear()
