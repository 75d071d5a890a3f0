"""Shared by the readers of the routed-expert scopes (``ops/moe.py``:
``moe-route``, ``moe-experts``).  A program without such scopes (a checkout
from before PR 32, a configuration without routed experts) reads None and the
metric is left out of the line.

``experts_seconds`` is the device time of the expert products in the traced
stretch as ``_ssm.update_seconds`` counts a scope's: its self time plus the
core's waits for the transfers that feed it or that no scope claims (the time
errs long, a roofline share over it low)."""
from __future__ import annotations

from benchmark import harness
from benchmark.layer_metrics import _ssm

EXPERTS, ROUTE = "moe-experts", "moe-route"


def scope_seconds(run, scope):
    if run.trace is None:
        return None
    return run.trace.scope_s.get(scope) or None


def routed(run) -> bool:
    trunk = getattr(run.dalle_cfg, "trunk", None)
    return bool(getattr(trunk, "experts", 0))


def experts_seconds(run):
    seconds = scope_seconds(run, EXPERTS)
    program = run.outcome.programs.get(_ssm.PROGRAM)
    xplane = (harness.Tracer(True, run.cell.name).xplane()
              if run.cell is not None else None)
    if seconds is None or program is None or xplane is None:
        return seconds
    waits = _ssm._wait_seconds(xplane, program)
    return seconds + waits.get(EXPERTS, 0.0) + waits.get(None, 0.0)
