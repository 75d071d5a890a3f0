"""Shared by the readers of the Mamba-2 scopes (``ops/ssm.py::Mamba2Mixer``:
``ssd-proj``, ``ssd-conv``, ``ssd-state``).  A program without such scopes (a
checkout from before the Mamba-2 mixer, a configuration without Mamba-2 layers) reads
None and the metric is left out of the line.

``update_seconds`` is the device time of the state updates in the traced
stretch as ``_ssm.update_seconds`` counts a Mamba-1 layer's: the self time
under ``ssd-state`` plus the core's waits for the transfers that feed it or
that no scope claims (the time errs long, a roofline share over it low)."""
from __future__ import annotations

from benchmark import harness
from benchmark.layer_metrics import _ssm

STATE_SCOPES = ("ssd-state",)


def mamba2(run) -> bool:
    trunk = getattr(run.dalle_cfg, "trunk", None)
    return "mamba2" in getattr(trunk, "mixers", ())


def state_seconds(run):
    """Device self seconds under ``ssd-state`` in the traced stretch, or
    None where nothing ran under it."""
    if run.trace is None:
        return None
    return sum(run.trace.scope_s.get(s, 0.0) for s in STATE_SCOPES) or None


def update_seconds(run):
    seconds = state_seconds(run)
    program = run.outcome.programs.get(_ssm.PROGRAM)
    xplane = (harness.Tracer(True, run.cell.name).xplane()
              if run.cell is not None else None)
    if seconds is None or program is None or xplane is None:
        return seconds
    waits = _ssm._wait_seconds(xplane, program)
    return seconds + sum(waits.get(s, 0.0) for s in STATE_SCOPES + (None,))
