"""Shared by the readers of a window-and-global trunk's attention
(``ops/attention.py``: ``attn-scores`` of its grouped-key reads,
``attn-gate``).  A configuration without such layers, or a program without
the scopes, reads None and the metric is left out of the line.

``read_seconds`` is the device time of the cache reads in the traced stretch
as ``_mla.read_seconds`` counts the latent's: the self time under
``attn-scores`` plus the core's waits for the transfers that feed it or that
no scope claims (the time errs long, a roofline share over it low)."""
from __future__ import annotations

from benchmark import harness
from benchmark.layer_metrics import _ssm
from benchmark.layer_metrics._moe import scope_seconds  # noqa: F401

READ, GATE = "attn-scores", "attn-gate"


def windowed(run) -> bool:
    """The trunk mixes window layers of their own head count with others."""
    trunk = getattr(run.dalle_cfg, "trunk", None)
    return bool(getattr(trunk, "window_heads", 0))


def read_seconds(run):
    seconds = scope_seconds(run, READ)
    program = run.outcome.programs.get(_ssm.PROGRAM)
    xplane = (harness.Tracer(True, run.cell.name).xplane()
              if run.cell is not None else None)
    if seconds is None or program is None or xplane is None:
        return seconds
    waits = _ssm._wait_seconds(xplane, program)
    return seconds + waits.get(READ, 0.0) + waits.get(None, 0.0)
