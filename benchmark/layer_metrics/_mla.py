"""Shared by the readers of the latent-attention scopes
(``ops/latent_attention.py``: ``mla-proj``, ``mla-read``).  A program without
such scopes (a checkout from before PR 38, a configuration without latent
layers) reads None and the metric is left out of the line.

``read_seconds`` is the device time of the latent reads in the traced stretch
as ``_moe.experts_seconds`` counts the experts': the self time under
``mla-read`` plus the core's waits for the transfers that feed it or that no
scope claims (the time errs long, a roofline share over it low)."""
from __future__ import annotations

from benchmark import harness
from benchmark.layer_metrics import _ssm
from benchmark.layer_metrics._moe import scope_seconds  # noqa: F401

READ, PROJ = "mla-read", "mla-proj"


def latent(run) -> bool:
    trunk = getattr(run.dalle_cfg, "trunk", None)
    return bool(getattr(trunk, "kv_rank", 0))


def read_seconds(run):
    seconds = scope_seconds(run, READ)
    program = run.outcome.programs.get(_ssm.PROGRAM)
    xplane = (harness.Tracer(True, run.cell.name).xplane()
              if run.cell is not None else None)
    if seconds is None or program is None or xplane is None:
        return seconds
    waits = _ssm._wait_seconds(xplane, program)
    return seconds + waits.get(READ, 0.0) + waits.get(None, 0.0)
