"""Least time of a decode tick's recurrent updates (every Mamba layer's
float32 state and window read and written once, the small tensors read once,
over the memory bandwidth) over the device time of those updates per tick:
the self time under ``ssm-conv`` and ``ssm-scan`` plus the core's waits for
the transfers that feed them or that no scope claims
(``_ssm.update_seconds``: the time errs long, the share low)."""
from benchmark import rooflines_jamba2_3b as rooflines
from benchmark.layer_metrics._ssm import ticks_traced, update_seconds
from benchmark.layer_metrics._common import pct


def read(run):
    seconds, ticks = update_seconds(run), ticks_traced(run)
    if seconds is None or not ticks or run.peaks is None:
        return None
    least = rooflines.ssm_step_least_s(run.dalle_cfg,
                                       run.outcome.host["rows"], run.peaks)
    return pct(least["seconds"] / (seconds / ticks))
