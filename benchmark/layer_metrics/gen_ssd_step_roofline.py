"""Least time of a decode tick's Mamba-2 state updates (every Mamba-2
layer's float32 state read and written once, the update's inputs and output
moved once, over the memory bandwidth; or the update's FLOPs if longer) over
the device time of those updates per tick: the self time under ``ssd-state``
plus the core's waits for the transfers that feed it or that no scope claims
(``_ssd.update_seconds``: the time errs long, the share low)."""
from benchmark import rooflines_nemotron_3_nano_30b_a3b as rooflines
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._ssd import mamba2, update_seconds
from benchmark.layer_metrics._ssm import ticks_traced


def read(run):
    seconds, ticks = update_seconds(run), ticks_traced(run)
    if seconds is None or not ticks or run.peaks is None or not mamba2(run):
        return None
    least = rooflines.ssd_step_least_s(run.dalle_cfg,
                                       run.outcome.host["rows"], run.peaks)
    return pct(least["seconds"] / (seconds / ticks))
