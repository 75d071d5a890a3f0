"""Least time of a decode tick's delta-rule updates (every linear-attention
layer's float32 state and convolution window read and written once, the
small tensors read once, over the memory bandwidth; or the update's FLOPs if
longer) over the device time of those updates per tick: the self time under
``gdn-conv`` and ``gdn-state`` plus the core's waits for the transfers that
feed them or that no scope claims (``_gdn.update_seconds``: the time errs
long, the share low)."""
from benchmark import rooflines_olmo_hybrid_7b as rooflines
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._gdn import linear, update_seconds
from benchmark.layer_metrics._ssm import ticks_traced


def read(run):
    seconds, ticks = update_seconds(run), ticks_traced(run)
    if seconds is None or not ticks or run.peaks is None or not linear(run):
        return None
    least = rooflines.gdn_step_least_s(run.dalle_cfg,
                                       run.outcome.host["rows"], run.peaks)
    return pct(least["seconds"] / (seconds / ticks))
