"""Least time of one tick's expert layers (the bfloat16 banks of the experts
that the routing is expected to touch at these rows, the rows' activations,
over the memory bandwidth; or the chosen experts' FLOPs if longer) over the
device time of the expert products per tick: the self time under
``moe-experts`` plus the core's waits for the transfers that feed it or that
no scope claims (``_moe.experts_seconds``)."""
from benchmark import rooflines_smallthinker_21ba3b as rooflines
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._moe import experts_seconds, routed
from benchmark.layer_metrics._ssm import ticks_traced


def read(run):
    seconds, ticks = experts_seconds(run), ticks_traced(run)
    if seconds is None or not ticks or run.peaks is None or not routed(run):
        return None
    least = rooflines.moe_experts_least_s(run.dalle_cfg,
                                          run.outcome.host["rows"], run.peaks)
    return pct(least["seconds"] / (seconds / ticks))
