"""Least time of a whole decode tick of a trunk with Mamba-2 layers (bfloat16
weights with every held expert bank, the head's image rows, the Mamba-2
layers' state and window traffic, the attention layers' reachable keys and
values over the memory bandwidth, or its FLOPs if longer) over the measured
device time of a tick: the share of the whole step that bounds a later claim
in the cell."""
from benchmark import rooflines_nemotron_3_nano_30b_a3b as rooflines
from benchmark.layer_metrics._common import decode_tick_s, pct
from benchmark.layer_metrics._ssd import mamba2


def read(run):
    tick_s = decode_tick_s(run, "jit_bench_decode",
                           run.outcome.host["decode_steps_traced"])
    if tick_s is None or run.peaks is None or not mamba2(run):
        return None
    least = rooflines.tick_least_s(run.dalle_cfg, run.outcome.host["rows"],
                                   run.peaks)
    return pct(least["seconds"] / tick_s)
