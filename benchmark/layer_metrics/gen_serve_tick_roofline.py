"""Least time of a decode tick over the arena's rows (weights + reachable
keys and values over the memory bandwidth: the static scan's least work,
whatever implements the tick) over the measured device time of an arena
tick."""
from benchmark.layer_metrics import _serve
from benchmark.layer_metrics._common import decode_roofline_pct


def read(run):
    return decode_roofline_pct(run, _serve.tick_s(run))
