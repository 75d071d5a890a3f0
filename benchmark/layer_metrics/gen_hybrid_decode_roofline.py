"""Least time of a whole decode tick of a hybrid trunk (bfloat16 weights,
the table's image rows, the recurrent state's traffic, the attention layers'
reachable keys and values over the memory bandwidth, or its FLOPs if longer)
over the measured device time of a tick."""
from benchmark import rooflines_jamba2_3b as rooflines
from benchmark.layer_metrics._common import decode_tick_s, pct


def read(run):
    tick_s = decode_tick_s(run, "jit_bench_decode",
                           run.outcome.host["decode_steps_traced"])
    if (tick_s is None or run.peaks is None
            or getattr(run.dalle_cfg, "trunk", None) is None):
        return None
    least = rooflines.hybrid_tick_least_s(run.dalle_cfg,
                                          run.outcome.host["rows"], run.peaks)
    return pct(least["seconds"] / tick_s)
