"""Least time of a whole decode tick of a window-and-global, routed trunk
(the held expert banks its rows touch, every other weight, the head's image
rows, the reachable keys and values averaged over the traced ticks'
positions, window-bounded in the window layers, over the memory bandwidth;
or its FLOPs if longer) over the measured device time of a tick."""
from benchmark import rooflines_laguna_s_2_1 as rooflines
from benchmark.layer_metrics._common import decode_tick_s, pct
from benchmark.layer_metrics._swa import windowed


def read(run):
    host = run.outcome.host
    tick_s = decode_tick_s(run, "jit_bench_decode",
                           host["decode_steps_traced"])
    if tick_s is None or run.peaks is None or not windowed(run):
        return None
    least = rooflines.tick_least_s(
        run.dalle_cfg, host["rows"], host.get("n_prime", 0),
        host["decode_steps_traced"], run.peaks)
    return pct(least["seconds"] / tick_s)
