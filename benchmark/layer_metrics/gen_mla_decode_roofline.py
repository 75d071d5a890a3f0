"""Least time of a whole decode tick of a latent, routed trunk (the held
expert banks its rows touch, every other weight, the head's image rows, the
reachable latent averaged over the traced ticks' positions, over the memory
bandwidth; or its FLOPs if longer) over the measured device time of a tick."""
from benchmark import rooflines_glm_4_7_flash as rooflines
from benchmark.layer_metrics._common import decode_tick_s, pct
from benchmark.layer_metrics._mla import latent


def read(run):
    host = run.outcome.host
    tick_s = decode_tick_s(run, "jit_bench_decode",
                           host["decode_steps_traced"])
    if tick_s is None or run.peaks is None or not latent(run):
        return None
    least = rooflines.tick_least_s(
        run.dalle_cfg, host["rows"], host.get("n_prime", 0),
        host["decode_steps_traced"], run.peaks)
    return pct(least["seconds"] / tick_s)
