"""Least time of a decode tick (weights + reachable keys and values over the
memory bandwidth) over the measured device time of a tick."""
from benchmark.layer_metrics._common import decode_roofline_pct, decode_tick_s


def read(run):
    return decode_roofline_pct(run, decode_tick_s(
        run, "jit_bench_decode", run.outcome.host["decode_steps_traced"]))
