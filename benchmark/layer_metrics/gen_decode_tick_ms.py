"""Device time of the decode scan per token step."""
from benchmark.layer_metrics._common import decode_tick_s


def read(run):
    s = decode_tick_s(run, "jit_bench_decode",
                      run.outcome.host["decode_steps_traced"])
    return None if s is None else 1e3 * s
