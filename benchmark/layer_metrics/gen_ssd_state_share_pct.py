"""Device self time under ``graftprof:ssd-state`` (the Mamba-2 layers'
decays, state update and read-out, ``D``, gate and grouped norm) over device
busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._ssd import state_seconds


def read(run):
    seconds = state_seconds(run)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
