"""Device self time under ``graftprof:moe-experts`` (the routed experts'
three products and their gate) over device busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._moe import EXPERTS, scope_seconds


def read(run):
    seconds = scope_seconds(run, EXPERTS)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
