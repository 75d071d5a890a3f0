"""Device self time under ``graftprof:moe-route`` (router product, softmax,
top-k, renormalise, sort, dispatch and combine) over device busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._moe import ROUTE, scope_seconds


def read(run):
    seconds = scope_seconds(run, ROUTE)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
