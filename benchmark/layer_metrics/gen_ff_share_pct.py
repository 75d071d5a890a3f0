"""Device time under ``graftprof:ff`` over device busy time."""
from benchmark.layer_metrics._common import scope_share_pct


def read(run):
    return scope_share_pct(run, "ff")
