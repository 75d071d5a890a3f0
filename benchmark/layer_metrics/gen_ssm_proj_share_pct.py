"""Device self time under ``graftprof:ssm-proj`` (the Mamba layers' input
and output projections and their norm) over device busy time."""
from benchmark.layer_metrics._common import scope_share_pct


def read(run):
    return scope_share_pct(run, "ssm-proj")
