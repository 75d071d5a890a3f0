"""Device self time under ``graftprof:ssd-proj`` (the Mamba-2 layers'
projections in and out and the norm that opens the sublayer) over device
busy time."""
from benchmark.layer_metrics._common import scope_share_pct


def read(run):
    return scope_share_pct(run, "ssd-proj")
