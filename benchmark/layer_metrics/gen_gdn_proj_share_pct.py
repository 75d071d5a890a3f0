"""Device self time under ``graftprof:gdn-proj`` (the linear-attention
layers' projections in and out and the norm that closes the sublayer) over
device busy time."""
from benchmark.layer_metrics._common import scope_share_pct


def read(run):
    return scope_share_pct(run, "gdn-proj")
