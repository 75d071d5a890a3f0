"""Device time under ``graftprof:sample`` (temperature, the top-k filter's
sort, top-p, the categorical draw) over device busy time."""
from benchmark.layer_metrics._common import scope_share_pct


def read(run):
    return scope_share_pct(run, "sample")
