"""Device time under ``graftprof:attn-scores`` over device busy time."""
from benchmark.layer_metrics._common import scope_share_pct


def read(run):
    return scope_share_pct(run, "attn-scores")
