"""Device self time under ``graftprof:mla-read`` (the absorbed read of the
latent cache: scores over latent and rotated key, softmax, the weighted sum
of the latent) over device busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._mla import READ, scope_seconds


def read(run):
    seconds = scope_seconds(run, READ)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
