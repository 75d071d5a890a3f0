"""Device self time under ``graftprof:gdn-conv`` and ``graftprof:gdn-state``
(the linear-attention layers' convolution step and delta-rule update) over
device busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._gdn import state_seconds


def read(run):
    seconds = state_seconds(run)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
