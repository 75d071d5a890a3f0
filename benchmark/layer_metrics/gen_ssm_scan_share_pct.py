"""Device self time under ``graftprof:ssm-conv`` and ``graftprof:ssm-scan``
(the Mamba layers' convolution step and recurrent update) over device busy
time."""
from benchmark.layer_metrics._ssm import scan_seconds
from benchmark.layer_metrics._common import pct


def read(run):
    seconds = scan_seconds(run)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
