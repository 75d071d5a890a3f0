"""Device self time under ``graftprof:attn-gate`` (each head's sigmoid gate
of the attention sublayer's input, and its product with the attended
values) over device busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._swa import GATE, scope_seconds


def read(run):
    seconds = scope_seconds(run, GATE)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
