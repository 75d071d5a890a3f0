"""Device self time under ``graftprof:mla-proj`` (latent attention's five
products, two norms and rotation, and the absorbed ``q_lat`` and ``o_lat
W_uv``) over device busy time."""
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._mla import PROJ, scope_seconds


def read(run):
    seconds = scope_seconds(run, PROJ)
    return None if seconds is None else pct(seconds / run.trace.busy_s)
