"""Least time of one tick's reads of the latent (the reachable latent's
bytes, averaged over the traced ticks' positions, over the memory bandwidth;
or the absorbed products' FLOPs if longer) over the device time of the reads
per tick: the self time under ``mla-read`` plus the core's waits for the
transfers that feed it or that no scope claims (``_mla.read_seconds``)."""
from benchmark import rooflines_glm_4_7_flash as rooflines
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._mla import latent, read_seconds
from benchmark.layer_metrics._ssm import ticks_traced


def read(run):
    seconds, ticks = read_seconds(run), ticks_traced(run)
    if seconds is None or not ticks or run.peaks is None or not latent(run):
        return None
    host = run.outcome.host
    least = rooflines.mla_read_least_s(
        run.dalle_cfg, host["rows"], host.get("n_prime", 0),
        host["decode_steps_traced"], run.peaks)
    return pct(least["seconds"] / (seconds / ticks))
