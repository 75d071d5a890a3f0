"""Least time of one tick's reads of the reachable keys and values (their
bytes, averaged over the traced ticks' positions and bounded by the window
in the window layers, over the memory bandwidth; or the reads' FLOPs if
longer) over the device time of the reads per tick: the self time under
``attn-scores`` plus the core's waits for the transfers that feed it or that
no scope claims (``_swa.read_seconds``)."""
from benchmark import rooflines_laguna_s_2_1 as rooflines
from benchmark.layer_metrics._common import pct
from benchmark.layer_metrics._ssm import ticks_traced
from benchmark.layer_metrics._swa import read_seconds, windowed


def read(run):
    seconds, ticks = read_seconds(run), ticks_traced(run)
    if seconds is None or not ticks or run.peaks is None or not windowed(run):
        return None
    host = run.outcome.host
    least = rooflines.read_least_s(
        run.dalle_cfg, host["rows"], host.get("n_prime", 0),
        host["decode_steps_traced"], run.peaks)
    return pct(least["seconds"] / (seconds / ticks))
