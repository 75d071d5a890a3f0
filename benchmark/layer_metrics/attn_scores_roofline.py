"""Least time the chip needs for the attention cores of one step
(``rooflines.attention_train_least_s``: the pattern's FLOPs, q/k/v/o bytes)
over the device time under ``graftprof:attn-scores`` per step."""
from benchmark import rooflines


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    seconds = run.trace.scope_s.get("attn-scores")
    if not seconds:
        return None
    host = run.outcome.host
    least = rooflines.attention_train_least_s(
        run.dalle_cfg, host["global_batch"] / len(run.devices), run.peaks)
    return 100.0 * least["seconds"] / (seconds / host["trace_steps"])
