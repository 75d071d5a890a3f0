"""Seconds the process spent tracing functions to jaxprs and lowering them
to MLIR before the first measured step or request (the union of the log's
``trace`` and ``lower`` spans: a nested jit's trace counts once)."""
from benchmark.layer_metrics import _compiles


def read(run):
    return _compiles.busy_s(_compiles.before_ready(run), "trace_lower")
