"""Programs asked of the backend (compiled, or loaded from the persistent
cache) before the first measured step or request."""
from benchmark.layer_metrics import _compiles


def read(run):
    return _compiles.count(_compiles.before_ready(run), "compile")
