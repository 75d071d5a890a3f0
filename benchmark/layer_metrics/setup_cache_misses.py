"""Programs compiled and written to the persistent cache during set-up: 0 in
a warm run, so a ``setup_s`` that jumps with this at 0 is the host's doing."""
from benchmark.layer_metrics import _compiles


def read(run):
    return _compiles.count(_compiles.before_ready(run), "cache_miss")
