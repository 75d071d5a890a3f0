"""Seconds inside jax's backend-compile span before the first measured step
or request: an XLA compile on a cache miss, reading and loading the
executable on a hit."""
from benchmark.layer_metrics import _compiles


def read(run):
    return _compiles.busy_s(_compiles.before_ready(run), "compile")
