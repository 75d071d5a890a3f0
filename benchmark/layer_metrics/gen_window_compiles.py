"""Functions jax traced inside the timed window of a generate cell (a
retrace of anything: a program, ``tile_prefill``'s repeats, an eager op on a
new shape).  Must read 0."""
from benchmark.layer_metrics import _compiles


def read(run):
    return _compiles.count(_compiles.in_window(run), "trace")
