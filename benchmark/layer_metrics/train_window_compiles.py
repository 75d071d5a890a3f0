"""Functions jax traced inside the timed window of a train cell (a retrace
of anything: the step, ``shard_batch``, an eager op on a new shape).  Must
read 0."""
from benchmark.layer_metrics import _compiles


def read(run):
    return _compiles.count(_compiles.in_window(run), "trace")
