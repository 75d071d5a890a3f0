"""Bytes the compiler plans for the main program (a count, not a reading)."""
from benchmark.layer_metrics._common import hbm_planned_gb as read  # noqa: F401
