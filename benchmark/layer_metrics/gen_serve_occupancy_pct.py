"""Occupied slot-ticks over ticks x slots, from the traced stretch's
``serve.tick`` records.  Must read 100 in a saturated cell."""
from benchmark.layer_metrics._serve import occupancy_pct as read  # noqa: F401
