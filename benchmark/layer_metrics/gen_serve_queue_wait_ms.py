"""Median ``queue_wait_s`` (submit to the dispatch of the install) of the
traced stretch's ``serve.admit`` records."""
from benchmark.layer_metrics._serve import queue_wait_ms as read  # noqa: F401
