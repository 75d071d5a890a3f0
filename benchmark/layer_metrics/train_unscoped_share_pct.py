"""Device time of ops under no ``graftprof:`` scope over device busy time."""
from benchmark.layer_metrics._common import unscoped_share_pct as read  # noqa: F401
