"""1 - union of device-op intervals over the traced stretch, chip mean."""
from benchmark.layer_metrics._common import device_idle_pct as read  # noqa: F401
