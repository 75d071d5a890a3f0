"""Of the traced window, the time device 0 ran nothing while the host was
inside a ``graft:serve.retire`` or ``graft:serve.admit`` span: the gaps the
scheduler's own phases open (the rest of ``gen_device_idle_pct`` is
dispatch and the client)."""
from benchmark.layer_metrics._serve import stall_pct as read  # noqa: F401
