"""Device time of admissions (``jit_serve_prefill`` + ``jit_serve_admit``)
over device busy time: what they take from the ticks."""
from benchmark.layer_metrics._serve import admission_share_pct as read  # noqa: F401
