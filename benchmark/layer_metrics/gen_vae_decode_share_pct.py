"""Device time of the VAE decode program over device busy time."""
from benchmark.layer_metrics._common import program_share_pct


def read(run):
    return program_share_pct(run, "jit_decode")
