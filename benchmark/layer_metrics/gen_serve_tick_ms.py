"""Device time of one arena tick (``jit_serve_tick``, median over the traced
calls): the number held against the static scan's ``gen_decode_tick_ms`` at
equal rows."""
from benchmark.layer_metrics import _serve


def read(run):
    s = _serve.tick_s(run)
    return None if s is None else 1e3 * s
