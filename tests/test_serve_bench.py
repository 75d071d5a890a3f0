"""bench_serve CPU smoke — the ISSUE 6 wall-clock acceptance gate.

Slow tier (--runslow / nightly): the model must be big enough that the
decode compute dominates per-tick dispatch, or the comparison measures
Python overhead instead of the serving design.  Two properties:

* **Throughput parity at full occupancy**: with every slot busy, the
  continuous-batching tick loop sustains >= 0.9x the aggregate tok/s of
  the static-batch `decode_codes` scan at the same batch size — the
  price of iteration-level scheduling (per-tick dispatch, phase-aligned
  cache writes, per-slot masks) is bounded, so interleaving wins whenever
  real traffic would leave static batches partially idle.
* **Open-loop interleaving**: with requests arriving mid-flight on a
  synthetic open-loop trace, admissions overlap in-flight decodes (true
  continuous batching), no recompile ever happens (cache-size sentinel ==
  1 — the same property graftspmd S3's serve harness gates chip-free),
  and the stats row carries occupancy + p50/p99 latency.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig
from dalle_pytorch_tpu.models.dalle import (decode_codes, prefill_codes,
                                            tile_prefill)
from dalle_pytorch_tpu.serve import GenerationServer

pytestmark = pytest.mark.slow

SLOTS = 8


@pytest.fixture(scope="module")
def served_model():
    """A model where per-tick compute dominates dispatch on CPU (measured:
    ~15 ms/tick vs ~0.5 ms overhead); full attention so the static control
    and the serve path read caches the same way."""
    cfg = DALLEConfig(dim=256, depth=8, heads=8, dim_head=64,
                      num_text_tokens=200, text_seq_len=48,
                      num_image_tokens=256, image_size=64,
                      image_fmap_size=8, attn_types=("full",))
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    text = jax.random.randint(rng, (1, cfg.text_seq_len), 1,
                              cfg.num_text_tokens)
    params = jax.jit(lambda r: dalle.init(
        r, text, jnp.zeros((1, cfg.image_seq_len), jnp.int32)))(rng)
    return cfg, dalle, params, np.asarray(text[0])


def test_full_occupancy_throughput_vs_static_batch(served_model):
    cfg, dalle, params, text = served_model
    L = cfg.image_seq_len

    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))
    decode = jax.jit(lambda p, fl, c, k: decode_codes(
        dalle, p, fl, c, k, filter_thres=0.9))
    fl, caches = tile_prefill(*prefill(params, jnp.asarray(text)[None]),
                              SLOTS)
    _ = jax.device_get(decode(params, fl, caches, jax.random.PRNGKey(1)))

    def static_dt():
        t0 = time.perf_counter()
        _ = jax.device_get(decode(params, fl, caches,
                                  jax.random.PRNGKey(2)))
        return time.perf_counter() - t0

    srv = GenerationServer(dalle, params, num_slots=SLOTS, filter_thres=0.9)

    def serve_dt():
        for i in range(SLOTS):
            srv.submit(text, key=np.asarray([9, i], np.uint32))
        srv.step(tick=False)        # admit everything: occupancy 1.0
        t0 = time.perf_counter()
        while srv.busy:
            srv.step()
        dt = time.perf_counter() - t0
        assert len(srv.completed) == SLOTS and not srv.failed
        srv.reset()
        return dt

    serve_dt()  # compile + warm
    # interleaved best-of-3: ambient load hits both sides of a round
    # roughly equally
    s_dts, v_dts = [], []
    for _ in range(3):
        s_dts.append(static_dt())
        v_dts.append(serve_dt())
    static_tps = SLOTS * L / min(s_dts)
    # the timed serve window decodes L-1 codes/slot (admit sampled the
    # first before t0) — count what the window actually produced
    serve_tps = SLOTS * (L - 1) / min(v_dts)
    ratio = serve_tps / static_tps
    print(f"\nbench_serve smoke: static {static_tps:.0f} tok/s, "
          f"serve {serve_tps:.0f} tok/s, ratio {ratio:.3f}")
    assert ratio >= 0.9, (
        f"continuous-batching tick loop at full occupancy fell to "
        f"{ratio:.3f}x the static-batch sampler (static {static_tps:.0f} "
        f"vs serve {serve_tps:.0f} tok/s)")
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_open_loop_trace_interleaves_and_reports(served_model):
    cfg, dalle, params, text = served_model
    srv = GenerationServer(dalle, params, num_slots=4, filter_thres=0.9)
    # warm the compiles outside the measured drive
    warm = srv.submit(text)
    srv.run_until_idle(max_ticks=2 * cfg.image_seq_len)
    _ = warm.result(0)
    srv.reset()

    # open loop: arrivals spread across roughly half a request's service
    # time, so later requests land mid-flight of earlier ones
    gap = 0.25 * cfg.image_seq_len * 0.015 / 4
    arrivals = [(i * gap, dict(text=text,
                               key=np.asarray([3, i], np.uint32),
                               slo="latency" if i % 3 == 0 else "throughput"))
                for i in range(8)]
    stats = srv.drive(arrivals, max_ticks=50 * cfg.image_seq_len)

    assert stats["completed"] == 8 and stats["failed"] == 0
    assert stats["tok_per_s"] > 0
    assert 0.0 < stats["occupancy"] <= 1.0
    for slo in ("latency", "throughput"):
        assert stats["latency_p50"][slo] is not None
        assert stats["latency_p99"][slo] >= stats["latency_p50"][slo]
    assert stats["trace_counts"] == {"prefill": 1, "admit": 1, "tick": 1}
    # true interleaving: early arrivals co-batch before anything finishes,
    # and late arrivals admit into slots retirements freed mid-drive
    admits = sorted(h.admitted_at for h in srv.completed)
    first_finish = min(h.finished_at for h in srv.completed)
    overlapped = sum(a < first_finish for a in admits)
    assert overlapped >= 2, (
        f"only {overlapped} admissions overlapped an in-flight decode — "
        "the trace degenerated to sequential batches")
    assert admits[-1] > first_finish, (
        "no admission reused a retired slot mid-drive")
