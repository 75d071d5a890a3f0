"""Profiling & efficiency counters: step timing, FLOPs, MFU, trace capture.

The reference's only perf instrumentation is `/usr/bin/time -p` around
genrank runs (SURVEY.md §5.1).  TPU-natively we report step time,
images/sec, and MFU (model FLOPs utilization = achieved FLOP/s over the
chip's peak) — the metric the BASELINE.md target (≥35% MFU) is defined in —
plus a `jax.profiler` trace context for deeper dives in XProf.
"""
from __future__ import annotations

import contextlib
import random
import time
from typing import Optional

import jax

# peak dense bf16 FLOP/s per chip by device kind substring (public numbers).
# Order matters: 'lite' variants must match before the bare generation
# (libtpu reports e.g. 'TPU v5 lite' for v5e but 'TPU v5' for v5p,
# 'TPU v6 lite' for v6e).
PEAK_FLOPS = (
    ("v5 lite", 197e12),   # v5e
    ("v5e", 197e12),
    ("v6 lite", 918e12),   # v6e (Trillium)
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5", 459e12),        # bare 'TPU v5' = v5p
    ("v6", 918e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)


def device_peak_flops(device_kind: Optional[str] = None) -> Optional[float]:
    """Peak dense bf16 FLOP/s of ``device_kind`` (default: the first
    device's), or None for a kind the table does not know — MFU is then
    "not measured", never computed from a guessed peak."""
    kind = (device_kind if device_kind is not None
            else jax.devices()[0].device_kind).lower()
    for sub, peak in PEAK_FLOPS:
        if sub in kind:
            return peak
    return None


def transformer_train_flops(dim: int, depth: int, seq_len: int, heads: int,
                            dim_head: int, ff_mult: int, vocab: int,
                            batch: int,
                            logits_flops: Optional[float] = None) -> float:
    """Analytic FLOPs for one *training* step (fwd + bwd ≈ 3x fwd) of a
    GEGLU decoder stack + logits head, matmul terms only.  ``logits_flops``
    overrides the forward head term for models whose head is not a single
    ``seq_len x vocab`` matmul (e.g. DALLE's phase-sliced head)."""
    inner = heads * dim_head
    per_layer = (
        2 * seq_len * dim * (3 * inner)        # qkv projection
        + 2 * seq_len * seq_len * inner * 2    # scores + attn·v
        + 2 * seq_len * inner * dim            # output projection
        + 2 * seq_len * dim * (ff_mult * dim * 2)  # GEGLU in
        + 2 * seq_len * (ff_mult * dim) * dim      # ff out
    )
    logits = (2 * seq_len * dim * vocab if logits_flops is None
              else logits_flops)
    fwd = depth * per_layer + logits
    return 3.0 * fwd * batch


def dalle_train_flops(cfg, batch: int) -> float:
    """FLOPs per train step for a DALLEConfig.

    Attention is counted dense (the convention sparse models quote MFU in,
    and what the default dense-masked path actually executes), and the
    logits head is counted as the phase-sliced matmuls the dense and
    pipeline training losses really run (models/dalle.py::loss_from_hidden
    slices positions before the head dot): ``text_seq_len`` positions x
    text vocab (incl. per-position pads) + ``image_seq_len`` positions x
    image vocab — not a ``seq_len x total_vocab`` product, which would
    overstate FLOPs (and MFU) by ~9% at the CUB geometry.  The
    sequence-parallel loss (``_sp_loss``) still executes the full-vocab
    head per shard position (shards straddle the phase boundary at traced
    offsets), so sp runs report conservatively: achieved FLOP/s/MFU there
    understate executed work by the same ~9% rather than overstating it."""
    logits_fwd = 2 * cfg.dim * (
        cfg.text_seq_len * cfg.total_text_tokens
        + cfg.image_seq_len * cfg.num_image_tokens)
    return transformer_train_flops(
        dim=cfg.dim, depth=cfg.depth, seq_len=cfg.seq_len + 1,
        heads=cfg.heads, dim_head=cfg.dim_head, ff_mult=4,
        vocab=cfg.total_tokens, batch=batch, logits_flops=logits_fwd)


def dalle_prefill_flops(cfg) -> float:
    """Analytic forward FLOPs of ONE batch-1 prompt prefill (the
    ``text_seq_len + 1`` prompt positions through the stack, attention
    counted dense, plus the single-position logits head) — what a
    radix-prefix-cache hit SAVES (serve/prefix.py accounts hits in these
    units so /metrics and obs_report can state the avoided work in a
    hardware-meaningful number rather than a raw hit count)."""
    n = cfg.text_seq_len + 1
    inner = cfg.heads * cfg.dim_head
    per_layer = (
        2 * n * cfg.dim * (3 * inner)        # qkv projection
        + 2 * n * n * inner * 2              # scores + attn·v
        + 2 * n * inner * cfg.dim            # output projection
        + 2 * n * cfg.dim * (4 * cfg.dim * 2)    # GEGLU in
        + 2 * n * (4 * cfg.dim) * cfg.dim        # ff out
    )
    head = 2.0 * cfg.dim * cfg.total_tokens  # first-image-token logits
    return float(cfg.depth * per_layer + head)


def dalle_decode_cache_bytes(cfg, batch: int) -> int:
    """Bytes of KV-cache state one decode step carries (each of depth x
    (k, v) caches at [batch, heads, seq_len, dim_head]) — the decode
    loop's dominant HBM stream (PERF.md: the loop is measured
    bandwidth-bound on cache reads, sliced-KV 2.16x).  The storage dtype
    follows ``cfg.kv_cache_int8`` (one byte per element PLUS the f32
    per-head scale planes [batch, heads, 1, 1] each cache carries —
    counting the payload without the scales would let the cost-model
    gate under-measure the true stream), then ``cfg.kv_cache_bf16``
    (bf16 even at f32 activations; the knob's whole point), then the
    activation dtype when that is already half-width.
    ``tests/test_perf_model.py`` pins the compiled decode step's cache
    I/O against this number."""
    import jax.numpy as jnp

    n_caches = cfg.depth * 2  # k and v per layer
    if cfg.kv_cache_int8:
        itemsize = 1
    elif cfg.kv_cache_bf16 or jnp.dtype(cfg.dtype).itemsize == 2:
        itemsize = 2
    else:
        itemsize = 4
    total = (n_caches * batch * cfg.heads * cfg.seq_len * cfg.dim_head
             * itemsize)
    if cfg.kv_cache_int8:
        total += n_caches * batch * cfg.heads * 4  # f32 scale planes
    return total


def compiled_cost_summary(fn, *args, donate_argnums=(),
                          static_argnums=()) -> dict:
    """Compile ``fn(*args)`` (no execution, no device memory) and return
    XLA's own per-step cost model:

    ``flops``            HLO-level floating-point operation count
    ``bytes_accessed``   the cost model's total memory traffic.  NOTE:
                         XLA's accounting is per-op and pre-fusion-naive —
                         an operand read by k ops is counted k times — so
                         treat it as a *regression signal*, not achievable
                         HBM traffic; compare builds, don't quote it.
    ``temp_bytes``       peak temporary allocation of the compiled program
    ``argument_bytes`` / ``output_bytes``  I/O footprint

    This is the chip-independent half of the perf story: the same numbers
    XLA computes on any backend, so FLOPs/traffic/memory regressions are
    caught by CPU-only CI runs without a TPU in the loop (the wall-clock
    half is ``BENCHMARK.json`` + ``benchmark/``).  The analytic
    ``dalle_train_flops`` is validated against this path (96.4% agreement
    at the CUB geometry, tests/test_perf_model.py)."""
    compiled = jax.jit(fn, donate_argnums=donate_argnums,
                       static_argnums=static_argnums).lower(*args).compile()
    ca = compiled.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    out = {"flops": ca.get("flops", 0.0),
           "bytes_accessed": ca.get("bytes accessed", 0.0)}
    try:
        ma = compiled.memory_analysis()
        out.update(temp_bytes=ma.temp_size_in_bytes,
                   argument_bytes=ma.argument_size_in_bytes,
                   output_bytes=ma.output_size_in_bytes)
    except Exception:  # pragma: no cover — graftlint: disable=EXC001 (optional XLA API: absence just skips the optional memory fields)
        pass
    return out


class StepTimer:
    """Wall-clock step timer with EMA, images/sec, MFU and loader-stall
    reporting.

    Call ``tick(batch)`` once per completed (synced) step.  MFU uses the
    analytic `flops_per_sample` when provided.  ``stall_s`` is the host
    time the step loop spent waiting on the input pipeline for this step
    (``DevicePrefetcher.last_wait_s``): the reported EMA and
    ``loader_stall_frac`` (stall over step time) make an *input-bound* run
    readable as such in the monitor's output instead of masquerading as a
    slow chip — at ~0 the step is device-bound, near 1 the chip is idling
    on the loader.

    Besides the EMAs (unchanged — the smooth "now" the logs show), raw
    per-step samples feed a bounded uniform reservoir (Vitter's Algorithm
    R, deterministic generator) so :meth:`percentiles` can report p50/p99
    step time and stall over the WHOLE run in O(reservoir) memory — the
    tail behavior EMAs structurally cannot show, consumed by
    ``tools/obs_report.py`` via the run's ``perf_summary`` event.
    """

    def __init__(self, flops_per_step: Optional[float] = None,
                 ema: float = 0.9, reservoir: int = 512):
        self.flops_per_step = flops_per_step
        self.ema = ema
        self.avg_dt: Optional[float] = None
        self.avg_stall: Optional[float] = None
        self._last: Optional[float] = None
        self._res_cap = int(reservoir)
        self._res_rng = random.Random(0x5eed)
        self._dt_res: list = []
        self._dt_n = 0
        self._stall_res: list = []
        self._stall_n = 0
        # flops_per_step covers the global batch, so peak spans all chips;
        # None on a device the peaks table does not know (no "mfu" then)
        peak = device_peak_flops()
        self.peak = peak * max(1, jax.device_count()) if peak else None

    def _reservoir_add(self, res: list, n: int, value: float) -> None:
        """Algorithm R: after n samples every one had cap/n odds of being
        in the reservoir — percentiles cover the run, not just its tail."""
        if len(res) < self._res_cap:
            res.append(value)
        else:
            j = self._res_rng.randrange(n)
            if j < self._res_cap:
                res[j] = value

    def tick(self, batch: int = 1, stall_s: Optional[float] = None) -> dict:
        now = time.perf_counter()
        out: dict = {}
        if self._last is not None:
            dt = now - self._last
            self.avg_dt = (dt if self.avg_dt is None
                           else self.ema * self.avg_dt + (1 - self.ema) * dt)
            self._dt_n += 1
            self._reservoir_add(self._dt_res, self._dt_n, dt)
            out["step_time_s"] = self.avg_dt
            out["images_per_sec"] = batch / self.avg_dt
            if self.flops_per_step and self.peak:
                out["mfu"] = self.flops_per_step / self.avg_dt / self.peak
            if stall_s is not None:
                self.avg_stall = (stall_s if self.avg_stall is None
                                  else self.ema * self.avg_stall
                                  + (1 - self.ema) * stall_s)
                self._stall_n += 1
                self._reservoir_add(self._stall_res, self._stall_n, stall_s)
                out["loader_stall_s"] = self.avg_stall
                out["loader_stall_frac"] = min(
                    self.avg_stall / self.avg_dt, 1.0)
        self._last = now
        return out

    def percentiles(self) -> dict:
        """p50/p99 of raw step time and stall over the reservoir samples
        (``reservoir_n`` = steps observed).  Empty dict before step 2."""
        def pct(values, q):
            ordered = sorted(values)
            idx = min(int(round((q / 100.0) * (len(ordered) - 1))),
                      len(ordered) - 1)
            return ordered[idx]

        out: dict = {}
        if self._dt_res:
            out["reservoir_n"] = self._dt_n
            out["step_time_p50"] = pct(self._dt_res, 50)
            out["step_time_p99"] = pct(self._dt_res, 99)
        if self._stall_res:
            out["stall_p50"] = pct(self._stall_res, 50)
            out["stall_p99"] = pct(self._stall_res, 99)
        return out


@contextlib.contextmanager
def profile_trace(logdir: str = "/tmp/jax-trace", enabled: bool = True):
    """`jax.profiler` trace context (view with XProf/TensorBoard).

    Delegates to :func:`obs.prof.capture` — the repo's one managed
    profiler entry point (graftlint OBS003) — so the trace window also
    lands as a ``prof.xprof`` span in the telemetry stream."""
    if not enabled:
        yield
        return
    from ..obs import prof

    with prof.capture(logdir):
        yield
