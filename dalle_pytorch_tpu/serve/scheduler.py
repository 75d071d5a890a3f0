"""GenerationServer: iteration-level scheduling over the slot arena.

The host half of continuous batching (the device half is
``serve/engine.py``).  One scheduler iteration (:meth:`GenerationServer.
step`) is:

1. **release** — slots whose request decoded its last token are freed,
   their codes taken as device arrays of their own (read in phase 4);
2. **admit** — queued requests are prefilled (batch 1) and written into
   free slots, latency-class first.  When the latency queue is non-empty
   and no slot is free, the least-progressed *throughput*-class running
   request is **preempted**: its slot is reclaimed for the latency request
   and it re-queues at the front of the throughput queue (restarting from
   prefill — its key replays, so the restart is deterministic).  Latency
   requests never preempt each other;
3. **tick** — one jitted decode step advances every occupied slot;
4. **retire** — the released requests' codes are read to the host and
   their futures resolved.  The read is the one place the loop blocks, and
   it comes after the dispatches so that the device has the admissions and
   the tick to run meanwhile.

Requests enter through the thread-safe :meth:`GenerationServer.submit`,
which returns a :class:`ServeHandle` carrying a ``concurrent.futures.
Future`` (``asyncio`` callers wrap it with ``asyncio.wrap_future``).  The
driving loop (:meth:`run_until_idle`, or :meth:`drive` for an open-loop
arrival trace) runs in whatever thread the caller owns — tests and
``bench_serve`` drive it synchronously for determinism; a daemon thread
calling ``step()`` is the serve-forever deployment shape.

Fault injection: every occupied slot hits the ``serve_request`` faultpoint
once per tick (``GRAFT_FAULTS="serve_request:fail_after=N"``), so a
mid-decode request failure is rehearsable: the failed request's future
carries the fault, its slot frees the same iteration, and co-batched
requests are untouched (tests/test_serve.py pins this).

Where an iteration's time goes is written as spans, each one ``B``/``E``
pair in the telemetry stream and one ``graft:serve.<name>`` annotation on
the profiler's clock (``obs/telemetry.py::_Span``), nested as the work
nests: ``serve.step`` > ``serve.admit`` (one an admitted request, around
``serve.prefill`` and the install) | ``serve.tick`` (the decode dispatch)
| ``serve.mem_watermark`` (the memory poll) | ``serve.retire`` (one a
retired request, around the blocking read of its codes).  ``rid`` on
``submit``, ``admit``, ``prefill`` and ``retire`` is one chain a request.
``tick_sample`` bounds the per-tick ``step`` and ``tick`` spans as it bounds
the ``tick`` records; with no stream open every span is the shared null one.

SLO accounting per request: queue wait (submit -> last admit), decode time
(last admit -> finish), end-to-end latency, preemption count.  The admit
stamp is taken when the host has DISPATCHED the install, not when the
device has run it: behind a queue of dispatched ticks the device may be up
to a retirement's worth of ticks later.
:meth:`stats` aggregates p50/p99 latency, occupancy, and decoded-token
throughput — the ``bench_serve`` row schema (PERF.md).

Lifecycle: :meth:`GenerationServer.evict_queued` (stop admitting, fail
the queued backlog typed — the drain-migration half) and
:meth:`GenerationServer.stop` (fail everything in flight typed) uphold
the no-hung-future contract the fleet tier (serve/replica.py +
serve/router.py) is built on: a future handed out by ``submit`` ALWAYS
resolves — with codes or with a typed error — whatever happens to the
server behind it.
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import threading
import time
from typing import Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import mem as obs_mem
from ..obs import metrics as obs_metrics
from ..obs import telemetry
from ..utils import faults
from ..utils import locks
from .engine import SlotArena
from .prefix import RadixPrefixCache

LATENCY = "latency"
THROUGHPUT = "throughput"
SLO_CLASSES = (LATENCY, THROUGHPUT)


class ServerStopped(RuntimeError):
    """Typed terminal error for a request a server will never finish: the
    server stopped (or started draining) with the request still queued or
    mid-decode.  The future RESOLVES with this — a caller blocked on
    ``handle.result()`` gets an exception immediately instead of hanging
    forever on a decode that will never run; a fleet router treats it as
    the retry-elsewhere signal (serve/router.py)."""


@dataclasses.dataclass
class ServeHandle:
    """One submitted request: its future plus the SLO bookkeeping."""

    request_id: int
    slo: str
    temperature: float
    text: np.ndarray                       # [1, text_seq_len] int32
    key: np.ndarray                        # [2] uint32 — replays on restart
    future: concurrent.futures.Future = dataclasses.field(
        default_factory=concurrent.futures.Future)
    submitted_at: float = 0.0
    admitted_at: Optional[float] = None    # last admission (post-preemption)
    finished_at: Optional[float] = None
    preemptions: int = 0

    def result(self, timeout: Optional[float] = None) -> np.ndarray:
        """Decoded image codes [image_seq_len]; raises the request's
        failure (e.g. an injected fault).  Only returns once the driving
        loop has retired the request — call from a different thread than
        the one stepping the server, or after ``run_until_idle``."""
        return self.future.result(timeout)

    @property
    def latency(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at


@dataclasses.dataclass
class _Running:
    handle: ServeHandle
    done: int  # codes decoded so far (admit samples the first)
    # prompt-token key pinning this request's prefix-cache payload
    # (None when the cache is off); released on retire/fail/preempt/stop
    prefix_key: Optional[Tuple[int, ...]] = None


class GenerationServer:
    """Continuous-batching generation service over one DALLE model."""

    def __init__(self, dalle, variables, num_slots: int = 8, *,
                 filter_thres: float = 0.9, top_p: Optional[float] = None,
                 seed: int = 0, time_fn=time.monotonic,
                 slo_targets: Optional[Dict[str, float]] = None,
                 tick_sample: int = 1, tel=None,
                 metrics_labels: Optional[Dict[str, str]] = None,
                 mem_watermark_ticks: int = 256,
                 mem_hbm_bytes: Optional[int] = None,
                 prefix_cache: bool = False, prefix_capacity: int = 32,
                 device=None):
        # device: the chip this server's params and arena live on (None =
        # jax's default device) — see SlotArena
        self.arena = SlotArena(dalle, variables, num_slots,
                               filter_thres=filter_thres, top_p=top_p,
                               device=device)
        # prefix_cache (a server knob, default OFF): admissions sharing a
        # prompt install copies of ONE batch-1 prefill via the refcounted
        # radix tree — including identical prompts already sitting in the
        # queue together (the dedupe case: the first admit misses and
        # inserts, the rest hit before any tick runs).
        self.prefix: Optional[RadixPrefixCache] = None
        if prefix_cache:
            from ..utils.profiling import dalle_prefill_flops
            self.prefix = RadixPrefixCache(
                prefix_capacity,
                prefill_flops=dalle_prefill_flops(dalle.cfg))
        self.prefill_count = 0  # arena.prefill CALLS (cache hits skip it)
        # tel: an explicit obs.telemetry.Telemetry instance to emit into
        # (a fleet replica's own per-stream lane); None = the module
        # singleton, the single-server deployment shape.  metrics_labels
        # ride every direct-instrumented series (e.g. {"replica": "r0"})
        # so N servers in one process don't clobber one another's gauges;
        # the default empty dict keeps the legacy series names bit-for-bit.
        self._tel = tel
        self._metrics_labels = dict(metrics_labels or {})
        self.num_slots = num_slots
        # the cost-model's HBM stream per decoded token for THIS arena
        # (cache payload + int8 scale planes, matching
        # profiling.dalle_decode_cache_bytes) — static per server, joined
        # against measured tok/s by monitor --fleet / graftprof --report
        from ..obs import prof
        self.predicted_bytes_per_token = prof.predicted_serve_bytes_per_token(
            dalle.cfg, num_slots)
        # the ledger row this arena's capacity math cites: graftscale
        # decision records carry it so "why did we scale" is answerable
        # from the stream alone (DESIGN.md §22)
        self.ledger_fingerprint = prof.row_fingerprint(
            prof.fingerprint_payload(dalle.cfg, target="serve",
                                     slots=int(num_slots)))
        # last serve-steady headroom watermark (None until the first
        # mem poll lands, or when the backend reports no byte limit)
        self.last_headroom_bytes: Optional[int] = None
        reg = obs_metrics.active()
        if reg is not None:
            reg.gauge("graft_serve_predicted_bytes_per_token",
                      "cost-model HBM bytes per decoded token",
                      **self._metrics_labels
                      ).set(self.predicted_bytes_per_token)
        # telemetry tick sampling: emit one aggregate `serve tick` record
        # per `tick_sample` decode ticks instead of 1:1 — a week-long serve
        # process at ~10ms/tick writes ~8.6M tick records a day unsampled.
        # The aggregate CARRIES the skipped ticks' stats (ticks covered,
        # summed/min/max active slots, covered clock range), so stream
        # consumers (obs/report.py) reconstruct totals exactly; partial
        # windows flush when the server drains idle, so nothing is lost.
        self.tick_sample = max(1, int(tick_sample))
        self._tick_agg = {"ticks": 0, "active_sum": 0,
                          "active_min": None, "active_max": 0,
                          "clock_first": None}
        # serve-steady memory watermarks: one obs/mem poll per
        # `mem_watermark_ticks` decode ticks (0 disables).  The tracker
        # owns the repo's managed polling surface (MEM001); emit=False
        # because the record must ride THIS server's lane (self._emit),
        # not the module singleton — and the replica-labeled headroom
        # gauge is set here so monitor --fleet can print it per replica.
        # mem_hbm_bytes pins the headroom denominator where the backend
        # reports no bytes_limit (CPU CI, the chaos rows) — on a real
        # chip leave it None and the device limit is used.
        self.mem_watermark_ticks = max(0, int(mem_watermark_ticks))
        self.mem_tracker = obs_mem.MemTracker(hbm_bytes=mem_hbm_bytes,
                                              emit=False)
        self._ticks_since_watermark = 0
        # optional end-to-end latency targets (seconds) per SLO class:
        # when set, each retirement records slo_ok and stats()/obs_report
        # aggregate attainment per class
        self.slo_targets = dict(slo_targets or {})
        self._time = time_fn
        self._seed = seed
        self._lock = locks.TracedLock("scheduler")
        self._queues: Dict[str, Deque[ServeHandle]] = {
            LATENCY: collections.deque(), THROUGHPUT: collections.deque()}
        self._running: Dict[int, _Running] = {}       # slot -> running
        # released this step, codes not yet on the host: (slot, run, codes)
        self._retiring: List[tuple] = []
        self._free: List[int] = list(range(num_slots))
        self._next_id = 0
        self._stopped = False
        self._draining = False
        self.completed: List[ServeHandle] = []
        self.failed: List[ServeHandle] = []
        self.preemption_count = 0
        self._ticks = 0
        self._clock = 0   # arena tick counter: the phase-aligned write column
        self._occupied_slot_ticks = 0
        self._decoded_tokens = 0

    # --- telemetry plumbing -------------------------------------------------

    def _emit(self, kind: str, name: str, **fields):
        """Emit into this server's own stream when one was given (the
        fleet tier: one lane per replica), else the module singleton."""
        if self._tel is not None:
            return self._tel.event(kind, name, **fields)
        return telemetry.emit(kind, name, **fields)

    def _span(self, kind: str, name: str, **fields):
        if self._tel is not None:
            return self._tel.span(kind, name, **fields)
        return telemetry.span(kind, name, **fields)

    # --- submission --------------------------------------------------------

    def submit(self, text, *, slo: str = THROUGHPUT,
               temperature: float = 1.0,
               key: Optional[np.ndarray] = None) -> ServeHandle:
        """Queue one request (thread-safe).  ``text`` is [text_seq_len] or
        [1, text_seq_len] int32 tokens; ``key`` overrides the per-request
        rng key (default: derived from (server seed, request id), so every
        request owns an independent deterministic stream)."""
        if slo not in SLO_CLASSES:
            raise ValueError(f"unknown SLO class {slo!r}; one of {SLO_CLASSES}")
        text = np.asarray(text, np.int32)
        if text.ndim == 1:
            text = text[None]
        assert text.shape[0] == 1, (
            f"one prompt per request; got batch {text.shape[0]}")
        with self._lock:
            if self._stopped or self._draining:
                # typed refusal, never a queued future nobody will serve:
                # a router that raced a drain/stop retries elsewhere
                raise ServerStopped(
                    "server is "
                    + ("stopped" if self._stopped else "draining")
                    + "; not admitting new requests")
            rid = self._next_id
            self._next_id += 1
            handle = ServeHandle(
                request_id=rid, slo=slo, temperature=float(temperature),
                text=text,
                key=(np.asarray(key, np.uint32) if key is not None
                     else np.asarray([self._seed, rid], np.uint32)),
                submitted_at=self._time())
            self._queues[slo].append(handle)
            depth = len(self._queues[slo])
        self._emit("serve", "submit", rid=rid, slo=slo)
        # queue depth is THE admission-feedback signal a front-end router
        # consumes (per-replica load); direct-instrumented (not derived
        # from events) so it works with telemetry off and never lags
        reg = obs_metrics.active()
        if reg is not None:
            reg.gauge("graft_serve_queue_depth",
                      "queued requests awaiting a slot", slo=slo,
                      **self._metrics_labels).set(depth)
        return handle

    # --- scheduler iteration ----------------------------------------------

    @property
    def busy(self) -> bool:
        with self._lock:
            return bool(self._running) or any(self._queues.values())

    def step(self, tick: bool = True) -> int:
        """One scheduler iteration: retire, admit, and (unless
        ``tick=False`` — the warm-the-batch move tests use) one decode
        tick.  Returns the number of slots that advanced."""
        # the tick that closes a `tick_sample` window carries the spans,
        # as it carries the aggregate record (every tick at tick_sample=1)
        sampled = self._tick_agg["ticks"] + 1 >= self.tick_sample
        with (self._span("serve", "step", clock=self._clock,
                         running=len(self._running),
                         queued=self.backlog()["queued_total"])
              if sampled else telemetry.NULL_SPAN):
            self._retire_finished()
            try:
                self._admit_pending()
                advanced = self._tick_once(sampled) if tick else 0
            finally:
                # the blocking read of the finished requests' codes comes
                # LAST: the device then holds the admissions and this
                # step's tick while the host waits, so a slow wake-up of
                # the host costs the device nothing (read first, the
                # device's queue was empty for as long as the read took);
                # in a finally, so that no released future is left hanging
                self._resolve_retired()
            if tick and advanced == 0:
                # drained idle: flush the partial sampling window so the
                # stream's aggregates cover every tick that actually ran
                self._flush_tick_agg()
            return advanced

    def run_until_idle(self, max_ticks: Optional[int] = None) -> None:
        """Drive until every queued/running request finishes (or fails)."""
        ticks = 0
        while self.busy:
            advanced = self.step()
            ticks += 1
            if advanced == 0 and not self.busy:
                break
            if max_ticks is not None and ticks > max_ticks:
                raise RuntimeError(
                    f"server not idle after {max_ticks} ticks: "
                    f"{len(self._running)} running, "
                    f"{self.backlog()['queued_total']} queued")

    def drive(self, arrivals: Sequence[Tuple[float, dict]],
              max_ticks: Optional[int] = None) -> dict:
        """Open-loop trace: ``arrivals`` is [(offset_seconds, submit_kwargs)]
        relative to the call.  Requests are submitted when the clock passes
        their offset — never gated on service progress (open loop: the
        queue grows if the server can't keep up, exactly like production
        ingress).  Returns :meth:`stats` over the drive window."""
        t0 = self._time()
        pending = sorted(arrivals, key=lambda a: a[0])
        i = 0
        ticks = 0
        tokens0 = self._decoded_tokens
        while i < len(pending) or self.busy:
            now = self._time() - t0
            while i < len(pending) and pending[i][0] <= now:
                self.submit(**pending[i][1])
                i += 1
            if not self.busy:
                # idle gap before the next arrival: jump the open loop
                # forward instead of busy-waiting on the clock
                time.sleep(min(0.001, max(0.0, pending[i][0] - now)))  # graftlint: disable=THR002 (open-loop trace pacing against the local clock — the wake condition is wall time reaching the next arrival offset, not shared state, and drive() runs on the single driver thread with nothing to stop early for)
                continue
            self.step()
            ticks += 1
            if max_ticks is not None and ticks > max_ticks:
                raise RuntimeError(f"drive exceeded {max_ticks} ticks")
        dt = self._time() - t0
        return self.stats(window_seconds=dt,
                          window_tokens=self._decoded_tokens - tokens0)

    # --- internals ---------------------------------------------------------

    def _retire_finished(self) -> None:
        """Release the slots whose request decoded its last token: the
        slot is free for this step's admissions at once, its codes are
        taken as a device array of their own (dispatched now, ahead of any
        install into the slot) and read by :meth:`_resolve_retired`."""
        total = self.arena.geometry.image_seq_len
        for slot in sorted(self._running):
            run = self._running[slot]
            if run.done >= total:
                self._retiring.append((slot, run, self.arena.take_codes(slot)))
                del self._running[slot]
                self._free.append(slot)
                if self.prefix is not None and run.prefix_key is not None:
                    self.prefix.release(run.prefix_key)

    def _resolve_retired(self) -> None:
        """Read the released requests' codes to the host and resolve their
        futures: the one place the loop blocks on the device."""
        while self._retiring:
            slot, run, codes = self._retiring.pop(0)
            h = run.handle
            with self._span("serve", "retire", rid=h.request_id, slot=slot):
                codes = jax.device_get(codes)
            h.finished_at = self._time()
            self.completed.append(h)
            target = self.slo_targets.get(h.slo)
            self._emit(
                "serve", "retire", rid=h.request_id, slot=slot,
                slo=h.slo, tokens=run.done, latency_s=h.latency,
                queue_wait_s=(h.admitted_at - h.submitted_at
                              if h.admitted_at is not None else None),
                decode_s=(h.finished_at - h.admitted_at
                          if h.admitted_at is not None else None),
                preemptions=h.preemptions,
                slo_ok=(None if target is None or h.latency is None
                        else bool(h.latency <= target)))
            reg = obs_metrics.active()
            if (reg is not None and h.latency is not None
                    and target is not None):
                reg.counter(
                    "graft_serve_slo_total",
                    "retirements by SLO verdict", slo=h.slo,
                    ok=str(bool(h.latency <= target)).lower(),
                    **self._metrics_labels).inc()
            h.future.set_result(codes)

    def _fail(self, slot: int, exc: BaseException) -> None:
        run = self._running.pop(slot)
        self._free.append(slot)
        if self.prefix is not None and run.prefix_key is not None:
            self.prefix.release(run.prefix_key)
        run.handle.finished_at = self._time()
        self.failed.append(run.handle)
        self._emit("serve", "fail", rid=run.handle.request_id, slot=slot,
                   slo=run.handle.slo, tokens=run.done, error=repr(exc))
        run.handle.future.set_exception(exc)

    def _preempt_one_throughput(self) -> Optional[int]:
        """Reclaim the least-progressed throughput-class slot for a
        waiting latency request; its request restarts from prefill at the
        front of the throughput queue.  None when nothing is preemptible
        (every running request is latency-class)."""
        victims = [(run.done, slot) for slot, run in self._running.items()
                   if run.handle.slo == THROUGHPUT]
        if not victims:
            return None
        _, slot = min(victims)
        run = self._running.pop(slot)
        self._free.append(slot)
        if self.prefix is not None and run.prefix_key is not None:
            # unpin now; the restart's admit re-acquires (likely a hit —
            # the payload stays resident unless eviction claims it)
            self.prefix.release(run.prefix_key)
        run.handle.preemptions += 1
        self.preemption_count += 1
        self._emit("serve", "preempt", rid=run.handle.request_id,
                   slot=slot, tokens=run.done,
                   preemptions=run.handle.preemptions)
        with self._lock:
            self._queues[THROUGHPUT].appendleft(run.handle)
        return slot

    def _admit_pending(self) -> None:
        while True:
            with self._lock:
                want_latency = bool(self._queues[LATENCY])
            if want_latency and not self._free:
                if self._preempt_one_throughput() is None:
                    break  # all slots latency-class: no preemption
            if not self._free:
                break
            with self._lock:
                for slo in SLO_CLASSES:  # latency first
                    if self._queues[slo]:
                        handle = self._queues[slo].popleft()
                        break
                else:
                    break
            self._admit(handle)

    def _admit(self, handle: ServeHandle) -> None:
        pkey: Optional[Tuple[int, ...]] = None
        payload = None
        slot = self._free[-1]
        with self._span("serve", "admit", rid=handle.request_id, slot=slot):
            if self.prefix is not None:
                pkey = tuple(int(t) for t in handle.text[0])
                payload = self.prefix.acquire(pkey)
            hit = payload is not None
            if payload is None:
                with self._span("serve", "prefill", rid=handle.request_id):
                    payload = self.arena.prefill(jnp.asarray(handle.text))
                self.prefill_count += 1
                if self.prefix is not None:
                    # insert pins for THIS request (and dedupes a racing
                    # identical insert by keeping the resident payload)
                    payload = self.prefix.insert(pkey, payload)
            first_logits, caches = payload
            self._free.pop()
            # self._clock is the NEXT tick's number — it pins the slot's
            # cache rotation so every later tick writes the shared physical
            # column
            self.arena.admit(slot, first_logits, caches, handle.key,
                             handle.temperature, self._clock)
        # stamped at the install's DISPATCH: the device runs it after the
        # ticks already queued (module docstring)
        handle.admitted_at = self._time()
        self._emit("serve", "admit", rid=handle.request_id, slot=slot,
                   slo=handle.slo,
                   queue_wait_s=handle.admitted_at - handle.submitted_at,
                   preemptions=handle.preemptions)
        if self.prefix is not None:
            st = self.prefix.stats()
            self._emit("serve", "prefix", rid=handle.request_id, hit=hit,
                       entries=st["entries"],
                       flops_saved=st["prefill_flops_saved"])
        reg = obs_metrics.active()
        if reg is not None:
            with self._lock:
                depth = len(self._queues[handle.slo])
            reg.gauge("graft_serve_queue_depth",
                      "queued requests awaiting a slot",
                      slo=handle.slo, **self._metrics_labels).set(depth)
            if self.prefix is not None:
                if hit:
                    reg.counter("graft_serve_prefix_hits_total",
                                "admissions served from the prefix cache",
                                **self._metrics_labels).inc()
                    reg.counter("graft_serve_prefix_flops_saved_total",
                                "prefill FLOPs avoided by prefix hits",
                                **self._metrics_labels
                                ).inc(self.prefix.prefill_flops)
                else:
                    reg.counter("graft_serve_prefix_misses_total",
                                "admissions that ran a fresh prefill",
                                **self._metrics_labels).inc()
                reg.gauge("graft_serve_prefix_entries",
                          "resident prefix-cache payloads",
                          **self._metrics_labels
                          ).set(self.prefix.stats()["entries"])
        self._running[slot] = _Running(handle=handle, done=1,
                                       prefix_key=pkey)
        self._decoded_tokens += 1  # admit samples the request's first code

    def _tick_once(self, sampled: bool = False) -> int:
        # the serve_request faultpoint: one hit per occupied slot per tick,
        # in slot order — an injected failure frees ITS slot and leaves
        # co-batched slots advancing this very tick
        for slot in sorted(self._running):
            try:
                faults.fire("serve_request",
                            step=self._running[slot].done)
            except faults.InjectedFault as e:
                self._fail(slot, e)
        # finished-but-unretired slots (possible only if a caller skips the
        # retire phase) must NOT advance: their output row is complete and
        # another tick would overwrite its clamped last position
        total = self.arena.geometry.image_seq_len
        advancing = [s for s, run in self._running.items()
                     if run.done < total]
        if not advancing:
            return 0
        mask = np.zeros((self.num_slots,), bool)
        for slot in advancing:
            mask[slot] = True
        span = (self._span("serve", "tick", clock=self._clock,
                           active=len(advancing))
                if sampled else telemetry.NULL_SPAN)
        with span:
            self.arena.tick(mask, self._clock)
        self._clock += 1
        for slot in advancing:
            self._running[slot].done += 1
        n = len(advancing)
        self._ticks += 1
        self._occupied_slot_ticks += n
        self._decoded_tokens += n
        # one record per `tick_sample` decode ticks (never per slot per
        # tick): occupancy and clock phase land on the timeline without
        # multiplying the stream by num_slots x tick rate
        agg = self._tick_agg
        agg["ticks"] += 1
        agg["active_sum"] += n
        agg["active_min"] = (n if agg["active_min"] is None
                             else min(agg["active_min"], n))
        agg["active_max"] = max(agg["active_max"], n)
        if agg["clock_first"] is None:
            agg["clock_first"] = self._clock - 1
        if agg["ticks"] >= self.tick_sample:
            self._flush_tick_agg()
        return n

    def _flush_tick_agg(self) -> None:
        """Emit the aggregate `serve tick` record for the covered window
        (1 tick at tick_sample=1 — the legacy 1:1 stream — or up to
        tick_sample skipped ticks' stats in one record)."""
        agg = self._tick_agg
        if not agg["ticks"]:
            return
        self._emit("serve", "tick", clock=self._clock - 1,
                   active=agg["active_sum"] / agg["ticks"],
                   ticks=agg["ticks"], active_sum=agg["active_sum"],
                   active_min=agg["active_min"],
                   active_max=agg["active_max"],
                   clock_first=agg["clock_first"])
        reg = obs_metrics.active()
        if reg is not None:
            reg.gauge("graft_serve_occupancy",
                      "occupied-slot fraction over the last tick window",
                      **self._metrics_labels
                      ).set(agg["active_sum"]
                            / (agg["ticks"] * self.num_slots))
            # re-assert the static byte-stream gauge here too: the
            # registry may have been installed after __init__ ran
            reg.gauge("graft_serve_predicted_bytes_per_token",
                      "cost-model HBM bytes per decoded token",
                      **self._metrics_labels
                      ).set(self.predicted_bytes_per_token)
        self._ticks_since_watermark += agg["ticks"]
        if (self.mem_watermark_ticks
                and self._ticks_since_watermark >= self.mem_watermark_ticks):
            self._emit_mem_watermark()
        self._tick_agg = {"ticks": 0, "active_sum": 0,
                          "active_min": None, "active_max": 0,
                          "clock_first": None}

    def _emit_mem_watermark(self) -> None:
        """One serve-steady memory poll: the watermark record rides this
        server's lane, and the headroom lands as a replica-labeled gauge
        (the series ``monitor --fleet`` prints beside the predicted byte
        stream)."""
        self._ticks_since_watermark = 0
        # on the loop's thread whether or not a stream is open: the span
        # is there so that the poll's cost is seen
        with self._span("serve", "mem_watermark"):
            rec = self.mem_tracker.snapshot("serve_steady")
        self._emit("mem", "watermark", **rec)
        if rec.get("headroom_bytes") is not None:
            self.last_headroom_bytes = int(rec["headroom_bytes"])
        reg = obs_metrics.active()
        if reg is not None and rec.get("headroom_bytes") is not None:
            reg.gauge("graft_hbm_headroom_bytes",
                      "HBM bytes left under the device limit",
                      **self._metrics_labels).set(rec["headroom_bytes"])

    # --- lifecycle: drain / stop -------------------------------------------

    @property
    def stopped(self) -> bool:
        with self._lock:
            return self._stopped

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def _zero_queue_gauges(self) -> None:
        reg = obs_metrics.active()
        if reg is not None:
            for slo in SLO_CLASSES:
                reg.gauge("graft_serve_queue_depth",
                          "queued requests awaiting a slot", slo=slo,
                          **self._metrics_labels).set(0)

    def evict_queued(self, error: Optional[BaseException] = None
                     ) -> List[ServeHandle]:
        """Drain, step 1: refuse new admissions and fail every QUEUED (not
        yet admitted) request's future with a typed error — the
        migrate-the-backlog half of the drain protocol.  Running slots
        keep decoding: they either finish inside the drain grace window or
        are failed-and-migrated by :meth:`stop` when it closes.  Returns
        the evicted handles."""
        err = (error if error is not None
               else ServerStopped("request evicted: server draining"))
        with self._lock:
            self._draining = True
            evicted = [h for slo in SLO_CLASSES for h in self._queues[slo]]
            for q in self._queues.values():
                q.clear()
        for h in evicted:
            h.finished_at = self._time()
            self.failed.append(h)
            self._emit("serve", "evicted", rid=h.request_id, slo=h.slo,
                       error=repr(err))
        self._zero_queue_gauges()
        # exceptions are set OUTSIDE every lock: done-callbacks (a fleet
        # router's retry path) run synchronously on this thread and may
        # submit to OTHER servers
        for h in evicted:
            h.future.set_exception(err)
        return evicted

    def stop(self, error: Optional[BaseException] = None
             ) -> List[ServeHandle]:
        """Stop serving: fail EVERY queued and running request's future
        with a typed error (default :class:`ServerStopped`) so no caller
        blocks forever on a decode that will never run — the
        blocked-forever shutdown bug this method exists to close.  Later
        :meth:`submit` calls raise the same typed error immediately.

        Must be called from the driving thread, or after the driving loop
        has exited (a fleet replica joins its driver first) — it reclaims
        the running slots' bookkeeping.  Returns the unfinished handles;
        idempotent (a second stop returns [])."""
        err = (error if error is not None
               else ServerStopped("server stopped with requests in flight"))
        with self._lock:
            self._stopped = True
            self._draining = True
            unfinished = [h for slo in SLO_CLASSES
                          for h in self._queues[slo]]
            for q in self._queues.values():
                q.clear()
        for slot in sorted(self._running):
            run = self._running.pop(slot)
            self._free.append(slot)
            if self.prefix is not None and run.prefix_key is not None:
                self.prefix.release(run.prefix_key)
            unfinished.append(run.handle)
        for h in unfinished:
            h.finished_at = self._time()
            self.failed.append(h)
            self._emit("serve", "stopped", rid=h.request_id, slo=h.slo,
                       error=repr(err))
        self._flush_tick_agg()
        self._zero_queue_gauges()
        # same outside-the-lock discipline as evict_queued
        for h in unfinished:
            h.future.set_exception(err)
        return unfinished

    # --- metrics ------------------------------------------------------------

    def backlog(self) -> dict:
        """Cheap load feedback for a fleet router: queued requests per SLO
        class plus the running-slot count — no percentile math (that is
        :meth:`stats`), so it can be polled per routing decision."""
        with self._lock:
            queued = {slo: len(self._queues[slo]) for slo in SLO_CLASSES}
        return dict(queued=queued, queued_total=sum(queued.values()),
                    running=len(self._running))

    def scale_signals(self) -> dict:
        """One autoscaler observation of THIS server: queue depth per
        class + running slots (the demand side), the last serve-steady
        headroom watermark + the ledger's per-slot byte stream and row
        fingerprint (the capacity side).  Cheap enough to ride the
        graftwire heartbeat."""
        b = self.backlog()
        return dict(
            queued=b["queued"], running=b["running"],
            num_slots=self.num_slots,
            headroom_bytes=self.last_headroom_bytes,
            predicted_bytes_per_token=self.predicted_bytes_per_token,
            ledger_fingerprint=self.ledger_fingerprint)

    def trace_counts(self) -> dict:
        return self.arena.trace_counts()

    def stats(self, window_seconds: Optional[float] = None,
              window_tokens: Optional[int] = None) -> dict:
        """The bench_serve row: aggregate throughput, occupancy, latency
        percentiles per SLO class, preemptions, failures."""
        lat = {slo: sorted(h.latency for h in self.completed
                           if h.slo == slo and h.latency is not None)
               for slo in SLO_CLASSES}

        def pct(values, q):
            return float(np.percentile(values, q)) if values else None

        tokens = (window_tokens if window_tokens is not None
                  else self._decoded_tokens)
        self._flush_tick_agg()  # a stats() reader sees every tick covered

        def attainment(slo):
            target = self.slo_targets.get(slo)
            if target is None or not lat[slo]:
                return None
            return sum(v <= target for v in lat[slo]) / len(lat[slo])

        with self._lock:
            queue_depth = {slo: len(self._queues[slo])
                           for slo in SLO_CLASSES}
        return dict(
            ticks=self._ticks,
            decoded_tokens=tokens,
            predicted_bytes_per_token=self.predicted_bytes_per_token,
            queue_depth=queue_depth,
            tok_per_s=(tokens / window_seconds
                       if window_seconds else None),
            occupancy=(self._occupied_slot_ticks
                       / (self._ticks * self.num_slots)
                       if self._ticks else 0.0),
            completed=len(self.completed),
            failed=len(self.failed),
            preemptions=self.preemption_count,
            latency_p50={slo: pct(lat[slo], 50) for slo in SLO_CLASSES},
            latency_p99={slo: pct(lat[slo], 99) for slo in SLO_CLASSES},
            slo_attainment={slo: attainment(slo) for slo in SLO_CLASSES},
            trace_counts=self.trace_counts(),
            prefill_count=self.prefill_count,
            **({"prefix": self.prefix.stats()}
               if self.prefix is not None else {}),
        )

    def reset(self) -> None:
        """Drop queues/stats for a fresh measurement over the SAME arena
        (the jitted entry points and their compiled executables survive —
        bench_serve re-measures without re-paying compiles).  Refuses to
        reset a busy server."""
        assert not self.busy, "reset() on a busy server"
        self._flush_tick_agg()
        self.completed = []
        self.failed = []
        self.preemption_count = 0
        self._ticks = 0
        self._occupied_slot_ticks = 0
        self._decoded_tokens = 0
        self.prefill_count = 0
