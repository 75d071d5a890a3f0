"""Continuous-batching serve tests (serve/engine.py + serve/scheduler.py).

The load-bearing properties, in order:

* **Exactness** — a request served through the slot arena (phase-aligned
  rotated caches, per-slot decode positions, mid-flight co-batching)
  produces BIT-IDENTICAL codes to the static `decode_codes` sampler under
  greedy decoding, for every attention pattern variant, at every admission
  interleaving.  Continuous batching is a scheduling change, not a model
  change.
* **No retrace** — admissions/retirements across every occupancy, slot id
  and clock phase reuse ONE compiled executable per entry point
  (prefill/admit/tick), asserted via the `_cache_size` sentinel graftspmd
  S3 also gates (tools/spmd_check.py serve-tick harness).
* **SLO scheduling** — latency-class requests preempt throughput-class
  fills, and a preempted request restarts deterministically.
* **Fault isolation** — an injected `serve_request` failure frees its slot
  without stalling co-batched requests (utils/faults.py).

The wall-clock acceptance gate (full-occupancy serve tok/s >= 0.9x the
static-batch sampler) lives in tests/test_serve_bench.py (slow tier:
it needs a model big enough that compute dominates dispatch).
"""
import concurrent.futures

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig
from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes
from dalle_pytorch_tpu.serve import (LATENCY, THROUGHPUT, GenerationServer,
                                     ServerStopped, SlotArena)
from dalle_pytorch_tpu.utils import faults, locks

VCFG = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16, num_layers=2,
                 hidden_dim=8)


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.install("")
    # graftrace witness armed for every row; the teardown assert is the
    # standing gate — any AB/BA lock-order inversion observed during the
    # test fails it, deadlock or not
    locks.reset()
    locks.arm()
    yield
    try:
        locks.assert_acyclic()
    finally:
        locks.disarm()
        locks.reset()
        faults.reset()


@pytest.fixture(scope="module")
def small():
    """Tiny model over all four pattern variants (the aligned decode's
    rotation math differs per variant) + per-prompt greedy references."""
    cfg = DALLEConfig.from_vae(
        VCFG, dim=32, num_text_tokens=50, text_seq_len=6, depth=4, heads=2,
        dim_head=8, attn_types=("full", "axial_row", "axial_col",
                                "conv_like"))
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(0)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(6)]
    codes = jax.random.randint(rng, (1, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, jnp.asarray(texts[0])[None], codes,
                        return_loss=True)
    prefill = jax.jit(lambda p, t: prefill_codes(dalle, p, t))

    def greedy_ref(i):
        fl, caches = prefill(params, jnp.asarray(texts[i])[None])
        return np.asarray(decode_codes(
            dalle, params, fl, caches, jax.random.PRNGKey(7),
            filter_thres=1.0))[0]

    refs = [greedy_ref(i) for i in range(len(texts))]
    return cfg, dalle, params, texts, refs


def make_server(small, num_slots, **kw):
    _, dalle, params, _, _ = small
    kw.setdefault("filter_thres", 1.0)  # greedy: bit-compare vs decode_codes
    return GenerationServer(dalle, params, num_slots=num_slots, **kw)


def test_single_request_matches_static_sampler(small):
    _, _, _, texts, refs = small
    srv = make_server(small, num_slots=2)
    h = srv.submit(texts[0])
    srv.run_until_idle(max_ticks=100)
    np.testing.assert_array_equal(h.result(0), refs[0])


def test_device_pins_params_and_arena(small):
    """``device=`` commits the params and the arena state to that chip and
    every entry point runs there — N servers in one process (a fleet of
    one-chip replicas) must not all sit on device 0.  Results and the
    no-retrace sentinel are unchanged."""
    _, _, _, texts, refs = small
    dev = jax.devices()[3]
    srv = make_server(small, num_slots=2, device=dev)
    handles = [srv.submit(texts[i]) for i in range(3)]
    srv.run_until_idle(max_ticks=300)
    for h, r in zip(handles, refs):
        np.testing.assert_array_equal(h.result(0), r)
    on = {d for leaf in jax.tree.leaves((srv.arena.state,
                                         srv.arena.variables))
          for d in leaf.devices()}
    assert on == {dev}
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_scale_signals_surface_demand_and_capacity(small):
    """graftscale's per-server observation: one cheap dict with the
    demand side (queues, running) and the capacity side (headroom + the
    ledger's per-slot byte stream and row fingerprint), and nothing
    else."""
    srv = make_server(small, num_slots=2)
    s = srv.scale_signals()
    assert set(s) == {"queued", "running", "num_slots", "headroom_bytes",
                      "predicted_bytes_per_token", "ledger_fingerprint"}
    assert s["num_slots"] == 2
    assert s["queued"] == {LATENCY: 0, THROUGHPUT: 0} and s["running"] == 0
    assert s["predicted_bytes_per_token"] > 0
    assert len(s["ledger_fingerprint"]) == 12   # prof.row_fingerprint
    # demand side tracks the queues
    for t in small[3][:3]:
        srv.submit(t)
    s = srv.scale_signals()
    assert s["queued"][THROUGHPUT] + s["running"] + s["queued"][LATENCY] == 3
    srv.run_until_idle(max_ticks=300)


def test_mid_flight_admission_is_exact_and_single_trace(small):
    """Requests admitted into an in-flight decode batch — slots at mixed
    depths — still reproduce the static sampler bit-for-bit, and the whole
    interleaving compiles each entry point exactly once (the acceptance
    criterion's cache-size sentinel)."""
    _, _, _, texts, refs = small
    srv = make_server(small, num_slots=2)
    h0 = srv.submit(texts[0])
    for _ in range(5):
        srv.step()
    h1 = srv.submit(texts[1])          # joins mid-flight
    for _ in range(3):
        srv.step()
    h2 = srv.submit(texts[2])          # queued: both slots busy
    srv.run_until_idle(max_ticks=300)
    for h, r in ((h0, refs[0]), (h1, refs[1]), (h2, refs[2])):
        np.testing.assert_array_equal(h.result(0), r)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_no_retrace_across_occupancies_and_clock_wrap(small):
    """Every occupancy 1..S, every slot id, and an arena clock that wraps
    seq_len several times — one executable each.  (The deliberately-broken
    shape-changing twin is proven caught in tests/test_spmd_check.py.)"""
    cfg, _, _, texts, refs = small
    srv = make_server(small, num_slots=3)
    handles = [(srv.submit(texts[i % len(texts)]), i % len(texts))
               for i in range(8)]
    srv.run_until_idle(max_ticks=2000)
    assert srv._clock > 2 * cfg.seq_len  # the wrap actually happened
    for h, i in handles:
        np.testing.assert_array_equal(h.result(0), refs[i])
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_per_request_temperature_is_traced(small):
    """Different temperatures ride the traced per-slot temp lane — no
    retrace, and temp!=1 actually changes sampled (non-greedy) output."""
    _, _, _, texts, _ = small
    srv = make_server(small, num_slots=2, filter_thres=0.0)  # full vocab
    key = np.asarray([1, 2], np.uint32)
    h_cold = srv.submit(texts[0], temperature=0.05, key=key)
    h_hot = srv.submit(texts[0], temperature=5.0, key=key)
    srv.run_until_idle(max_ticks=100)
    assert srv.trace_counts()["admit"] == 1
    assert not np.array_equal(h_cold.result(0), h_hot.result(0))


def test_per_request_key_determinism(small):
    """Same (prompt, key, temperature) -> identical codes across server
    instances and admission orders; distinct keys diverge."""
    _, _, _, texts, _ = small
    key = np.asarray([11, 22], np.uint32)
    outs = []
    for order in ((0, 1), (1, 0)):
        srv = make_server(small, num_slots=2, filter_thres=0.9)
        hs = {}
        for j in order:
            hs[j] = srv.submit(texts[0],
                               key=key if j == 0 else np.asarray(
                                   [33, 44], np.uint32))
        srv.run_until_idle(max_ticks=100)
        outs.append((hs[0].result(0), hs[1].result(0)))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    assert not np.array_equal(outs[0][0], outs[0][1])


def test_latency_class_preempts_throughput_fill(small):
    """Both slots busy with throughput-class work: a latency submission
    evicts the least-progressed fill (which restarts deterministically and
    still finishes exact) and finishes before it."""
    _, _, _, texts, refs = small
    srv = make_server(small, num_slots=2)
    a = srv.submit(texts[0], slo=THROUGHPUT)
    b = srv.submit(texts[1], slo=THROUGHPUT)
    srv.step()
    srv.step()
    lat = srv.submit(texts[2], slo=LATENCY)
    srv.run_until_idle(max_ticks=300)
    assert srv.preemption_count == 1
    assert lat.preemptions == 0
    assert a.preemptions + b.preemptions == 1
    for h, r in ((a, refs[0]), (b, refs[1]), (lat, refs[2])):
        np.testing.assert_array_equal(h.result(0), r)
    assert lat.finished_at < max(a.finished_at, b.finished_at)


def test_latency_never_preempts_latency(small):
    _, _, _, texts, _ = small
    srv = make_server(small, num_slots=2)
    srv.submit(texts[0], slo=LATENCY)
    srv.submit(texts[1], slo=LATENCY)
    srv.step()
    srv.submit(texts[2], slo=LATENCY)  # queues; cannot evict its own class
    srv.run_until_idle(max_ticks=300)
    assert srv.preemption_count == 0
    assert len(srv.completed) == 3


def test_injected_fault_frees_slot_without_stalling_cobatch(small):
    """GRAFT_FAULTS serve_request:fail_after=N mid-decode: exactly one
    request fails (its future carries the InjectedFault), its co-batched
    neighbors finish bit-exact, and the freed slot serves a later
    request."""
    _, _, _, texts, refs = small
    faults.install("serve_request:fail_after=10")
    srv = make_server(small, num_slots=3)
    hs = [srv.submit(texts[i]) for i in range(3)]
    h_next = None
    while srv.busy:
        srv.step()
        if srv.failed and h_next is None:
            h_next = srv.submit(texts[3])  # lands in the freed slot
    srv.run_until_idle(max_ticks=300)
    failed = [h for h in hs if h.future.exception() is not None]
    assert len(failed) == 1
    assert isinstance(failed[0].future.exception(), faults.InjectedFault)
    for h in hs:
        if h is not failed[0]:
            np.testing.assert_array_equal(h.result(0), refs[hs.index(h)])
    assert h_next is not None
    np.testing.assert_array_equal(h_next.result(0), refs[3])
    assert len(srv.completed) == 3 and len(srv.failed) == 1
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


def test_submit_validation_and_stats(small):
    _, _, _, texts, _ = small
    srv = make_server(small, num_slots=2)
    with pytest.raises(ValueError, match="SLO"):
        srv.submit(texts[0], slo="bulk")
    h = srv.submit(texts[0])
    srv.run_until_idle(max_ticks=100)
    stats = srv.stats(window_seconds=1.0)
    assert stats["completed"] == 1 and stats["failed"] == 0
    assert stats["decoded_tokens"] == h.result(0).shape[0]
    assert 0.0 < stats["occupancy"] <= 1.0
    assert stats["latency_p50"][THROUGHPUT] is not None
    assert stats["latency_p50"][LATENCY] is None  # no latency-class traffic
    assert stats["trace_counts"] == {"prefill": 1, "admit": 1, "tick": 1}


def test_future_result_from_another_thread(small):
    """The async-queue contract: a waiter thread blocks on the future
    while the serving loop runs elsewhere."""
    _, _, _, texts, refs = small
    srv = make_server(small, num_slots=1)
    h = srv.submit(texts[0])
    with concurrent.futures.ThreadPoolExecutor(1) as ex:
        waiter = ex.submit(h.result, 30.0)
        srv.run_until_idle(max_ticks=100)
        np.testing.assert_array_equal(waiter.result(30.0), refs[0])


def _rewidth(cfg, dim_head):
    """``cfg`` with another head width, and parameters for it."""
    cfg = dataclasses.replace(cfg, dim_head=dim_head)
    dalle = DALLE(cfg)
    params = dalle.init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.text_seq_len), jnp.int32),
        jnp.zeros((1, cfg.image_seq_len), jnp.int32), return_loss=True)
    return cfg, dalle, params


def _stored_shapes(cfg, slots, folded):
    """Per layer, the shape the arena stores a cache in: plain, or where the
    rule folds (ops/attention.py::MultiHeadAttention.arena_form) two heads
    side by side, the sliced layers position-major."""
    plain = (slots, cfg.heads, cfg.seq_len, cfg.dim_head)
    if not folded:
        return [plain] * cfg.depth
    head_major = (slots, cfg.heads // 2, cfg.seq_len, 2 * cfg.dim_head)
    position_major = (slots, cfg.seq_len, cfg.heads // 2, 2 * cfg.dim_head)
    return [head_major if kind == "full" else position_major
            for kind in cfg.attn_types]


@pytest.mark.parametrize("dim_head", [8, 64], ids=["plain", "folded"])
def test_arena_geometry_and_cache_dtype(small, dim_head):
    """The arena honors kv_cache_bf16 storage (the serve path inherits the
    measured byte-cut) and its shapes never depend on occupancy; two heads
    of 64 are stored side by side, two of 8 as they come."""
    cfg, dalle, params = (small[:3] if dim_head == small[0].dim_head
                          else _rewidth(small[0], dim_head))
    arena = SlotArena(dalle, params, num_slots=4)
    g = arena.geometry
    assert (g.num_slots, g.n_pre, g.image_seq_len, g.seq_len) == (
        4, cfg.text_seq_len + 1, cfg.image_seq_len, cfg.seq_len)
    for (k, v), shape in zip(arena.state["caches"],
                             _stored_shapes(cfg, 4, dim_head == 64)):
        assert k.shape == v.shape == shape
        assert k.dtype == jnp.bfloat16  # kv_cache_bf16 default ON
        assert v.dtype == jnp.bfloat16


# --- shutdown/stop: the no-hung-future contract (ISSUE 12) ----------------


def test_stop_fails_queued_and_running_futures_typed(small):
    """The shutdown bugfix: stopping a server with requests queued AND
    mid-decode fails every future with the typed ServerStopped — a caller
    blocked on result() gets an exception immediately, never a hang —
    and later submits are refused with the same type."""
    _, _, _, texts, _ = small
    srv = make_server(small, num_slots=1)
    hs = [srv.submit(texts[i]) for i in range(3)]
    srv.step()  # admit h0; h1/h2 stay queued
    srv.step()
    unfinished = srv.stop()
    assert {h.request_id for h in unfinished} == {h.request_id for h in hs}
    for h in hs:
        assert h.future.done()
        assert isinstance(h.future.exception(), ServerStopped)
        with pytest.raises(ServerStopped):
            h.result(0)
    assert not srv.busy
    assert srv.stopped and len(srv.failed) == 3
    with pytest.raises(ServerStopped):
        srv.submit(texts[0])
    assert srv.stop() == []  # idempotent


def test_stop_idle_server_then_submit_refused(small):
    _, _, _, texts, _ = small
    srv = make_server(small, num_slots=2)
    h = srv.submit(texts[0])
    srv.run_until_idle(max_ticks=100)
    assert srv.stop() == []  # nothing in flight: nothing failed
    assert h.future.exception() is None  # completed work is untouched
    with pytest.raises(ServerStopped):
        srv.submit(texts[1])


def test_evict_queued_migrates_backlog_but_running_finishes(small):
    """The drain primitive: evict_queued fails ONLY the queued backlog
    (typed), refuses new admissions, and the running slot finishes its
    decode bit-exact — the finish-or-migrate split the fleet drain
    protocol is built on."""
    _, _, _, texts, refs = small
    srv = make_server(small, num_slots=1)
    hs = [srv.submit(texts[i]) for i in range(3)]
    srv.step()  # admit h0 only
    evicted = srv.evict_queued()
    assert [h.request_id for h in evicted] == [hs[1].request_id,
                                               hs[2].request_id]
    for h in evicted:
        assert isinstance(h.future.exception(), ServerStopped)
    assert srv.draining and not srv.stopped
    with pytest.raises(ServerStopped):
        srv.submit(texts[3])
    srv.run_until_idle(max_ticks=200)
    np.testing.assert_array_equal(hs[0].result(0), refs[0])


def test_backlog_feedback_signal(small):
    """backlog(): the cheap per-decision router feedback — queued per SLO
    class + running count, consistent with stats()['queue_depth']."""
    _, _, _, texts, _ = small
    srv = make_server(small, num_slots=1)
    assert srv.backlog() == {"queued": {LATENCY: 0, THROUGHPUT: 0},
                             "queued_total": 0, "running": 0}
    srv.submit(texts[0])
    srv.submit(texts[1], slo=LATENCY)
    srv.submit(texts[2])
    srv.step(tick=False)  # admit one (latency first)
    b = srv.backlog()
    assert b["running"] == 1
    assert b["queued"] == {LATENCY: 0, THROUGHPUT: 2}
    assert b["queued_total"] == 2
    assert srv.stats()["queue_depth"] == b["queued"]
    srv.run_until_idle(max_ticks=300)


# --- int8 quantized serving (ISSUE 7) -------------------------------------


import dataclasses  # noqa: E402


def _int8_setup(small, **overrides):
    """The `small` fixture's model re-planned for int8 serving (same
    params — the quantization flags are plan fields, not model identity)
    plus fresh greedy references through the int8 static sampler."""
    cfg, _, params, texts, _ = small
    cfg8 = dataclasses.replace(cfg, kv_cache_int8=True, **overrides)
    dalle8 = DALLE(cfg8)
    prefill = jax.jit(lambda p, t: prefill_codes(dalle8, p, t))

    def greedy_ref(i):
        fl, caches = prefill(params, jnp.asarray(texts[i])[None])
        return np.asarray(decode_codes(
            dalle8, params, fl, caches, jax.random.PRNGKey(7),
            filter_thres=1.0))[0]

    return cfg8, dalle8, params, texts, [greedy_ref(i) for i in range(4)]


@pytest.mark.parametrize("weights", [False, True])
def test_int8_serve_bit_matches_static_sampler(small, weights):
    """ISSUE 7 satellite: greedy serve through the int8 arena (per-slot
    scale planes, rotated int8 caches, session-quantized weights) is
    BIT-IDENTICAL to the int8 static `decode_codes` sampler, across
    mid-flight admissions — and still compiles each entry point once."""
    cfg8, dalle8, params, texts, refs = _int8_setup(
        small, weights_int8=weights)
    srv = GenerationServer(dalle8, params, num_slots=2, filter_thres=1.0)
    h0 = srv.submit(texts[0])
    for _ in range(5):
        srv.step()
    h1 = srv.submit(texts[1])          # joins mid-flight
    for _ in range(3):
        srv.step()
    h2 = srv.submit(texts[2])          # queued: both slots busy
    srv.run_until_idle(max_ticks=300)
    for h, r in ((h0, refs[0]), (h1, refs[1]), (h2, refs[2])):
        np.testing.assert_array_equal(h.result(0), r)
    assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}


@pytest.mark.parametrize("dim_head", [8, 64], ids=["plain", "folded"])
def test_int8_arena_carries_scale_planes(small, dim_head):
    """The int8 arena's cache entries are (int8 values, f32 per-slot
    per-head scale) pairs, scale planes init to ones (a zero scale would
    NaN the masked lanes' saturating re-quantize); the values in the form
    the rule gives a one-byte cache, the planes per head under either."""
    cfg8, dalle8, params, _, _ = _int8_setup(small)
    if dim_head != cfg8.dim_head:
        cfg8, dalle8, params = _rewidth(cfg8, dim_head)
    arena = SlotArena(dalle8, params, num_slots=3)
    for (k, v), shape in zip(arena.state["caches"],
                             _stored_shapes(cfg8, 3, dim_head == 64)):
        for values, scale in (k, v):
            assert values.dtype == jnp.int8
            assert values.shape == shape
            assert scale.dtype == jnp.float32
            assert scale.shape == (3, cfg8.heads, 1, 1)
            np.testing.assert_array_equal(np.asarray(scale), 1.0)


@pytest.mark.parametrize("int8", [False, True])
def test_aligned_span_reads_bit_match_gather(small, int8):
    """ISSUE 7 satellite (carried PR 6 follow-up): the serve path's
    circular-span sliced reads (aligned_span_decode=True, ≤2
    dynamic_slice spans per row) are BIT-IDENTICAL to the vmapped-gather
    control across mid-flight admissions, clock wrap, and sampled (non-
    greedy) decoding — same key order, values and masks, only the HBM
    access pattern differs."""
    cfg, _, params, texts, _ = small
    outs = {}
    for span in (True, False):
        cfg_v = dataclasses.replace(cfg, kv_cache_int8=int8,
                                    aligned_span_decode=span)
        srv = GenerationServer(DALLE(cfg_v), params, num_slots=2,
                               filter_thres=0.5)
        hs = [srv.submit(texts[i % len(texts)],
                         key=np.asarray([9, i], np.uint32))
              for i in range(5)]  # 5 requests through 2 slots: clock wraps
        srv.run_until_idle(max_ticks=1000)
        outs[span] = [h.result(0) for h in hs]
        assert srv.trace_counts() == {"prefill": 1, "admit": 1, "tick": 1}
    for a, b in zip(outs[True], outs[False]):
        np.testing.assert_array_equal(a, b)
