"""graftwire remote-replica contract (serve/remote.py over serve/wire.py).

The claims, in dependency order:

* **Same surface, same bits** — a :class:`RemoteReplica` driving a
  replica over real sockets resolves futures with codes BIT-IDENTICAL
  to the in-process path (the wire is a scheduling change, not a model
  change).
* **Exactly-once across ambiguity** — requests are idempotent by wid
  (derived from the pinned key): a transport retry after a dropped
  response attaches to the execution already in flight (``dedup_hits``,
  ONE ``submits``); a router re-dispatch after an ambiguous
  :class:`ReplicaDown` dedups the same way; an acked SUCCESS pins the
  wid forever while an acked ERROR forgets it so a retry re-executes.
* **Failure class → policy** — each wire failure maps onto exactly one of
  the router's three policies: connect-refused → transport dead
  (policy 2: declare dead + migrate), ambiguous timeout on submit →
  typed :class:`ReplicaDown` (policy 1: retry elsewhere), torn frame →
  sticky unhealthy probe (policy 3: graceful drain), stale REMOTE
  heartbeat behind a live RPC plane → unhealthy probe (policy 3).
* **Fleet integration** — a FleetRouter over remote replicas migrates
  off a dead transport with zero dropped futures; the slow-marked leg
  does it against true subprocesses with SIGKILL and merges the child
  telemetry lanes into one fleet timeline.

Everything that touches the toy-model fixture is slow-tier (the module
compile alone costs ~10s on the single-core tier-1 budget); CI's
``loadgen_smoke`` step runs this file with ``--runslow``.  Tier-1 keeps
the model-free transport-policy check.
"""
import concurrent.futures
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_pytorch_tpu import DALLE, DALLEConfig, VAEConfig
from dalle_pytorch_tpu.models.dalle import decode_codes, prefill_codes
from dalle_pytorch_tpu.obs import merge_streams
from dalle_pytorch_tpu.serve import (DEAD, DRAINING, SERVING, FleetRouter,
                                     RemoteReplica, Replica, ReplicaDown,
                                     ReplicaServer, RouterError,
                                     spawn_replica)
from dalle_pytorch_tpu.serve import remote as serve_remote
from dalle_pytorch_tpu.utils import faults, locks

VCFG = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16, num_layers=2,
                 hidden_dim=8)
WAIT_S = 120.0


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.install("")
    locks.reset()
    locks.arm()
    yield
    locks.disarm()
    locks.reset()
    faults.reset()


@pytest.fixture(scope="module")
def small():
    cfg = DALLEConfig.from_vae(
        VCFG, dim=32, num_text_tokens=50, text_seq_len=6, depth=2, heads=2,
        dim_head=8, attn_types=("full", "axial_row"))
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


def _wait_state(replica, state, timeout_s=WAIT_S):
    deadline = time.monotonic() + timeout_s
    while replica.state != state:
        assert time.monotonic() < deadline, \
            f"{replica.name} stuck in {replica.state}, wanted {state}"
        time.sleep(0.02)


def _make_pair(small, name):
    """In-thread Replica + its wire front end, warmed to SERVING."""
    _, dalle, params, texts, _ = small
    replica = Replica(name, dalle, params, 2, filter_thres=1.0,
                      warmup_text=texts[0])
    rs = ReplicaServer(replica).start()
    replica.start()
    _wait_state(replica, SERVING)
    return replica, rs


@pytest.fixture(scope="module")
def pair(small):
    """One shared serving pair: tests isolate by using distinct wids
    (distinct text/key), so the server-side idempotency maps never
    collide across tests."""
    replica, rs = _make_pair(small, "rloc")
    yield replica, rs
    replica.halt()
    rs.close()


def _collect_until_done(rr, handle, timeout_s=WAIT_S):
    deadline = time.monotonic() + timeout_s
    while not handle.future.done():
        assert time.monotonic() < deadline, "future never resolved"
        rr._collect_once()
        time.sleep(0.02)


KEY = np.asarray([0, 11], np.uint32)


# --- same surface, same bits ------------------------------------------------


@pytest.mark.slow
def test_remote_submit_bit_matches_inprocess(small, pair):
    _, _, _, texts, refs = small
    replica, rs = pair
    rr = RemoteReplica("rr0", "127.0.0.1", rs.port).start()
    try:
        before = rs.submits
        h = rr.server.submit(texts[0], key=KEY)
        deadline = time.monotonic() + WAIT_S
        while not h.future.done():
            assert time.monotonic() < deadline
            time.sleep(0.02)  # the pump thread collects
        np.testing.assert_array_equal(h.future.result(0), refs[0])
        assert rs.submits == before + 1
        # the pump mirrored the remote lifecycle across the wire
        assert rr.state == SERVING
        assert rr.healthz()["ok"]
        assert rr.beat_age() < 5.0
    finally:
        rr.close()


# --- exactly-once across ambiguity ------------------------------------------


@pytest.mark.slow
def test_transport_retry_dedups_to_single_execution(small, pair):
    """A dropped RESPONSE (peer executed, caller never heard) is retried
    inside WireClient; the duplicate submit dedups by wid — one
    execution, bit-exact delivery."""
    _, _, _, texts, refs = small
    _, rs = pair
    rr = RemoteReplica("rr1", "127.0.0.1", rs.port)  # pump NOT started:
    # the Nth-hit fault counters stay deterministic
    before_sub, before_dup = rs.submits, rs.dedup_hits
    faults.install("rpc_recv:drop=1")
    try:
        h = rr.server.submit(texts[1], key=KEY)
        assert rr._client.retries == 1  # one drop, one winning retry
        assert rs.submits == before_sub + 1      # executed ONCE
        assert rs.dedup_hits == before_dup + 1   # the retry dedup'd
        _collect_until_done(rr, h)
        np.testing.assert_array_equal(h.future.result(0), refs[1])
    finally:
        rr.close()


@pytest.mark.slow
def test_ambiguous_timeout_redispatch_no_double_execution(small, pair):
    """THE idempotency scenario: every response dropped → the submit
    surfaces a typed ReplicaDown (ambiguous: the peer DID execute).  The
    router's re-dispatch replays the same pinned key → same wid → dedup
    onto the in-flight execution.  Exactly one execution, exactly one
    resolution, bits intact."""
    _, _, _, texts, refs = small
    _, rs = pair
    rr = RemoteReplica("rr2", "127.0.0.1", rs.port)
    before_sub = rs.submits
    # drop the response of all 3 attempts of the first call
    faults.install("rpc_recv:drop=1,rpc_recv:drop=2,rpc_recv:drop=3")
    try:
        with pytest.raises(ReplicaDown):
            rr.server.submit(texts[2], key=KEY)
        assert rs.submits == before_sub + 1  # the peer executed ONCE
        # the re-dispatch (faults spent): dedups, attaches, delivers
        h2 = rr.server.submit(texts[2], key=KEY)
        assert rs.submits == before_sub + 1  # STILL one execution
        _collect_until_done(rr, h2)
        np.testing.assert_array_equal(h2.future.result(0), refs[2])
    finally:
        rr.close()


@pytest.mark.slow
def test_acked_success_pins_wid_acked_error_forgets_it(small, pair):
    """The asymmetric ack contract: a delivered-and-acked SUCCESS makes
    later duplicates pure no-ops; a delivered-and-acked ERROR forgets
    the wid so the router's retry RE-EXECUTES instead of replaying a
    stale error."""
    _, _, _, texts, refs = small
    _, rs = pair
    rr = RemoteReplica("rr3", "127.0.0.1", rs.port)
    before_sub, before_dup = rs.submits, rs.dedup_hits
    try:
        # success path: run to delivery + ack
        h = rr.server.submit(texts[3], key=KEY)
        _collect_until_done(rr, h)
        rr._collect_once()  # the ack ships with the NEXT collect
        np.testing.assert_array_equal(h.future.result(0), refs[3])
        assert rs.submits == before_sub + 1
        # duplicate after acked success: dedup, zero executions
        h_dup = rr.server.submit(texts[3], key=KEY)
        assert rs.submits == before_sub + 1
        assert rs.dedup_hits == before_dup + 1

        # error path: next serve_request raises once
        faults.install("serve_request:fail_after=0")
        h_err = rr.server.submit(texts[4], key=KEY)
        _collect_until_done(rr, h_err)
        assert isinstance(h_err.future.exception(), faults.InjectedFault)
        rr._collect_once()  # ack the ERROR → the wid is forgotten
        assert rs.submits == before_sub + 2
        # the retry re-executes (the injected fault was one-shot)
        h_retry = rr.server.submit(texts[4], key=KEY)
        assert rs.submits == before_sub + 3  # a REAL new execution
        _collect_until_done(rr, h_retry)
        np.testing.assert_array_equal(h_retry.future.result(0), refs[4])
    finally:
        rr.close()


# --- failure class → policy -------------------------------------------------


def test_connect_refused_marks_transport_dead_policy2():
    """Nothing listening → WireUnavailable → transport dead: alive()
    goes False, which is EXACTLY the signal the router monitor's
    policy 2 (declare dead + migrate) consumes."""
    import socket
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()
    rr = RemoteReplica("rdead", "127.0.0.1", port,
                       call_timeout_s=1.0).start()
    try:
        hz = rr.healthz()
        assert hz["ok"] is False
        assert not rr.alive()  # policy 2's liveness check
        with pytest.raises(ReplicaDown):
            rr.server.submit(np.zeros(6, np.int32), key=KEY)
    finally:
        rr.close()


@pytest.mark.slow
def test_ambiguous_submit_failure_is_typed_replica_down_policy1(small, pair):
    """All sends dropped → the deadline fires → typed ReplicaDown
    carrying the wire cause: the future-exception shape policy 1
    retries onto another replica."""
    _, _, _, texts, _ = small
    _, rs = pair
    rr = RemoteReplica("rr4", "127.0.0.1", rs.port, submit_timeout_s=0.5)
    before = rs.submits
    faults.install("rpc_send:drop=1,rpc_send:drop=2,rpc_send:drop=3")
    try:
        with pytest.raises(ReplicaDown) as ei:
            rr.server.submit(texts[5], key=KEY)
        assert "WireTimeout" in str(ei.value)
        assert rs.submits == before  # dropped SENDS: peer never executed
        assert not rr._dead  # ambiguous != dead: the replica stays usable
    finally:
        rr.close()


@pytest.mark.slow
def test_protocol_error_is_sticky_unhealthy_policy3(small, pair):
    """A torn frame means the wire itself can't be trusted: the probe
    reports unhealthy and KEEPS reporting unhealthy after the fault
    clears — the shape policy 3 turns into a graceful drain."""
    _, rs = pair
    rr = RemoteReplica("rr5", "127.0.0.1", rs.port)
    faults.install("rpc_recv:truncate=1")
    try:
        assert rr.healthz()["ok"] is False
        faults.install("")  # the wire works again...
        hz = rr.healthz()
        assert hz["ok"] is False  # ...but trust does not come back
        assert "protocol error" in hz["error"]
        assert not rr._dead  # drain-shaped, not dead-shaped
    finally:
        rr.close()


@pytest.mark.slow
def test_stale_remote_heartbeat_is_unhealthy_policy3(small, pair):
    """The remote DRIVER wedged while its RPC plane still answers: the
    probe relays the remote beat age and the client-side staleness
    threshold turns it into unhealthy (policy 3 drains it)."""
    _, rs = pair
    # remote_stale_s < 0: ANY remote beat age reads as stale — the
    # deterministic stand-in for a wedged driver behind a live socket
    rr = RemoteReplica("rr6", "127.0.0.1", rs.port, remote_stale_s=-1.0)
    try:
        hz = rr.healthz()
        assert hz["ok"] is False
        assert "stale" in hz["error"]
        assert not rr._dead
    finally:
        rr.close()


# --- fleet integration ------------------------------------------------------


@pytest.mark.slow
def test_router_migrates_off_dead_transport_zero_dropped(small):
    """Policy 2 end-to-end over the wire: kill one remote's transport
    under traffic — the router declares it dead, migrates its work via
    pinned-key replay, and every future resolves bit-exact."""
    _, _, _, texts, refs = small
    rep_a, rs_a = _make_pair(small, "ra")
    rep_b, rs_b = _make_pair(small, "rb")
    ra = RemoteReplica("ra", "127.0.0.1", rs_a.port, proc=None)
    rb = RemoteReplica("rb", "127.0.0.1", rs_b.port, proc=None)
    router = FleetRouter([ra, rb], retry_backoff_s=0.01,
                         monitor_interval_s=0.01, probe_every_s=0.1,
                         heartbeat_timeout_s=1.0,
                         shed_bounds={"latency": 10_000,
                                      "throughput": 10_000})
    router.start()
    try:
        router.wait_serving(2, timeout_s=WAIT_S)
        hs = [router.submit(texts[i % len(texts)]) for i in range(6)]
        # kill ONE transport (listener + conns): its remote goes
        # unavailable, policy 2 fires, the work migrates to the survivor
        rs_b.close()
        deadline = time.monotonic() + WAIT_S
        for h in hs:
            try:
                h.future.exception(max(0.1, deadline - time.monotonic()))
            except concurrent.futures.TimeoutError:
                pass  # converted into the done() failure below
        for i, h in enumerate(hs):
            assert h.future.done(), f"future {h.request_id} never resolved"
            if h.future.exception() is None:
                np.testing.assert_array_equal(
                    h.result(0), refs[i % len(texts)])
            else:
                assert isinstance(h.future.exception(), RouterError)
        audit = router.audit()
        assert audit["balanced"], audit
        assert audit["outstanding"] == 0, audit
        assert audit["resolved_ok"] == 6, audit  # migration lost nothing
        locks.assert_acyclic()
    finally:
        router.close()
        rs_a.close()
        rs_b.close()
        rep_a.halt()
        rep_b.halt()


@pytest.mark.slow
def test_subprocess_fleet_sigkill_migrates_and_lanes_merge(small, tmp_path):
    """The true process-remote leg: two spawned children (own telemetry
    lanes, own metrics ports), SIGKILL one mid-traffic, zero dropped
    futures, and the child lanes merge into one fleet timeline."""
    _, _, _, texts, refs = small
    os.environ["GRAFT_CLOCK_RDV"] = str(tmp_path / "rdv")
    try:
        remotes = [spawn_replica(f"s{i}", out_dir=tmp_path, slots=2,
                                 host_index=i + 1)
                   for i in range(2)]
        router = FleetRouter(remotes, retry_backoff_s=0.05,
                             monitor_interval_s=0.02, probe_every_s=0.2,
                             heartbeat_timeout_s=2.0,
                             shed_bounds={"latency": 10_000,
                                          "throughput": 10_000})
        router.start()
        try:
            router.wait_serving(2, timeout_s=240.0)
            hs = [router.submit(texts[i % 4]) for i in range(6)]
            remotes[1].proc.send_signal(signal.SIGKILL)
            deadline = time.monotonic() + 240.0
            for h in hs:
                try:
                    h.future.exception(max(0.1,
                                           deadline - time.monotonic()))
                except concurrent.futures.TimeoutError:
                    pass
            ok = 0
            for i, h in enumerate(hs):
                assert h.future.done()
                if h.future.exception() is None:
                    ok += 1
                    np.testing.assert_array_equal(h.result(0),
                                                  refs[i % 4])
            audit = router.audit()
            assert audit["balanced"] and audit["outstanding"] == 0, audit
            assert ok == 6, audit  # SIGKILL lost nothing
            assert audit["replica_deaths"] >= 1
        finally:
            router.close()
        events, clocks = merge_streams([tmp_path / "s0", tmp_path / "s1"])
        assert len(clocks) == 2  # one aligned lane per child process
        assert any(e.get("kind") == "serve" for e in events)
    finally:
        os.environ.pop("GRAFT_CLOCK_RDV", None)


@pytest.mark.slow
def test_spawned_replica_metrics_and_clean_drain(tmp_path):
    """Spawn plumbing: ready-file handshake, live /metrics + /healthz in
    the CHILD, graceful drain-to-exit."""
    import urllib.request
    rr = spawn_replica("m0", out_dir=tmp_path, slots=2, host_index=1,
                       metrics_port=0)
    try:
        ready = json.loads((tmp_path / "m0.ready.json").read_text())
        assert ready["pid"] == rr.proc.pid
        with urllib.request.urlopen(
                f"http://127.0.0.1:{ready['metrics_port']}/healthz",
                timeout=10) as resp:
            assert resp.status == 200
        rr.start()
        _wait_state(rr, SERVING)
        rr.begin_drain(reason="test")
        assert rr.state == DRAINING
        rr.finish_drain()
        assert rr.state == DEAD
        assert rr.proc.wait(timeout=30) == 0  # clean exit via final stop
    finally:
        rr.close()
