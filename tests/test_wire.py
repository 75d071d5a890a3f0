"""graftwire transport contract (serve/wire.py).

What these tests pin, in order:

* **Frames** — ``GWR1 | uint32 len | JSON`` roundtrips every payload
  shape the replica RPC carries, numpy arrays included, bit-exactly.
* **Typed failures** — each transport failure surfaces as exactly one
  exception class: refused → :class:`WireUnavailable`, deadline →
  :class:`WireTimeout`, peer-vanished → :class:`WireReset`, torn frame →
  :class:`WireProtocolError` (NEVER retried), handler exception →
  :class:`WireRemoteError` with the original type name.
* **Bounded retry** — the transient class (timeout/reset/unavailable)
  retries with exponential backoff + seeded jitter under ONE deadline
  shared by the whole attempt train; a seed pins the schedule.
* **Deterministic injection** — every ``GRAFT_FAULTS`` rpc action
  (drop / delay_ms / truncate / conn_reset) fires client-side on the
  exact Nth hit, so a spec string reproduces a failure bit-for-bit.
"""
import socket
import struct
import threading
import time

import numpy as np
import pytest

from dalle_pytorch_tpu.serve import wire
from dalle_pytorch_tpu.serve.wire import (WireClient, WireProtocolError,
                                          WireRemoteError, WireReset,
                                          WireServer, WireTimeout,
                                          WireUnavailable)
from dalle_pytorch_tpu.utils import faults, locks


@pytest.fixture(autouse=True)
def _fresh_faults():
    faults.install("")
    locks.reset()
    locks.arm()
    yield
    locks.disarm()
    locks.reset()
    faults.reset()


def _echo_server():
    return WireServer({
        "echo": lambda p: p,
        "boom": lambda p: (_ for _ in ()).throw(ValueError("kaboom")),
        "slow": lambda p: time.sleep(p.get("s", 1.0)) or "late",
    }).start()


# --- frames -----------------------------------------------------------------


def test_frame_roundtrip_json_and_numpy():
    payload = {"id": 7, "method": "submit",
               "params": {"text": np.arange(6, dtype=np.int32),
                          "key": np.asarray([0, 9], np.uint32),
                          "slo": "latency", "temperature": 1.0,
                          "nested": {"xs": [1, 2.5, None, "s"]}}}
    body = wire.encode(payload)
    assert body[:4] == wire.MAGIC
    (length,) = struct.unpack(">I", body[4:8])
    assert length == len(body) - 8
    back = wire.decode_body(body[8:])
    assert back["id"] == 7
    got = back["params"]["text"]
    assert isinstance(got, np.ndarray) and got.dtype == np.int32
    np.testing.assert_array_equal(got, np.arange(6, dtype=np.int32))
    assert back["params"]["key"].dtype == np.uint32
    assert back["params"]["nested"] == {"xs": [1, 2.5, None, "s"]}


def test_torn_body_is_protocol_error():
    body = wire.encode({"ok": 1})
    with pytest.raises(WireProtocolError):
        wire.decode_body(body[8: 8 + (len(body) - 8) // 2])


# --- typed failures over real sockets----------------------------------------


def test_echo_roundtrip_and_counters():
    srv = _echo_server()
    cli = WireClient(srv.host, srv.port)
    try:
        out = cli.call("echo", {"x": [1, 2, 3]})
        assert out == {"x": [1, 2, 3]}
        assert cli.calls == 1 and cli.retries == 0
        assert srv.requests == 1
    finally:
        cli.close()
        srv.close()


def test_remote_exception_carries_type_and_msg():
    srv = _echo_server()
    cli = WireClient(srv.host, srv.port)
    try:
        with pytest.raises(WireRemoteError) as ei:
            cli.call("boom", {})
        assert ei.value.etype == "ValueError"
        assert "kaboom" in ei.value.msg
        # remote errors are NOT transport failures: no retry burned
        assert cli.retries == 0
    finally:
        cli.close()
        srv.close()


def test_unknown_method_is_remote_error():
    srv = _echo_server()
    cli = WireClient(srv.host, srv.port)
    try:
        with pytest.raises(WireRemoteError) as ei:
            cli.call("nope", {})
        assert ei.value.etype == "NoSuchMethod"
    finally:
        cli.close()
        srv.close()


def test_connect_refused_is_unavailable():
    probe = socket.socket()
    probe.bind(("127.0.0.1", 0))
    port = probe.getsockname()[1]
    probe.close()  # nobody listening here now
    cli = WireClient("127.0.0.1", port, backoff_base_s=0.001,
                     backoff_cap_s=0.002)
    try:
        with pytest.raises(WireUnavailable):
            cli.call("echo", {}, deadline_s=2.0)
        # transient class: the full retry train ran before surfacing
        assert cli.retries == wire.RETRY_ATTEMPTS - 1
    finally:
        cli.close()


def test_deadline_is_shared_by_the_attempt_train():
    srv = _echo_server()
    cli = WireClient(srv.host, srv.port, backoff_base_s=0.01,
                     backoff_cap_s=0.02)
    try:
        t0 = time.monotonic()
        with pytest.raises(WireTimeout):
            cli.call("slow", {"s": 30.0}, deadline_s=0.4)
        # one deadline across ALL attempts — not deadline * attempts
        assert time.monotonic() - t0 < 5.0
    finally:
        cli.close()
        srv.close()


def test_peer_vanishing_midcall_is_reset_then_unavailable():
    srv = _echo_server()
    cli = WireClient(srv.host, srv.port, backoff_base_s=0.001,
                     backoff_cap_s=0.002)
    try:
        assert cli.call("echo", {"warm": 1}) == {"warm": 1}
        srv.close()  # peer dies between calls: cached socket goes stale
        with pytest.raises((WireReset, WireUnavailable, WireTimeout)):
            cli.call("echo", {"x": 2}, deadline_s=1.0)
    finally:
        cli.close()


# --- retry schedule ---------------------------------------------------------


def test_backoff_schedule_is_seeded_and_bounded():
    base, cap, jf = 0.05, 1.0, 0.25
    for seed in (0, 7):
        import random as _random
        rng = _random.Random(seed)
        waits = []
        for attempt in range(1, 4):
            b = min(base * (2 ** (attempt - 1)), cap)
            waits.append(b * (1.0 + jf * (2.0 * rng.random() - 1.0)))
        # the documented envelope: base*2^(k-1) +/- 25%, capped
        for k, w in enumerate(waits):
            b = min(base * (2 ** k), cap)
            assert b * (1 - jf) <= w <= b * (1 + jf)
        rng2 = _random.Random(seed)
        waits2 = [min(base * (2 ** k), cap)
                  * (1.0 + jf * (2.0 * rng2.random() - 1.0))
                  for k in range(3)]
        assert waits == waits2  # same seed -> same schedule


def test_protocol_error_never_retried():
    srv = _echo_server()
    faults.install("rpc_send:truncate=1")
    cli = WireClient(srv.host, srv.port)
    try:
        with pytest.raises(WireProtocolError):
            cli.call("echo", {"x": 1})
        assert cli.retries == 0  # fail-fast: no retry burned on a torn frame
    finally:
        cli.close()
        srv.close()


# --- fault actions, each deterministic at the wire --------------------------


def test_rpc_send_drop_times_out_without_execution():
    srv = _echo_server()
    faults.install("rpc_send:drop=1")
    cli = WireClient(srv.host, srv.port, retry_attempts=1)
    try:
        with pytest.raises(WireTimeout):
            cli.call("echo", {"x": 1}, deadline_s=0.3)
        assert srv.requests == 0  # the request never reached the peer
    finally:
        cli.close()
        srv.close()


def test_rpc_recv_drop_is_ambiguous_peer_did_execute():
    srv = _echo_server()
    faults.install("rpc_recv:drop=1")
    cli = WireClient(srv.host, srv.port, retry_attempts=1)
    try:
        with pytest.raises(WireTimeout):
            cli.call("echo", {"x": 1}, deadline_s=1.0)
        # THE ambiguous loss: the server executed, the caller timed out —
        # the idempotency layer above exists for exactly this
        assert srv.requests == 1
    finally:
        cli.close()
        srv.close()


def test_rpc_recv_drop_then_retry_succeeds():
    srv = _echo_server()
    faults.install("rpc_recv:drop=1")
    cli = WireClient(srv.host, srv.port, backoff_base_s=0.005,
                     backoff_cap_s=0.01)
    try:
        out = cli.call("echo", {"x": 5}, deadline_s=5.0)
        assert out == {"x": 5}
        assert cli.retries == 1  # one drop, one winning retry
        assert srv.requests == 2  # ... and the peer saw both sends
    finally:
        cli.close()
        srv.close()


def test_conn_reset_is_retried_to_success():
    srv = _echo_server()
    faults.install("rpc_send:conn_reset=1")
    cli = WireClient(srv.host, srv.port, backoff_base_s=0.005,
                     backoff_cap_s=0.01)
    try:
        assert cli.call("echo", {"x": 9}, deadline_s=5.0) == {"x": 9}
        assert cli.retries == 1
    finally:
        cli.close()
        srv.close()


def test_rpc_recv_truncate_is_protocol_error():
    srv = _echo_server()
    faults.install("rpc_recv:truncate=1")
    cli = WireClient(srv.host, srv.port)
    try:
        with pytest.raises(WireProtocolError):
            cli.call("echo", {"x": 1}, deadline_s=2.0)
        assert cli.retries == 0
    finally:
        cli.close()
        srv.close()


def test_delay_ms_slows_but_does_not_fail():
    srv = _echo_server()
    faults.install("rpc_send:delay_ms=120")
    cli = WireClient(srv.host, srv.port)
    try:
        t0 = time.monotonic()
        assert cli.call("echo", {"x": 1}, deadline_s=5.0) == {"x": 1}
        assert time.monotonic() - t0 >= 0.1  # the injected latency
        assert cli.retries == 0
    finally:
        cli.close()
        srv.close()


def test_server_survives_torn_inbound_frame():
    srv = _echo_server()
    try:
        raw = socket.create_connection((srv.host, srv.port))
        raw.sendall(wire.MAGIC + struct.pack(">I", 100) + b'{"half')
        raw.close()  # torn frame kills only THIS connection
        cli = WireClient(srv.host, srv.port)
        try:
            assert cli.call("echo", {"ok": 1}) == {"ok": 1}
        finally:
            cli.close()
    finally:
        srv.close()


def test_concurrent_clients_one_server():
    srv = _echo_server()
    outs = {}

    def worker(i):
        cli = WireClient(srv.host, srv.port)
        try:
            outs[i] = cli.call("echo", {"i": i})
        finally:
            cli.close()

    try:
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert outs == {i: {"i": i} for i in range(8)}
        assert srv.requests == 8
    finally:
        srv.close()
