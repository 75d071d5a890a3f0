"""graftwire: the fleet's RPC transport, with injectable failure.

The :class:`~.replica.Replica` contract (submit / collect / healthz /
drain / stop) was the transport boundary by design — DESIGN.md §17 kept
the router ignorant of everything behind ``Replica``'s surface.  This
module carries that contract across a process boundary on nothing but
the stdlib: length-prefixed JSON frames over a TCP socket, so a
``RemoteReplica`` (serve/remote.py) can drive a ``GenerationServer``
living in a subprocess while ``FleetRouter`` stays unchanged above the
seam.

**Frames.**  Every message is ``MAGIC (4B) | length (uint32 BE) | JSON
payload``.  Requests are ``{"id": seq, "method": name, "params": {...}}``;
responses ``{"id": seq, "ok": result}`` or ``{"id": seq, "err":
{"type": ExcName, "msg": str}}``.  numpy arrays ride as
``{"__nd__": [dtype, shape, flat-list]}`` — token ids and decoded codes
are small int32 vectors, so JSON beats inventing a binary layout the
next reader has to learn.

**Failure is typed, and the types are the classification** the router's three
policies key off (see serve/remote.py for the mapping):

* :class:`WireUnavailable` — connect refused / no listener: the peer
  process is GONE (→ DEAD + migrate).
* :class:`WireTimeout` — the deadline expired with no response: maybe
  the request was lost, maybe only the response was — the *ambiguous*
  failure (→ retry, idempotent by request id, then migrate).
* :class:`WireReset` — the connection died mid-call (→ retry/migrate,
  same ambiguity as a timeout).
* :class:`WireProtocolError` — a torn or malformed frame: the bytes
  themselves can't be trusted, so retrying the same bytes is wrong
  (NEVER retried at this layer → surfaces as a health failure → drain).

**Every call** gets a deadline, bounded retries, and exponential
backoff with deterministic jitter — module constants, so the fleet has
ONE retry policy, not one per caller.

**Injection** (utils/faults.py): the ``rpc_send`` / ``rpc_recv`` sites
fire once per frame the CLIENT writes/reads — never on the server side,
so a test whose client and server share one in-process registry can aim
``rpc_send:drop=3`` at exactly the third outbound frame.  Actions:
``drop=N`` (the frame vanishes; a dropped recv is read-then-discarded,
i.e. the server executed — the idempotency drill), ``conn_reset=N``
(the socket is torn), ``truncate=N`` (half a frame → protocol error),
``delay_ms=V`` (per-hit latency).
"""
from __future__ import annotations

import json
import random
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

from ..obs import telemetry
from ..utils import faults
from ..utils import locks

MAGIC = b"GWR1"
MAX_FRAME_BYTES = 64 * 1024 * 1024  # a torn length field must not OOM us

# ONE retry policy for the fleet: every caller of the transport reads
# these constants
RETRY_ATTEMPTS = 3        # total tries per call
BACKOFF_BASE_S = 0.05     # first retry waits ~this
BACKOFF_CAP_S = 1.0       # exponential growth stops here
JITTER_FRAC = 0.25        # +/- fraction of the backoff, decorrelates herds


class WireError(RuntimeError):
    """Base of every transport-layer failure a :class:`WireClient` call
    can raise.  Subclasses ARE the failure classes; callers map them to
    router policy, never parse messages."""


class WireUnavailable(WireError):
    """No listener: connect refused / name resolution / socket create
    failed.  The peer process is gone or never existed."""


class WireTimeout(WireError):
    """The call's deadline expired before a response arrived.  Ambiguous
    by nature: the request OR the response may have been lost — retries
    must be idempotent."""


class WireReset(WireError):
    """The connection died mid-call (ECONNRESET / broken pipe / EOF at a
    frame boundary).  Same ambiguity as a timeout."""


class WireProtocolError(WireError):
    """A malformed frame: bad magic, torn payload, unparseable JSON, or
    a response id that can't belong to this call.  Never retried at the
    transport layer — the same bytes would tear the same way."""


class WireRemoteError(WireError):
    """The peer executed the call and raised: ``etype`` carries the
    remote exception class name, ``msg`` its text.  Not a transport
    failure — the wire worked; the caller maps ``etype`` to a local
    exception (serve/remote.py keeps the table)."""

    def __init__(self, etype: str, msg: str):
        super().__init__(f"remote {etype}: {msg}")
        self.etype = etype
        self.msg = msg


# --- encoding ---------------------------------------------------------------


def _default(obj):
    if isinstance(obj, np.ndarray):
        return {"__nd__": [obj.dtype.str, list(obj.shape),
                           obj.ravel().tolist()]}
    if isinstance(obj, (np.integer,)):
        return int(obj)
    if isinstance(obj, (np.floating,)):
        return float(obj)
    raise TypeError(f"not wire-encodable: {type(obj).__name__}")


def _object_hook(d):
    nd = d.get("__nd__")
    if nd is not None and len(d) == 1:
        dtype, shape, flat = nd
        return np.asarray(flat, dtype=np.dtype(dtype)).reshape(shape)
    return d


def encode(payload: Any) -> bytes:
    """One frame: MAGIC | uint32 length | JSON (numpy-aware)."""
    body = json.dumps(payload, default=_default,
                      separators=(",", ":")).encode("utf-8")
    return MAGIC + struct.pack(">I", len(body)) + body


def decode_body(body: bytes) -> Any:
    try:
        return json.loads(body.decode("utf-8"), object_hook=_object_hook)
    except (ValueError, UnicodeDecodeError) as e:
        raise WireProtocolError(f"unparseable frame body: {e}") from e


def _recv_exact(sock: socket.socket, n: int, *, mid_frame: bool) -> bytes:
    """Read exactly n bytes.  EOF at a frame boundary is a RESET (the
    peer closed between calls — retryable); EOF mid-frame is a torn
    frame (protocol error: bytes were lost, not a connection)."""
    buf = b""
    while len(buf) < n:
        try:
            chunk = sock.recv(n - len(buf))
        except socket.timeout as e:
            raise WireTimeout("recv timed out mid-frame" if buf or mid_frame
                              else "recv timed out") from e
        except OSError as e:
            raise WireReset(f"recv failed: {e}") from e
        if not chunk:
            if buf or mid_frame:
                raise WireProtocolError(
                    f"torn frame: EOF after {len(buf)}/{n} bytes")
            raise WireReset("peer closed the connection")
        buf += chunk
    return buf


def read_frame(sock: socket.socket) -> Any:
    """Read one frame off ``sock`` (numpy-aware payload)."""
    header = _recv_exact(sock, 8, mid_frame=False)
    if header[:4] != MAGIC:
        raise WireProtocolError(f"bad magic {header[:4]!r}")
    (length,) = struct.unpack(">I", header[4:8])
    if length > MAX_FRAME_BYTES:
        raise WireProtocolError(f"frame length {length} exceeds cap")
    return decode_body(_recv_exact(sock, length, mid_frame=True))


# --- client -----------------------------------------------------------------


class WireClient:
    """One connection + one in-flight call at a time (serialized by a
    TracedLock — the pump/probe callers each own their own client when
    they must not contend).  Reconnects lazily; EVERY transport error
    closes the socket so a retry starts from a clean connection and a
    stale response can never be matched to a new call."""

    def __init__(self, host: str, port: int, *,
                 timeout_s: float = 5.0,
                 retry_attempts: int = RETRY_ATTEMPTS,
                 backoff_base_s: float = BACKOFF_BASE_S,
                 backoff_cap_s: float = BACKOFF_CAP_S,
                 jitter_frac: float = JITTER_FRAC,
                 jitter_seed: int = 0,
                 time_fn=time.monotonic):
        self.host = str(host)
        self.port = int(port)
        self.timeout_s = float(timeout_s)
        self.retry_attempts = int(retry_attempts)
        self.backoff_base_s = float(backoff_base_s)
        self.backoff_cap_s = float(backoff_cap_s)
        self.jitter_frac = float(jitter_frac)
        self._time = time_fn
        # deterministic jitter: tests pin the backoff schedule by seed
        self._rng = random.Random(jitter_seed)
        self._lock = locks.TracedLock("wire.client")
        self._sock: Optional[socket.socket] = None
        self._seq = 0
        self._sleep_evt = threading.Event()  # interruptible backoff sleep
        self.calls = 0
        self.retries = 0

    # -- connection management --

    def _connect(self, deadline: float) -> socket.socket:
        if self._sock is not None:
            return self._sock
        try:
            sock = socket.create_connection(
                (self.host, self.port),
                timeout=max(0.001, deadline - self._time()))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        except socket.timeout as e:
            raise WireTimeout(f"connect to {self.host}:{self.port} "
                              "timed out") from e
        except OSError as e:
            raise WireUnavailable(
                f"connect to {self.host}:{self.port} failed: {e}") from e
        self._sock = sock
        return sock

    def _teardown(self) -> None:
        sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def close(self) -> None:
        with self._lock:
            self._sleep_evt.set()
            self._teardown()

    # -- fault injection (CLIENT side only) --

    def _fire_site(self, site: str) -> frozenset:
        delay_ms = faults.get_registry().config(site, "delay_ms")
        if delay_ms:
            # injected network latency: a plain bounded wait, per hit
            self._sleep_evt.wait(delay_ms / 1000.0)
        try:
            acts = faults.fire(site)
        except faults.InjectedFault as e:
            # fail_after/every on an rpc site: a generic transient
            # transport failure — same shape as a reset
            self._teardown()
            raise WireReset(f"injected transport fault at {site}") from e
        if "conn_reset" in acts:
            self._teardown()
            raise WireReset(f"injected conn_reset at {site}")
        return acts

    # -- the call --

    def call(self, method: str, params: Optional[dict] = None, *,
             deadline_s: Optional[float] = None) -> Any:
        """Invoke ``method`` on the peer; returns the decoded result.

        Bounded retry with exponential backoff + jitter on
        timeout/reset/unavailable (the ambiguous-or-transient class);
        protocol errors and remote errors surface immediately.  The
        whole attempt train shares ONE deadline."""
        deadline = self._time() + (self.timeout_s if deadline_s is None
                                   else float(deadline_s))
        last: Optional[WireError] = None
        with self._lock:
            self.calls += 1
            for attempt in range(1, self.retry_attempts + 1):
                try:
                    return self._call_once(method, params or {}, deadline)
                except (WireTimeout, WireReset, WireUnavailable) as e:
                    self._teardown()
                    last = e
                    telemetry.emit("wire", "retry", method=method,
                                   attempt=attempt, error=repr(e))
                    if attempt >= self.retry_attempts:
                        break
                    backoff = min(self.backoff_base_s * (2 ** (attempt - 1)),
                                  self.backoff_cap_s)
                    backoff *= 1.0 + self.jitter_frac * (
                        2.0 * self._rng.random() - 1.0)
                    if self._time() + backoff >= deadline:
                        break  # no budget left for another attempt
                    self.retries += 1
                    self._sleep_evt.wait(backoff)
                except WireProtocolError:
                    self._teardown()
                    raise
        assert last is not None
        raise last

    def _call_once(self, method: str, params: dict, deadline: float) -> Any:
        budget = deadline - self._time()
        if budget <= 0:
            raise WireTimeout(f"{method}: deadline exhausted before send")
        sock = self._connect(deadline)
        sock.settimeout(budget)
        self._seq += 1
        seq = self._seq
        frame = encode({"id": seq, "method": method, "params": params})

        acts = self._fire_site("rpc_send")
        if "truncate" in acts:
            # a torn outbound frame: the peer's reader discards it and
            # the connection is garbage — protocol error, not retried
            try:
                sock.sendall(frame[: max(1, len(frame) // 2)])
            except OSError:
                pass
            self._teardown()
            raise WireProtocolError(
                f"injected truncate at rpc_send ({method})")
        if "drop" not in acts:
            try:
                sock.sendall(frame)
            except socket.timeout as e:
                raise WireTimeout(f"{method}: send timed out") from e
            except OSError as e:
                raise WireReset(f"{method}: send failed: {e}") from e
        # a dropped send still WAITS: the caller learns via deadline,
        # exactly like a frame lost in the network

        while True:
            sock.settimeout(max(0.001, deadline - self._time()))
            resp = self._read_response(sock, method)
            rid = resp.get("id")
            if rid == seq:
                break
            if isinstance(rid, int) and rid < seq:
                continue  # stale response from an abandoned call: discard
            self._teardown()
            raise WireProtocolError(
                f"{method}: response id {rid!r} for request {seq}")
        if "err" in resp:
            err = resp["err"]
            raise WireRemoteError(str(err.get("type", "Exception")),
                                  str(err.get("msg", "")))
        return resp.get("ok")

    def _read_response(self, sock: socket.socket, method: str) -> dict:
        acts = self._fire_site("rpc_recv")
        if "truncate" in acts:
            # read-and-tear: pull the length header, then parse half the
            # body — the torn-frame read path, deterministically
            header = _recv_exact(sock, 8, mid_frame=False)
            if header[:4] != MAGIC:
                raise WireProtocolError(f"bad magic {header[:4]!r}")
            (length,) = struct.unpack(">I", header[4:8])
            body = _recv_exact(sock, length, mid_frame=True)
            self._teardown()
            return decode_body(body[: length // 2])  # raises
        resp = read_frame(sock)
        if not isinstance(resp, dict):
            raise WireProtocolError(f"{method}: non-object response")
        if "drop" in acts:
            # the response existed — the peer EXECUTED — but never
            # reached the caller: the ambiguous loss idempotency is for
            raise WireTimeout(
                f"{method}: response dropped (injected rpc_recv drop)")
        return resp


# --- server -----------------------------------------------------------------


class WireServer:
    """Frame server: one accept thread, one thread per connection, a
    dict of ``method -> callable(params) -> result``.  The server side
    NEVER fires fault sites — injection belongs to the caller's edge so
    shared-registry tests stay deterministic.  Handler exceptions are
    serialized as ``{type, msg}`` and the connection survives them; torn
    inbound frames close only that connection."""

    def __init__(self, handlers: Dict[str, Callable[[dict], Any]], *,
                 host: str = "127.0.0.1", port: int = 0):
        self.handlers = dict(handlers)
        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(16)
        self.host, self.port = self._listener.getsockname()[:2]
        self._stop_evt = threading.Event()
        self._lock = locks.TracedLock("wire.server")
        self._conns: list = []
        self._threads: list = []
        self._accept_thread: Optional[threading.Thread] = None
        self.requests = 0

    def start(self) -> "WireServer":
        assert self._accept_thread is None, "wire server already started"
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name=f"wire-accept-{self.port}",
            daemon=True)
        self._accept_thread.start()
        return self

    def _accept_loop(self) -> None:
        while not self._stop_evt.is_set():
            try:
                conn, _addr = self._listener.accept()
            except OSError:
                return  # listener closed: shutdown
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            t = threading.Thread(
                target=self._serve_conn, args=(conn,),
                name=f"wire-conn-{self.port}", daemon=True)
            with self._lock:
                self._conns.append(conn)
                self._threads.append(t)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        try:
            while not self._stop_evt.is_set():
                try:
                    req = read_frame(conn)
                except (WireReset, WireProtocolError, WireTimeout):
                    return  # torn/closed connection: drop it, serve on
                if not isinstance(req, dict) or "method" not in req:
                    return
                with self._lock:
                    self.requests += 1
                resp: dict = {"id": req.get("id")}
                handler = self.handlers.get(str(req["method"]))
                if handler is None:
                    resp["err"] = {"type": "NoSuchMethod",
                                   "msg": str(req["method"])}
                else:
                    try:
                        resp["ok"] = handler(req.get("params") or {})
                    # graftlint: disable=EXC001 (the RPC boundary: every handler exception is serialized typed to the caller, which maps it to router policy — swallowing here IS the delivery)
                    except Exception as e:
                        resp["err"] = {"type": type(e).__name__,
                                       "msg": str(e)}
                try:
                    conn.sendall(encode(resp))
                except OSError:
                    return  # peer gone mid-response
        finally:
            try:
                conn.close()
            except OSError:
                pass

    def close(self) -> None:
        self._stop_evt.set()
        try:
            # close() alone does not wake a thread blocked in accept();
            # shutdown() makes it return EINVAL immediately.
            self._listener.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._listener.close()
        except OSError:
            pass
        with self._lock:
            conns, self._conns = list(self._conns), []
            threads, self._threads = list(self._threads), []
        for c in conns:
            try:
                c.close()
            except OSError:
                pass
        if self._accept_thread is not None:
            self._accept_thread.join(timeout=2.0)
            self._accept_thread = None
        for t in threads:
            t.join(timeout=2.0)
