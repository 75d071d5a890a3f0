"""graftwire remote replicas: a GenerationServer in another process.

Two halves of one seam (DESIGN.md §21):

* :class:`ReplicaServer` — runs NEXT TO a :class:`~.replica.Replica`
  (in the subprocess, or in-thread for deterministic tests) and exposes
  its contract over :mod:`~.wire`: ``submit`` / ``collect`` /
  ``healthz`` / ``drain`` / ``stop`` / ``ping``.  Results are delivered
  **at-least-once with acks** (a result stays buffered until the client
  acknowledges it in a later ``collect``), and submissions are
  **idempotent by wid** — a work id the client derives from the pinned
  request key — so a retry after an ambiguous timeout can never
  double-execute: the duplicate submit attaches to the execution
  already in flight.
* :class:`RemoteReplica` — the client half, presenting the exact
  ``Replica`` surface :class:`~.router.FleetRouter` already consumes
  (``state`` / ``alive()`` / ``beat_age()`` / ``healthz()`` /
  ``begin_drain`` / ``finish_drain`` / ``halt`` / ``server.submit`` /
  ``server.backlog()``), so the router needs NO remote-aware code.

The transport failure classes map onto the router's three existing
policies:

======================  =====================================  ========
wire failure            RemoteReplica surface                  policy
======================  =====================================  ========
connect refused         ``alive()`` → False                    2: DEAD + migrate
deadline / reset        ``submit`` raises :class:`ReplicaDown` 1: retry → migrate
torn frame (protocol)   ``healthz()`` → ``ok: False`` sticky   3: drain
remote heartbeat stale  ``healthz()`` → ``ok: False``          3: drain
======================  =====================================  ========

The subprocess entry point (``python -m dalle_pytorch_tpu.serve.remote``)
builds the CI-scale toy model, owns its OWN graftscope lane
(``--telemetry-dir``, with its own boot nonce and clock beacons — the
merged fleet report aligns it like any other host) and its own
``/metrics`` port, and announces readiness by atomically writing a JSON
ready-file (``{port, metrics_port, pid}``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import signal
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import telemetry
from ..utils import faults
from ..utils import locks
from . import wire
from .replica import DEAD, DRAINING, JOINING, SERVING, Replica, ReplicaDown
from .scheduler import LATENCY, SLO_CLASSES, THROUGHPUT, ServerStopped

REPO_ROOT = Path(__file__).resolve().parents[2]

_EMPTY_BACKLOG = {"queued": {slo: 0 for slo in SLO_CLASSES},
                  "queued_total": 0, "running": 0}

# scale_signals before first contact: unknown headroom, zero ledger —
# the autoscaler treats an all-default row as "no information yet"
_EMPTY_SIGNALS = {"queued": {slo: 0 for slo in SLO_CLASSES}, "running": 0,
                  "num_slots": 0, "headroom_bytes": None,
                  "predicted_bytes_per_token": 0, "ledger_fingerprint": ""}


class SpawnFailed(RuntimeError):
    """:func:`spawn_replica`'s ready-file handshake failed: the child
    exited before announcing readiness (``rc`` set) or never wrote the
    ready file inside the timeout (``rc`` None).  Either way the child
    has been killed AND reaped before this raises — a failed spawn never
    leaks an orphan process.  graftscale's spawn budget counts these."""

    def __init__(self, msg: str, *, name: str = "",
                 rc: Optional[int] = None):
        super().__init__(msg)
        self.name = name
        self.rc = rc

# remote exception-name -> local type: how a collected error re-raises
# on the caller's side of the wire.  Transient types keep their transient
# meaning (the router retries them); anything unknown is terminal.
_TRANSIENT_ERRORS = {
    "ReplicaDown": ReplicaDown,
    "ServerStopped": ServerStopped,
    "InjectedFault": faults.InjectedFault,
}


def _map_remote_error(err: dict) -> BaseException:
    etype = str(err.get("type", "Exception"))
    msg = str(err.get("msg", ""))
    cls = _TRANSIENT_ERRORS.get(etype)
    if cls is not None:
        return cls(f"remote {etype}: {msg}")
    return RuntimeError(f"remote {etype}: {msg}")


# --- server half ------------------------------------------------------------


class ReplicaServer:
    """Wire front end over a local :class:`Replica`.

    Exactly-once bookkeeping: ``_pending`` holds executions in flight,
    ``_done`` holds results awaiting an ack, ``_delivered_ok`` pins the
    wids whose SUCCESS was acknowledged (a duplicate submit of one of
    those is a pure no-op).  An acknowledged *error* forgets its wid
    entirely — the router retrying the same replica after a transient
    failure must re-execute, not replay the stale error."""

    def __init__(self, replica: Replica, *, host: str = "127.0.0.1",
                 port: int = 0):
        self.replica = replica
        self._lock = locks.TracedLock("remote.server")
        self._pending: Dict[str, object] = {}
        self._done: Dict[str, dict] = {}
        self._delivered_ok: set = set()
        self.dedup_hits = 0
        self.submits = 0
        self.shutdown_evt = threading.Event()
        self._wire = wire.WireServer({
            "submit": self._h_submit,
            "collect": self._h_collect,
            "healthz": self._h_healthz,
            "drain": self._h_drain,
            "stop": self._h_stop,
            "ping": self._h_ping,
        }, host=host, port=port)
        self.port = self._wire.port

    def start(self) -> "ReplicaServer":
        self._wire.start()
        return self

    def close(self) -> None:
        self._wire.close()

    def wait_shutdown(self, timeout_s: Optional[float] = None) -> bool:
        return self.shutdown_evt.wait(timeout_s)

    # -- handlers (run on wire connection threads) --

    def _h_submit(self, params: dict) -> dict:
        wid = str(params["wid"])
        with self._lock:
            if (wid in self._pending or wid in self._done
                    or wid in self._delivered_ok):
                # the idempotency contract: a duplicate submit (transport
                # retry, or a router re-dispatch after an ambiguous
                # timeout) attaches to the execution already in flight
                self.dedup_hits += 1
                return {"accepted": True, "dup": True}
        handle = self.replica.server.submit(
            np.asarray(params["text"], np.int32),
            slo=str(params.get("slo", THROUGHPUT)),
            temperature=float(params.get("temperature", 1.0)),
            key=np.asarray(params["key"], np.uint32))
        with self._lock:
            self.submits += 1
            self._pending[wid] = handle
        handle.future.add_done_callback(
            lambda f, wid=wid: self._on_done(wid, f))
        return {"accepted": True, "dup": False}

    def _on_done(self, wid: str, f: Future) -> None:
        exc = f.exception()
        if exc is None:
            entry = {"wid": wid, "ok": np.asarray(f.result(0))}
        else:
            entry = {"wid": wid, "err": {"type": type(exc).__name__,
                                         "msg": str(exc)}}
        with self._lock:
            self._pending.pop(wid, None)
            self._done[wid] = entry

    def _heartbeat(self) -> dict:
        r = self.replica
        return {"state": r.state, "beat_age_s": round(r.beat_age(), 4),
                "ticks": r.ticks, "work_ticks": r.work_ticks,
                "busy": bool(r.server.busy),
                "backlog": r.server.backlog(),
                # graftscale's observation row rides every collect, so
                # the client-side cache is at most one pump tick stale
                "signals": r.server.scale_signals()}

    def _h_collect(self, params: dict) -> dict:
        with self._lock:
            for wid in params.get("ack") or ():
                entry = self._done.pop(str(wid), None)
                if entry is not None and "ok" in entry:
                    self._delivered_ok.add(str(wid))
            results = list(self._done.values())
        return {"results": results, **self._heartbeat()}

    def _h_healthz(self, params: dict) -> dict:
        return self.replica.healthz()

    def _h_drain(self, params: dict) -> dict:
        evicted = self.replica.begin_drain(
            reason=str(params.get("reason", "remote drain")))
        return {"draining": True, "evicted": len(evicted)}

    def _h_stop(self, params: dict) -> dict:
        mode = str(params.get("mode", "halt"))
        if mode == "drain":
            left = self.replica.finish_drain()
        else:
            left = self.replica.halt(ReplicaDown(
                f"replica {self.replica.name}: remote halt"))
        if params.get("final"):
            self.shutdown_evt.set()
        return {"stopped": True, "mode": mode, "left": len(left)}

    def _h_ping(self, params: dict) -> dict:
        return {"ok": True, "pid": os.getpid(),
                "replica": self.replica.name}


# --- client half ------------------------------------------------------------


@dataclasses.dataclass
class RemoteHandle:
    """Client-side stand-in for a remote ``ServeHandle``: the local
    future the router wires its done-callback to."""

    request_id: int
    wid: str
    slo: str
    future: Future


class _RemoteServerFacade:
    """The slice of ``GenerationServer``'s surface the router touches
    (``submit`` / ``backlog()`` / ``busy``), backed by RPC + the cached
    heartbeat the collect pump refreshes."""

    def __init__(self, remote: "RemoteReplica"):
        self._r = remote

    def submit(self, text, *, slo: str = THROUGHPUT,
               temperature: float = 1.0, key=None):
        return self._r._submit(text, slo=slo, temperature=temperature,
                               key=key)

    def backlog(self) -> dict:
        return self._r._cached_backlog()

    def scale_signals(self) -> dict:
        return self._r._cached_signals()

    @property
    def busy(self) -> bool:
        return self._r._busy()


class RemoteReplica:
    """The router-facing half: ``Replica``'s surface over the wire.

    A **pump thread** (the ``_thread`` the router's liveness check sees)
    polls ``collect`` — harvesting results, acking deliveries, and
    refreshing the cached remote heartbeat.  ``last_beat`` is the last
    *successful transport contact*: a SIGKILLed or wedged peer stops
    refreshing it and policy 2 (heartbeat staleness → DEAD + migrate)
    fires exactly as it does for an in-process corpse."""

    def __init__(self, name: str, host: str, port: int, *,
                 num_slots: int = 2, proc: Optional[subprocess.Popen] = None,
                 call_timeout_s: float = 5.0,
                 submit_timeout_s: Optional[float] = None,
                 poll_interval_s: float = 0.02,
                 remote_stale_s: float = 5.0,
                 jitter_seed: int = 0, time_fn=time.monotonic):
        self.name = str(name)
        self.num_slots = int(num_slots)
        self.proc = proc
        self.call_timeout_s = float(call_timeout_s)
        self.submit_timeout_s = float(call_timeout_s if submit_timeout_s
                                      is None else submit_timeout_s)
        self.poll_interval_s = float(poll_interval_s)
        self.remote_stale_s = float(remote_stale_s)
        self._time = time_fn
        self._client = wire.WireClient(host, port, timeout_s=call_timeout_s,
                                       jitter_seed=jitter_seed)
        # probes ride their own connection: a healthz must not queue
        # behind a slow collect on the pump's client
        self._probe = wire.WireClient(host, port, timeout_s=call_timeout_s,
                                      jitter_seed=jitter_seed + 1)
        self._lock = locks.TracedLock("remote.replica")
        self._pending: Dict[str, RemoteHandle] = {}
        self._to_ack: set = set()
        self._remote: dict = {"state": JOINING, "beat_age_s": 0.0,
                              "busy": False, "backlog": dict(_EMPTY_BACKLOG),
                              "signals": dict(_EMPTY_SIGNALS),
                              "ticks": 0, "work_ticks": 0}
        self._state_hint: Optional[str] = None  # DRAINING/DEAD overlay
        self._protocol_errors = 0
        self._dead = False
        self._dead_reason = ""
        self.last_beat = self._time()
        self._next_rid = 0
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self.server = _RemoteServerFacade(self)

    # -- lifecycle surface (what FleetRouter consumes) --

    @property
    def state(self) -> str:
        with self._lock:
            if self._state_hint is not None:
                return self._state_hint
            return self._remote["state"]

    @property
    def ticks(self) -> int:
        with self._lock:
            return int(self._remote["ticks"])

    @property
    def work_ticks(self) -> int:
        with self._lock:
            return int(self._remote["work_ticks"])

    def start(self) -> "RemoteReplica":
        assert self._thread is None, f"remote {self.name} already started"
        self._thread = threading.Thread(
            target=self._pump, name=f"remote-pump-{self.name}", daemon=True)
        self._thread.start()
        return self

    def alive(self) -> bool:
        return (not self._dead and self._thread is not None
                and self._thread.is_alive())

    def beat_age(self) -> float:
        return self._time() - self.last_beat

    def healthz(self) -> dict:
        """Active probe, mapped to policy 3 (drain): transport probe
        failures, any observed protocol error (sticky — torn frames mean
        the wire itself can't be trusted), and a STALE REMOTE heartbeat
        (the peer's driver wedged while its RPC plane still answers) all
        read as unhealthy."""
        if self._dead:
            return {"ok": False, "replica": self.name,
                    "error": f"transport dead: {self._dead_reason}"}
        try:
            hz = self._probe.call("healthz", {},
                                  deadline_s=self.call_timeout_s)
        except wire.WireProtocolError as e:
            self._note_protocol_error(e)
            return {"ok": False, "replica": self.name,
                    "error": f"protocol error: {e}"}
        except wire.WireUnavailable as e:
            self._mark_dead(f"healthz connect refused: {e}")
            return {"ok": False, "replica": self.name, "error": repr(e)}
        except wire.WireError as e:
            return {"ok": False, "replica": self.name, "error": repr(e)}
        self.last_beat = self._time()
        with self._lock:
            protocol_errors = self._protocol_errors
        if protocol_errors:
            return {**hz, "ok": False, "replica": self.name,
                    "error": f"{protocol_errors} wire protocol error(s)"}
        if float(hz.get("beat_age_s", 0.0)) > self.remote_stale_s:
            return {**hz, "ok": False, "replica": self.name,
                    "error": f"remote heartbeat stale "
                             f"{hz.get('beat_age_s')}s"}
        return hz

    def begin_drain(self, *, reason: str = "drain") -> list:
        self._set_state(DRAINING, reason=reason)
        try:
            self._client.call("drain", {"reason": reason},
                              deadline_s=self.call_timeout_s)
        except wire.WireError as e:
            # unreachable peers still drain LOCALLY: the state flip stops
            # new submits and poll() escalates to halt at grace expiry
            telemetry.emit("remote", "drain_rpc_failed", replica=self.name,
                           error=repr(e))
        return []

    def finish_drain(self, *, join_timeout_s: float = 5.0) -> list:
        self._stop_pump(join_timeout_s)
        try:
            self._client.call(
                "stop", {"mode": "drain", "final": self.proc is not None},
                deadline_s=self.call_timeout_s + join_timeout_s)
            self._collect_once()  # final harvest of finished slots
        except wire.WireError as e:
            telemetry.emit("remote", "stop_rpc_failed", replica=self.name,
                           mode="drain", error=repr(e))
        left = self._fail_pending(ReplicaDown(
            f"replica {self.name}: stopped at drain completion"))
        self._set_state(DEAD, reason="drained")
        self._reap_proc(kill=False)
        return left

    def halt(self, error: Optional[BaseException] = None, *,
             join_timeout_s: float = 5.0) -> list:
        err = (error if error is not None
               else ReplicaDown(f"replica {self.name} halted"))
        self._stop_pump(join_timeout_s)
        if not self._dead:
            try:
                self._client.call(
                    "stop", {"mode": "halt", "final": self.proc is not None},
                    deadline_s=self.call_timeout_s)
                self._collect_once()
            except wire.WireError as e:
                telemetry.emit("remote", "stop_rpc_failed",
                               replica=self.name, mode="halt",
                               error=repr(e))
        unfinished = self._fail_pending(err)
        self._set_state(DEAD, reason="halt")
        self._reap_proc(kill=True)
        return unfinished

    def close(self) -> None:
        self._stop_pump(1.0)
        self._client.close()
        self._probe.close()
        self._reap_proc(kill=True)

    # -- internals --

    def _set_state(self, new: str, *, reason: str = "") -> None:
        with self._lock:
            old = self._state_hint or self._remote["state"]
            self._state_hint = new
        if old != new:
            telemetry.emit("remote", "state", replica=self.name, frm=old,
                           to=new, reason=reason)

    def _mark_dead(self, reason: str) -> None:
        first = not self._dead
        self._dead = True
        self._dead_reason = reason
        if first:
            telemetry.emit("remote", "transport_dead", replica=self.name,
                           reason=reason)

    def _note_protocol_error(self, e: BaseException) -> None:
        with self._lock:
            self._protocol_errors += 1
            n = self._protocol_errors
        telemetry.emit("remote", "protocol_error", replica=self.name,
                       count=n, error=repr(e))

    def _note_contact(self, hb: dict) -> None:
        self.last_beat = self._time()
        with self._lock:
            for k in ("state", "beat_age_s", "busy", "ticks", "work_ticks"):
                if k in hb:
                    self._remote[k] = hb[k]
            if "backlog" in hb:
                self._remote["backlog"] = hb["backlog"]
            if "signals" in hb:
                self._remote["signals"] = hb["signals"]

    def _cached_backlog(self) -> dict:
        with self._lock:
            b = self._remote["backlog"]
            return {"queued": dict(b["queued"]),
                    "queued_total": b["queued_total"],
                    "running": b["running"]}

    def _cached_signals(self) -> dict:
        with self._lock:
            s = dict(self._remote["signals"])
        s["queued"] = dict(s.get("queued") or {})
        return s

    def _busy(self) -> bool:
        with self._lock:
            return bool(self._remote["busy"]) or bool(self._pending)

    def _submit(self, text, *, slo: str, temperature: float, key):
        if self._dead:
            raise ReplicaDown(f"remote replica {self.name} transport dead")
        if self.state in (DRAINING, DEAD):
            raise ReplicaDown(f"remote replica {self.name} is {self.state}")
        text = np.asarray(text, np.int32)
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
        if key is not None:
            key = np.asarray(key, np.uint32)
            wid_src = b"|".join((text.tobytes(), key.tobytes(),
                                 slo.encode(), repr(float(temperature))
                                 .encode()))
        else:
            # no pinned key, no replay identity: a fresh nonce per call
            # (the router always pins keys; this path is direct use)
            key = np.asarray([os.getpid() & 0xFFFF, rid], np.uint32)
            wid_src = b"|".join((self.name.encode(), str(rid).encode(),
                                 str(os.getpid()).encode()))
        wid = hashlib.sha1(wid_src).hexdigest()[:20]
        handle = RemoteHandle(request_id=rid, wid=wid, slo=slo,
                              future=Future())
        # registered BEFORE the call: if the response is lost but the
        # peer executed, the pump's collect still finds a home for the
        # result — and a router re-dispatch to this same replica dedups
        # onto the same wid (exactly-once across ambiguous retries)
        with self._lock:
            self._pending[wid] = handle
        try:
            self._client.call(
                "submit", {"wid": wid, "text": text, "slo": slo,
                           "temperature": float(temperature), "key": key},
                deadline_s=self.submit_timeout_s)
        except wire.WireProtocolError as e:
            self._note_protocol_error(e)
            with self._lock:
                self._pending.pop(wid, None)
            raise ReplicaDown(
                f"remote {self.name}: protocol error on submit") from e
        except wire.WireUnavailable as e:
            self._mark_dead(f"submit connect refused: {e}")
            with self._lock:
                self._pending.pop(wid, None)
            raise ReplicaDown(
                f"remote {self.name}: unavailable on submit") from e
        except (wire.WireTimeout, wire.WireReset) as e:
            # AMBIGUOUS: the peer may or may not have executed.  Forget
            # the local handle (an orphan result is acked away by the
            # pump); the router's retry replays the same pinned key —
            # on this replica it dedups by wid, elsewhere it decodes
            # bit-identically
            with self._lock:
                self._pending.pop(wid, None)
            raise ReplicaDown(
                f"remote {self.name}: {type(e).__name__} on submit") from e
        except wire.WireRemoteError as e:
            with self._lock:
                self._pending.pop(wid, None)
            raise _map_remote_error(
                {"type": e.etype, "msg": e.msg}) from e
        self.last_beat = self._time()
        return handle

    def _pump(self) -> None:
        while not self._stop_evt.wait(self.poll_interval_s):
            if self._dead:
                return
            try:
                self._collect_once()
            except wire.WireProtocolError as e:
                self._note_protocol_error(e)
            except wire.WireUnavailable as e:
                self._mark_dead(f"collect connect refused: {e}")
                return
            except wire.WireError as e:
                # timeout/reset: transient — last_beat simply isn't
                # refreshed, and policy 2 notices if it persists
                telemetry.emit("remote", "collect_error",
                               replica=self.name, error=repr(e))

    def _collect_once(self) -> None:
        with self._lock:
            ack = sorted(self._to_ack)
        resp = self._client.call("collect", {"ack": ack},
                                 deadline_s=self.call_timeout_s)
        self._note_contact(resp)
        with self._lock:
            self._to_ack.difference_update(ack)
        for entry in resp.get("results") or ():
            wid = str(entry.get("wid"))
            with self._lock:
                handle = self._pending.pop(wid, None)
                # ack everything we saw — including orphans whose local
                # handle was abandoned after an ambiguous timeout
                self._to_ack.add(wid)
            if handle is None or handle.future.done():
                continue
            if "ok" in entry:
                handle.future.set_result(np.asarray(entry["ok"]))
            else:
                handle.future.set_exception(
                    _map_remote_error(entry.get("err") or {}))

    def _fail_pending(self, err: BaseException) -> List[RemoteHandle]:
        with self._lock:
            leftovers = list(self._pending.values())
            self._pending.clear()
        for h in leftovers:
            if not h.future.done():
                h.future.set_exception(err)
        return leftovers

    def _stop_pump(self, join_timeout_s: float) -> None:
        self._stop_evt.set()
        if self._thread is not None and self._thread.is_alive():
            if threading.current_thread() is not self._thread:
                self._thread.join(timeout=join_timeout_s)

    def _reap_proc(self, *, kill: bool) -> None:
        proc = self.proc
        if proc is None:
            return
        if proc.poll() is None and kill:
            proc.kill()
        try:
            proc.wait(timeout=10.0)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10.0)


# --- subprocess plumbing ----------------------------------------------------


def spawn_replica(name: str, *, out_dir, slots: int = 2,
                  host_index: int = 0, metrics_port: int = 0,
                  filter_thres: float = 1.0,
                  slo_targets: Optional[Dict[str, float]] = None,
                  prefix_cache: bool = False, seed: int = 0,
                  inherit_faults: bool = False,
                  ready_timeout_s: float = 240.0,
                  **remote_kwargs) -> RemoteReplica:
    """Launch ``python -m dalle_pytorch_tpu.serve.remote`` and return a
    connected :class:`RemoteReplica` owning the child process.

    The child gets a CLEAN fault env by default (``inherit_faults=False``
    strips ``GRAFT_FAULTS``): the rpc sites inject at the CLIENT edge in
    this process, and a chaos spec meant for the parent's transport must
    not also fire inside the children."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    ready = out_dir / f"{name}.ready.json"
    if ready.exists():
        ready.unlink()
    cmd = [sys.executable, "-m", "dalle_pytorch_tpu.serve.remote",
           "--name", name, "--port", "0", "--slots", str(slots),
           "--telemetry-dir", str(out_dir / name),
           "--metrics-port", str(metrics_port),
           "--ready-file", str(ready), "--host-index", str(host_index),
           "--filter-thres", str(filter_thres), "--seed", str(seed)]
    for slo, target in (slo_targets or {}).items():
        cmd += [f"--slo-{slo}", str(target)]
    if prefix_cache:
        cmd.append("--prefix-cache")
    env = dict(os.environ)
    # a CPU replica by design: a chip belongs to one process at a time, so
    # a child cannot share the one its parent holds
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = (str(REPO_ROOT) + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    if not inherit_faults:
        env.pop("GRAFT_FAULTS", None)
    proc = subprocess.Popen(cmd, env=env, cwd=str(REPO_ROOT))
    info = _wait_ready(ready, proc, name, ready_timeout_s)
    return RemoteReplica(name, "127.0.0.1", int(info["port"]),
                         num_slots=slots, proc=proc, **remote_kwargs)


def _wait_ready(ready: Path, proc: subprocess.Popen, name: str,
                timeout_s: float) -> dict:
    pace = threading.Event()
    deadline = time.monotonic() + timeout_s
    while True:
        if ready.exists():
            try:
                return json.loads(ready.read_text())
            except ValueError:
                pass  # ready file mid-write despite atomic rename: next tick
        rc = proc.poll()
        if rc is not None:
            raise SpawnFailed(
                f"remote replica {name} exited rc={rc} before ready",
                name=name, rc=rc)
        if time.monotonic() > deadline:
            # kill AND reap: a spawn that never reached the handshake
            # must not leave an orphan child behind (it would survive
            # this process and hold its telemetry dir / ports forever)
            proc.kill()
            try:
                proc.wait(timeout=10.0)
            except subprocess.TimeoutExpired:
                pass  # unreapable (wedged in the kernel): raise anyway
            raise SpawnFailed(
                f"remote replica {name} not ready after {timeout_s}s "
                f"(child killed and reaped)", name=name, rc=None)
        pace.wait(0.05)


def _build_toy_model(seed: int = 0, prompts: int = 4):
    """The CI-scale toy (the fleet_smoke geometry): big enough to tick,
    small enough to compile in seconds in every child process."""
    import jax
    import jax.numpy as jnp

    from .. import DALLE, DALLEConfig, VAEConfig

    vcfg = VAEConfig(image_size=16, num_tokens=32, codebook_dim=16,
                     num_layers=2, hidden_dim=8)
    cfg = DALLEConfig.from_vae(
        vcfg, dim=32, num_text_tokens=50, text_seq_len=6, depth=2, heads=2,
        dim_head=8, attn_types=("full", "axial_row"))
    dalle = DALLE(cfg)
    rng = jax.random.PRNGKey(seed)
    texts = [np.asarray(jax.random.randint(
        jax.random.PRNGKey(i), (cfg.text_seq_len,), 1, 50), np.int32)
        for i in range(prompts)]
    codes = jax.random.randint(rng, (1, cfg.image_seq_len), 0, 32)
    params = dalle.init(rng, jnp.asarray(texts[0])[None], codes,
                        return_loss=True)
    return cfg, dalle, params, texts


def main(argv=None) -> int:
    """Subprocess entry: one Replica + wire server + own obs lane."""
    import argparse

    os.environ.setdefault("JAX_PLATFORMS", "cpu")  # see spawn_replica
    parser = argparse.ArgumentParser(
        description="graftwire remote replica (subprocess half)")
    parser.add_argument("--name", required=True)
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--slots", type=int, default=2)
    parser.add_argument("--telemetry-dir", type=Path, required=True)
    parser.add_argument("--metrics-port", type=int, default=0)
    parser.add_argument("--ready-file", type=Path, required=True)
    parser.add_argument("--host-index", type=int, default=0)
    parser.add_argument("--filter-thres", type=float, default=1.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--slo-latency", type=float, default=None)
    parser.add_argument("--slo-throughput", type=float, default=None)
    parser.add_argument("--prefix-cache", action="store_true")
    args = parser.parse_args(argv)

    faults.install_from_env()
    reg = obs_metrics.init()
    _cfg, dalle, params, texts = _build_toy_model(seed=args.seed)
    slo_targets = {}
    if args.slo_latency is not None:
        slo_targets[LATENCY] = args.slo_latency
    if args.slo_throughput is not None:
        slo_targets[THROUGHPUT] = args.slo_throughput
    replica = Replica(
        args.name, dalle, params, args.slots,
        telemetry_dir=args.telemetry_dir, host_index=args.host_index,
        warmup_text=texts[0], filter_thres=args.filter_thres,
        seed=args.seed, slo_targets=slo_targets or None,
        prefix_cache=args.prefix_cache)
    metrics_server = obs_metrics.serve(args.metrics_port, reg,
                                       health_fn=replica.healthz)
    server = ReplicaServer(replica, port=args.port).start()
    replica.start()

    def _on_signal(signum, frame):
        server.shutdown_evt.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)

    tmp = args.ready_file.with_suffix(".tmp")
    tmp.write_text(json.dumps({"port": server.port,
                               "metrics_port": metrics_server.port,
                               "pid": os.getpid()}))
    os.replace(tmp, args.ready_file)

    server.wait_shutdown()
    if replica.state != DEAD:
        replica.halt(ReplicaDown(f"replica {args.name}: process shutdown"))
    server.close()
    metrics_server.close()
    replica.close()
    faults.reset()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
