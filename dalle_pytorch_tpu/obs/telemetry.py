"""graftscope telemetry core: one structured event stream per run.

Eight PRs of observability grew *fragmented*: step metrics in ``StepTimer``
EMAs, health in heartbeat JSON, checkpoint narration in stderr prints,
serve latency in ``GenerationServer.stats()``.  None of it survives the
process, and none of it can answer the operator's question after a death:
"what happened to this run, and where did the time go?"  This module is
the single answer surface — a crash-durable, schema-versioned JSONL event
stream every layer appends to, that ``tools/obs_report.py`` replays into a
run report or a Perfetto timeline.

Design constraints, in order:

* **Crash-durable** — every record is ONE ``os.write`` to an ``O_APPEND``
  fd (no userspace buffering): whatever the process managed to emit before
  a kill is on disk, and a torn final line (the only possible tear) is
  skipped by :func:`read_events`, never fatal.  No fsync — durability to
  the OS, not to the platter; the stream is diagnostics, not a commit
  record (those stay with ``CheckpointManager``).
* **Cheap when on, free when off** — an enabled ``event()`` is one dict,
  one ``json.dumps``, one syscall (bounded in tests/test_obs.py); the
  disabled path is a single attribute check with NO allocation, NO I/O
  (``span()`` returns a shared singleton).  The hard off-switch
  ``GRAFT_TELEMETRY=0`` wins over any CLI flag.
* **Correlatable** — every record carries ``run`` (run id), ``host``
  (process index), ``pid``, ``thread``, and a per-process ``seq`` that
  totally orders one host's records even when wall clocks wobble; spans
  pair a ``ph: B`` record with its ``ph: E`` by ``sid`` (the B record's
  seq), so a kill inside a span leaves a *visible* unfinished span rather
  than silence.
* **Bounded** — ``rotate_bytes`` rotates the active file to
  ``events.jsonl.N`` (``keep_rotated`` newest kept), so a week-long serve
  process cannot fill the disk.
* **jax-free** — this module imports only the stdlib, so every tool
  (monitor, obs_report, a supervisor) can read or tail a stream without
  claiming the chip the run is using, and while that run is hung —
  which is exactly when the stream is needed (the BACKEND001 lesson,
  applied to observability).

The module-level singleton (``init`` / ``get`` / ``emit`` / ``span`` /
``note``) is how library layers participate without plumbing a handle
through every constructor: trainers ``init()`` once, everything else
emits into whatever is active (or no-ops).  :func:`note` is the sanctioned
replacement for the hot paths' operator prints (graftlint OBS001): the
stderr line the operator sees AND the event the stream keeps are one call.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path
from typing import Iterable, List, Optional

from ..utils import locks

SCHEMA_VERSION = 1

#: spans are mirrored into ``jax.profiler`` captures under this prefix
PROFILER_PREFIX = "graft:"

# envelope keys every record carries; payload fields must not collide
# (event() lets the envelope win, so a colliding field is silently dropped
# — keep payload keys out of this set)
ENVELOPE_KEYS = ("v", "run", "host", "pid", "seq", "t", "mono", "thread",
                 "kind", "name")

# the contract tests/test_obs.py validates emitted records against; bump
# SCHEMA_VERSION on breaking changes (readers skip records with v > theirs)
EVENT_SCHEMA = {
    "$schema": "http://json-schema.org/draft-07/schema#",
    "type": "object",
    "required": list(ENVELOPE_KEYS),
    "properties": {
        "v": {"type": "integer", "minimum": 1},
        "run": {"type": "string"},
        "host": {"type": "integer", "minimum": 0},
        "pid": {"type": "integer", "minimum": 0},
        "seq": {"type": "integer", "minimum": 1},
        "t": {"type": "number"},
        "mono": {"type": "number"},
        "thread": {"type": "string"},
        "kind": {"type": "string"},
        "name": {"type": "string"},
        "ph": {"enum": ["B", "E"]},          # span begin/end markers
        "sid": {"type": "integer"},          # E only: the paired B's seq
        "dur_s": {"type": "number"},         # span E records: monotonic
                                             # duration; `compile` records:
                                             # seconds of the phase
        # kind `compile` (obs/compiles.py): name = trace / lower / compile /
        # cache_request / cache_hit / cache_miss, `fun` = the jitted
        # function where jax names one
        "fun": {"type": "string"},
    },
}


def _env_disabled() -> bool:
    """The hard off-switch: ``GRAFT_TELEMETRY`` set to an OFF value
    (``0/false/no/off``, any case — env_flag semantics, restated here so
    this module stays stdlib-only) disables telemetry regardless of CLI
    flags."""
    val = os.environ.get("GRAFT_TELEMETRY")
    if val is None:
        return False
    return val.strip().lower() in ("", "0", "false", "no", "off")


def _env_clock_skew() -> float:
    """``GRAFT_CLOCK_SKEW_S``: test-only wall-clock skew injection (added
    to every envelope ``t`` and beacon ``wall`` this process stamps) so
    chaos/CI runs can rehearse a fleet whose hosts disagree about the
    time — the exact condition ``align.py``'s solver must undo.  Never
    set in production; real skew comes free."""
    try:
        return float(os.environ.get("GRAFT_CLOCK_SKEW_S", ""))
    except ValueError:  # unset, empty, or junk: no injected skew
        return 0.0


# per-process boot nonce: names THIS process's monotonic clock, because a
# monotonic reading is only comparable to another from the same boot of
# the same process — heartbeats and clock beacons both carry it so the
# offset solver never pairs mono values across a restart
_BOOT = f"{os.getpid():x}-{time.time_ns() & 0xFFFFFFFFFF:010x}"


class _NullSpan:
    """Shared no-op context manager: the disabled ``span()`` path returns
    this singleton — no per-call allocation."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
#: the one no-op span every disabled ``span()`` returns; a caller that
#: skips a span on a condition of its own (a sampled loop) hands out the same
NULL_SPAN = _NULL_SPAN


class _Span:
    """Context manager emitting a ``ph: B`` record on entry and a paired
    ``ph: E`` (``sid`` = the B's seq, ``dur_s`` = monotonic delta) on exit.
    An exception rides out on the E record (``ok: false`` + ``error``); a
    process death inside the span leaves the B unpaired — the torn-span
    signature obs_report and the Perfetto exporter surface explicitly."""

    __slots__ = ("_tel", "_kind", "_name", "_fields", "_sid", "_t0", "_ann")

    def __init__(self, tel: "Telemetry", kind: str, name: str, fields: dict):
        self._tel = tel
        self._kind = kind
        self._name = name
        self._fields = fields

    def __enter__(self) -> "_Span":
        self._t0 = time.monotonic()
        self._sid = self._tel.event(self._kind, self._name, ph="B",
                                    **self._fields)
        # the same span on the profiler's clock: in an --xprof_dir capture
        # `graft:serve.prefill`, `graft:ckpt.save`, `graft:prof.xprof` sit
        # in the host plane beside the device ops.  Only where jax is
        # already loaded (this module never imports it); outside a capture
        # a TraceAnnotation is a no-op in the runtime.
        jax = sys.modules.get("jax")
        profiler = getattr(jax, "profiler", None)
        self._ann = None
        if profiler is not None:
            self._ann = profiler.TraceAnnotation(
                f"{PROFILER_PREFIX}{self._kind}.{self._name}")
            self._ann.__enter__()
        return self

    def __exit__(self, etype, evalue, tb) -> bool:
        if self._ann is not None:
            self._ann.__exit__(etype, evalue, tb)
        extra = {} if etype is None else {"error": repr(evalue)}
        self._tel.event(self._kind, self._name, ph="E", sid=self._sid,
                        dur_s=time.monotonic() - self._t0,
                        ok=etype is None, **extra)
        return False


class Telemetry:
    """One process's half of a run's event stream.

    Process 0 writes ``events.jsonl``; other hosts write
    ``events-p{host}.jsonl`` next to it (the heartbeat-file convention) —
    :func:`read_events` merges any number of them.  Thread-safe: the step
    loop, the async checkpoint writer, serve driver threads and prefetch
    workers all emit into the same instance (an ``RLock``, so a signal
    handler interrupting an in-flight ``event()`` on the main thread can
    still emit its own record instead of deadlocking).
    """

    def __init__(self, directory, run_id: Optional[str] = None, *,
                 host: int = 0, rotate_bytes: int = 64 << 20,
                 keep_rotated: int = 4, beacon_every: int = 256,
                 enabled: bool = True):
        self.host = int(host)
        self.pid = os.getpid()
        self.boot = _BOOT
        self.rotate_bytes = int(rotate_bytes)
        self.keep_rotated = int(keep_rotated)
        # clock beacons: every `beacon_every` records (and on the first
        # one) a `clock.beacon` rides the stream — the wall<->monotonic
        # offset pair + boot nonce align.py's solver runs on, re-emitted
        # periodically so rotation pruning never drops the last one.
        # 0 disables (tests that pin exact stream shapes).
        self.beacon_every = int(beacon_every)
        self._last_beacon = -self.beacon_every  # first event emits one
        self._clock_skew = _env_clock_skew()
        # shared-file rendezvous dir (GRAFT_CLOCK_RDV): when set, beacons
        # also carry `ref` = a shared filesystem's mtime clock, giving
        # hosts with no common workload a common reference (see
        # rendezvous())
        # graftlint: disable=ENV001 (GRAFT_CLOCK_RDV is a path: truthiness here is presence-of-value, not a boolean flag)
        self._rdv_dir = os.environ.get("GRAFT_CLOCK_RDV") or None
        # optional attach points (see attach_metrics / attach_alerts):
        # None keeps the emit path allocation-free, exactly like the
        # GRAFT_TELEMETRY=0 contract
        self._metrics = None
        self._alerts = None
        self._in_hook = False
        self._lock = locks.TracedRLock("telemetry")
        self._seq = 0
        self._fd: Optional[int] = None
        self._bytes = 0
        if not enabled or _env_disabled():
            self.dir = None
            self.path = None
            self.run_id = run_id or "disabled"
            return
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        if run_id is None:
            # content-free fallback identity: start time + pid is unique
            # enough to tell two restarts of one supervisor apart
            run_id = time.strftime("run-%Y%m%d-%H%M%S") + f"-{self.pid}"
        self.run_id = str(run_id)
        name = "events.jsonl" if self.host == 0 else f"events-p{self.host}.jsonl"
        self.path = self.dir / name
        self._fd = os.open(self.path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                           0o644)
        try:
            self._bytes = os.fstat(self._fd).st_size
        except OSError:
            self._bytes = 0

    @classmethod
    def disabled(cls) -> "Telemetry":
        """A telemetry that never writes: the allocation-free off path."""
        return cls(None, enabled=False)

    @property
    def enabled(self) -> bool:
        return self._fd is not None  # graftrace: unguarded (free-when-off contract: one atomic attribute read; _fd only transitions via close/rotate and a stale view is indistinguishable from a racing close)

    @property
    def seq(self) -> int:
        """Sequence number of the last emitted record (0 before any) —
        what heartbeats ride so monitors can line a stalled host up with
        its telemetry tail."""
        return self._seq  # graftrace: unguarded (monotonic watermark: an int read is atomic and heartbeats only need "some recent seq", never an exact one)

    # --- emission ---------------------------------------------------------

    def event(self, kind: str, name: str, **fields) -> Optional[int]:
        """Append one record; returns its ``seq`` (None when disabled).
        Payload ``fields`` must be JSON-serializable (anything else is
        stringified) and must not collide with :data:`ENVELOPE_KEYS`."""
        if self._fd is None:  # graftrace: unguarded (the documented free-when-off fast path: one attribute check, no lock; a record racing close() is dropped, which close() already implies)
            return None
        with self._lock:
            self._seq += 1
            seq = self._seq
            rec = dict(fields)
            rec.update(v=SCHEMA_VERSION, run=self.run_id, host=self.host,
                       pid=self.pid, seq=seq, t=time.time() + self._clock_skew,
                       mono=time.monotonic(),
                       thread=threading.current_thread().name,
                       kind=kind, name=name)
            line = (json.dumps(rec, separators=(",", ":"), default=str)
                    + "\n").encode()
            try:
                os.write(self._fd, line)  # graftrace: allow=T2 (deliberate: the lock IS the serializer for the O_APPEND stream — one writer at a time keeps records whole; writes are line-sized and local)
            except OSError:
                # a full/broken disk must never take the run down with it:
                # telemetry is diagnostics, losing it is the lesser failure
                return seq
            self._bytes += len(line)
            if self._bytes > self.rotate_bytes:
                self._rotate_locked()
            # attach hooks: the metrics feed and the alert engine both see
            # the record AFTER it landed, so anything they emit (an alert
            # record) gets a LATER seq — causally ordered after its cause.
            # The metrics feed never emits, so it runs unguarded (and
            # therefore counts nested alert records too); `_in_hook`
            # keeps the alert engine out of its own emissions.  Detached
            # (None) hooks cost one attribute check — the same
            # free-when-off contract as GRAFT_TELEMETRY=0.
            if self._metrics is not None:
                self._metrics.observe_event(rec)
            if self._alerts is not None and not self._in_hook:
                self._in_hook = True
                try:
                    self._fire_alerts_locked(self._alerts.observe(rec))
                finally:
                    self._in_hook = False
            if self.beacon_every > 0 \
                    and seq - self._last_beacon >= self.beacon_every:
                self._emit_beacon_locked()
        return seq

    def span(self, kind: str, name: str, **fields):
        """Context manager for a timed span (B/E record pair)."""
        if self._fd is None:  # graftrace: unguarded (free-when-off fast path, same contract as event())
            return _NULL_SPAN
        return _Span(self, kind, name, fields)

    # --- fleet clock model (align.py's write side) ------------------------

    def clock_beacon(self) -> dict:
        """This instant's wall<->monotonic offset pair + boot nonce — the
        payload `clock.beacon` records and heartbeats carry so the offset
        solver can place this host on the fleet timebase even when the
        host dies between telemetry rotations."""
        return {"wall": time.time() + self._clock_skew,
                "mono": time.monotonic(), "boot": self.boot}

    def _emit_beacon_locked(self) -> None:
        """Emit one `clock.beacon` record (called with the lock held; the
        cadence counter is advanced FIRST so the beacon's own event() call
        cannot recurse)."""
        self._last_beacon = self._seq + 1
        payload = self.clock_beacon()
        if self._rdv_dir is not None:
            ref = self._rendezvous_ref()
            if ref is not None:
                payload["ref"] = ref
        self.event("clock", "beacon", **payload)

    def _rendezvous_ref(self) -> Optional[float]:
        """Shared-file rendezvous: (re)write this host's marker file in
        the shared dir and read back its mtime — the filesystem server's
        clock, one reference every host observes — so hosts with no
        common workload (disjoint serve replicas) still align.  None on
        any filesystem error: rendezvous is opportunistic."""
        try:
            d = Path(self._rdv_dir)
            d.mkdir(parents=True, exist_ok=True)
            f = d / f"rdv-h{self.host}-{self.boot}"
            f.write_text(json.dumps(
                {"run": self.run_id, "host": self.host, "boot": self.boot}))
            return float(f.stat().st_mtime)
        except OSError:
            return None

    def rendezvous(self, shared_dir) -> Optional[float]:
        """Explicitly rendezvous against ``shared_dir`` (a directory on a
        filesystem all hosts mount) and emit a ref-bearing beacon.  The
        env ``GRAFT_CLOCK_RDV`` arms the same thing on the periodic
        beacon cadence."""
        if self._fd is None:  # graftrace: unguarded (free-when-off fast path, same contract as event())
            return None
        with self._lock:
            prev = self._rdv_dir
            self._rdv_dir = str(shared_dir)
            try:
                self._emit_beacon_locked()
            finally:
                self._rdv_dir = prev if prev is not None else str(shared_dir)
        return None

    # --- attach points ----------------------------------------------------

    def attach_metrics(self, registry) -> None:
        """Feed every emitted record to ``registry.observe_event`` (see
        obs/metrics.py) — the emit path IS the metrics pipeline, so the
        /metrics endpoint needs no second instrumentation pass."""
        self._metrics = registry

    def attach_alerts(self, engine) -> None:
        """Run ``engine.observe`` (see obs/alerts.py) over every emitted
        record; fired alerts are emitted back into this stream as
        ``alert`` events (seq AFTER the cause record) and printed the way
        note() prints."""
        self._alerts = engine

    def _fire_alerts_locked(self, fired) -> None:
        for alert in fired or ():
            msg = alert.get("msg") or alert.get("rule", "alert")
            _print_note("[alert]", msg, "stderr")
            self.event("alert", str(alert.get("rule", "?")), **alert)

    # --- rotation / lifecycle --------------------------------------------

    def _rotate_locked(self) -> None:
        """Rename the active file to ``<name>.N`` (N = newest) and start a
        fresh one; prune rotated files beyond ``keep_rotated``.  Called
        with the lock held."""
        existing = sorted(
            (int(p.name.rsplit(".", 1)[1]), p)
            for p in self.dir.glob(self.path.name + ".*")
            if p.name.rsplit(".", 1)[1].isdigit())
        nxt = (existing[-1][0] + 1) if existing else 1
        os.close(self._fd)
        self._fd = None
        rotated_to = self.path.with_name(f"{self.path.name}.{nxt}")
        os.replace(self.path, rotated_to)
        rotated = existing + [(nxt, rotated_to)]
        for _, p in rotated[:max(len(rotated) - self.keep_rotated, 0)]:
            try:
                p.unlink()
            except OSError:
                pass
        self._fd = os.open(self.path,  # graftrace: allow=T2 (rotation happens at most once per rotate_bytes of output; reopening under the lock is what keeps racing writers off the renamed file)
                           os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        self._bytes = 0

    def close(self) -> None:
        with self._lock:
            if self._fd is not None:
                os.close(self._fd)
                self._fd = None


# --- module-level singleton: how library layers participate ---------------

_active: Optional[Telemetry] = None
_active_lock = locks.TracedLock("telemetry.active")


def init(directory, run_id: Optional[str] = None, **kwargs) -> Telemetry:
    """Install the process-wide telemetry (closing any previous one).
    Honors the ``GRAFT_TELEMETRY=0`` hard off-switch: the returned
    instance is then disabled and nothing is installed, so every
    downstream ``emit``/``span``/``note`` stays on the free path."""
    global _active
    tel = Telemetry(directory, run_id=run_id, **kwargs)
    with _active_lock:
        if _active is not None:
            _active.close()
        _active = tel if tel.enabled else None
    return tel


def get() -> Optional[Telemetry]:
    """The active telemetry, or None — hot loops hold the result and guard
    with ``if tel is not None`` so the disabled path allocates nothing."""
    return _active


def shutdown() -> None:
    """Close and uninstall the active telemetry (trainer exit paths; also
    what makes in-process reruns — rollback relaunches, tests — start a
    fresh stream instead of appending to a closed fd)."""
    global _active
    with _active_lock:
        if _active is not None:
            _active.close()
        _active = None


def emit(kind: str, name: str, **fields) -> Optional[int]:
    """Emit into the active telemetry, if any."""
    tel = _active
    if tel is None:
        return None
    return tel.event(kind, name, **fields)


def span(kind: str, name: str, **fields):
    """Span on the active telemetry; the shared no-op when none."""
    tel = _active
    if tel is None:
        return _NULL_SPAN
    return tel.span(kind, name, **fields)


def _print_note(prefix: str, msg: str, stream: str) -> None:
    """The operator-line half of note() — also what fired alerts print."""
    out = sys.stdout if stream == "stdout" else sys.stderr
    print(f"{prefix} {msg}", file=out, flush=True)


def note(kind: str, name: str, msg: str, *, prefix: Optional[str] = None,
         stream: str = "stderr", **fields) -> None:
    """Operator message + telemetry event in one call — the OBS001
    replacement for bare prints in step/serve/ckpt hot paths.

    Prints ``{prefix} {msg}`` (prefix defaults to ``[{kind}]``) to stderr
    (or stdout for the legacy warning surfaces that monitors scrape), and
    emits a ``kind``/``name`` event carrying ``msg`` + ``fields`` when a
    telemetry is active.  The print half is unconditional: the stream is
    *additional* observability, never a replacement for the line a human
    tails."""
    _print_note(prefix if prefix is not None else f"[{kind}]", msg, stream)
    tel = _active
    if tel is not None:
        tel.event(kind, name, msg=msg, **fields)


def clock_beacon_payload() -> dict:
    """The heartbeat-side clock payload: the active telemetry's beacon if
    one is installed, else a fresh (wall, mono, boot) triple with the same
    skew-injection semantics — so heartbeats carry alignment material even
    on a run with telemetry off."""
    tel = _active
    if tel is not None:
        return tel.clock_beacon()
    return {"wall": time.time() + _env_clock_skew(),
            "mono": time.monotonic(), "boot": _BOOT}


# --- read side ------------------------------------------------------------


def _iter_stream_files(path: Path) -> List[Path]:
    """Event files under ``path``: the file itself, or a directory's
    ``events*.jsonl*`` members (rotated parts included), rotation-ordered
    so records come out in emission order per host."""
    if path.is_file():
        # an active-segment path brings its rotated siblings
        # (<name>.1 .. <name>.N, oldest first) so merge/report see the
        # full history, not just the live segment — a week-long run's
        # events.jsonl is only the tail of its own story
        rotated = sorted(
            (int(p.name.rsplit(".", 1)[1]), p)
            for p in path.parent.glob(path.name + ".*")
            if p.name.rsplit(".", 1)[1].isdigit())
        return [p for _, p in rotated] + [path]

    def order(p: Path):
        tail = p.name.rsplit(".", 1)[1]
        # rotated parts (events.jsonl.N) precede the active file
        return (p.name.split(".jsonl")[0],
                int(tail) if tail.isdigit() else 1 << 30)

    return sorted(path.glob("events*.jsonl*"), key=order)


def read_events(paths: Iterable) -> List[dict]:
    """Parse one or more event files / stream directories into records.

    Torn trailing lines (the crash signature of the O_APPEND discipline)
    and records newer than this reader's schema are skipped, never fatal —
    the reader exists precisely for post-crash streams.  Records are
    returned sorted by (run, host, seq): total per-host causal order, with
    wall time (``t``) left to consumers that align across hosts."""
    records: List[dict] = []
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    for p in paths:
        for f in _iter_stream_files(Path(p)):
            try:
                data = f.read_text(errors="replace")
            except OSError:
                continue
            for line in data.splitlines():
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn write: skip, keep reading
                if not isinstance(rec, dict) or \
                        rec.get("v", 0) > SCHEMA_VERSION:
                    continue
                records.append(rec)
    records.sort(key=lambda r: (str(r.get("run", "")), r.get("host", 0),
                                r.get("seq", 0)))
    return records
