"""Failure detection & preemption-safe training.

The reference has no failure handling (SURVEY.md §5.3): recovery is "rerun
``train_dalle.py --dalle_path ./dalle.pt``" and a preempted run silently
loses everything since the last 100-iter checkpoint, while a hung
collective or dead host is invisible until the scheduler kills the job.
TPU pods make both failure modes routine (preemptible capacity, multi-host
collectives), so this framework makes them first-class:

* ``GracefulShutdown`` converts SIGTERM/SIGINT — the preemption notice every
  scheduler sends before the hard kill — into a cooperative stop flag the
  training loop polls at step boundaries, so the loop can write a final
  resume checkpoint and exit cleanly.  In multi-host runs the flag is made
  *collective* (any-process OR via the backend's ``average_all``) so every
  process leaves the loop at the same step — required because the
  checkpoint save paths (``host_fetch`` gathers, Orbax sharded writes) are
  collective operations that deadlock if only one process calls them.
* ``Heartbeat`` writes a small per-process progress file (atomic
  rename) at most once per ``beat_interval`` seconds and optionally runs an
  in-process
  watchdog thread that warns on stderr when no step has completed for
  ``stall_timeout`` seconds — catching hung device steps / collectives from
  *inside* the process, while the files let an external monitor detect a
  dead or wedged host by mtime age (``Heartbeat.is_stalled``).
"""
from __future__ import annotations

import enum
import json
import os
import signal
import sys
import tempfile
import threading
import time
from pathlib import Path
from typing import Optional

import jax
import numpy as np

from ..obs import telemetry


class ExitCode(enum.IntEnum):
    """The process exit-code table — THE one place these numbers live.

    Supervisors key restart decisions off these values (``tools/monitor.py``,
    any external scheduler), so they are a frozen contract: never renumber, only add
    (``tests/test_failure.py`` pins them).

    Trainer processes (train_dalle.py / train_vae.py):

    * ``CLEAN`` (0) — the run completed.  ``PREEMPTED`` is deliberately an
      alias: a graceful SIGTERM stop writes its resume checkpoint and exits
      *cleanly*; supervisors distinguish "finished" from "preempted" by the
      heartbeat done-marker (``Heartbeat.close(done=True)``), never by exit
      code, so an impatient scheduler reading 0 does not re-kill the pod.
    * ``ROLLBACK_BUDGET`` (70, EX_SOFTWARE) — the anomaly-recovery ladder
      exhausted its ``--max_rollbacks``: the run will NOT converge by
      relaunching; a human must read the anomaly bundles.  Terminal —
      supervisors must not restart it.
    * ``WEDGED`` (75, EX_TEMPFAIL) — the hung-step watchdog fired: a device
      call or collective never returned.  Transient by definition —
      supervisors relaunch with ``--resume auto``.
    * ``PREEMPT_EXPIRED`` (74, EX_IOERR) — a preemption notice's grace
      window ran out before the final checkpoint committed (the
      ``preempt:at_step`` faultpoint's bounded-grace drill, and the shape
      of a real scheduler's hard kill): whatever the commit protocol made
      durable is what resume gets.  Transient — supervisors relaunch with
      ``--resume auto`` (possibly under a different ``--plan``: the
      manifest-recorded plan + topology make the checkpoint restorable on
      whatever hardware the scheduler grants next).

    External monitor (``tools/monitor.py``):

    * ``MONITOR_STALLED`` (1) — some host's heartbeat is stale/missing.
    * ``MONITOR_NO_HEARTBEATS`` (2) — no heartbeat files at all.
    * ``RESTART_BUDGET`` (3) — ``--restart-cmd`` budget exhausted (or
      nothing manifest-valid to restart from).  Terminal, like 70.
    """

    CLEAN = 0
    PREEMPTED = 0  # alias of CLEAN — see the docstring for why
    MONITOR_STALLED = 1
    MONITOR_NO_HEARTBEATS = 2
    RESTART_BUDGET = 3
    ROLLBACK_BUDGET = 70
    PREEMPT_EXPIRED = 74
    WEDGED = 75


class GracefulShutdown:
    """Context manager turning termination signals into a pollable stop flag.

    A second delivery of the same signal restores the previous handler and
    re-raises, so an impatient ``kill`` (or ctrl-C twice) still terminates
    immediately instead of waiting for the checkpoint.
    """

    def __init__(self, signals=(signal.SIGTERM, signal.SIGINT)):
        self._signals = tuple(signals)
        self._previous = {}
        self._requested = False

    # --- signal plumbing ---

    def _handler(self, signum, frame):
        if self._requested:  # second signal: escalate to the old behavior
            self._restore()
            signal.raise_signal(signum)
            return
        self._requested = True
        # note() is signal-safe here: Telemetry's lock is an RLock, so a
        # handler interrupting the main thread mid-event still emits
        telemetry.note(
            "run", "preempt_signal",
            f"received signal {signum}: will checkpoint and stop at the "
            "next step boundary (send again to force-quit)",
            prefix="[failure]", signum=int(signum))

    def _restore(self):
        for sig, prev in self._previous.items():
            signal.signal(sig, prev)
        self._previous = {}

    def __enter__(self) -> "GracefulShutdown":
        for sig in self._signals:
            self._previous[sig] = signal.signal(sig, self._handler)
        return self

    def __exit__(self, *exc):
        self._restore()
        return False

    # --- polling API ---

    @property
    def requested(self) -> bool:
        """This process's local flag (no collective)."""
        return self._requested

    def should_stop(self, backend=None, step: Optional[int] = None,
                    check_every: int = 1) -> bool:
        """Collective stop decision, safe to act on with collective saves.

        Single-process: just the local flag.  Multi-process: every
        ``check_every`` steps all processes agree on OR(local flags) via the
        backend's ``average_all`` (flags are 0/1, so mean > 0 iff any set).
        Note the multi-process collective *blocks the host*; a loop that
        already averages a per-step metric should use
        :meth:`average_and_poll` instead, which rides the stop flag on that
        existing collective for free.  A ``check_every`` larger than 1 must
        be called symmetrically by every process — pass the global step so
        the modulo lines up.
        """
        if jax.process_count() <= 1 or backend is None:
            return self._requested
        if step is not None and check_every > 1 and step % check_every != 0:
            return False
        flag = np.float32(1.0 if self._requested else 0.0)
        return float(backend.average_all(flag)) > 0.0

    def average_and_poll(self, backend, value) -> tuple:
        """Average a per-step host metric *and* decide the collective stop
        in one collective: returns ``(mean_value, stop)``.

        The train loops already block once per step to average the loss
        across processes; gathering ``[loss, stop_flag]`` as a single
        2-vector makes the preemption check free instead of doubling the
        per-step host collectives.  Every process must call this
        symmetrically (same as the loss averaging it replaces).
        """
        if backend is None or jax.process_count() <= 1:
            return float(value), self._requested
        pair = np.asarray([np.float32(value),
                           np.float32(1.0 if self._requested else 0.0)])
        avg = backend.average_all(pair)
        return float(avg[0]), float(avg[1]) > 0.0


class Heartbeat:
    """Per-process progress file + optional in-process stall watchdog.

    ``run_id`` (explicit, else inherited from the active telemetry) and the
    telemetry stream's last-event sequence number ride every heartbeat
    write, so an external monitor can correlate a stalled host with its
    telemetry tail — not just *that* it stalled, but what it was doing
    (``tools/monitor.py --telemetry-dir``)."""

    def __init__(self, directory, beat_interval: float = 15.0,
                 stall_timeout: Optional[float] = None,
                 run_id: Optional[str] = None):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.path = self.dir / f"heartbeat-p{jax.process_index()}.json"
        self.run_id = run_id
        self.beat_interval = float(beat_interval)
        self._sweep_stale_temps()
        # None until the first beat: the stretch from construction to step 1
        # includes the XLA compile (minutes at real sizes), which must not
        # read as a stall
        self._last_beat = None
        self._last_write = None  # monotonic time of the last file write
        self._last_step = 0
        self._stop = threading.Event()
        self._thread = None
        self._stalled_since = None
        if stall_timeout:
            self._timeout = float(stall_timeout)
            self._thread = threading.Thread(
                target=self._watch, name="heartbeat-watchdog", daemon=True)
            self._thread.start()

    def beat(self, step: int, **extra) -> None:
        """Record a completed step.  The file write is rate-limited by
        *time* (``beat_interval`` seconds), not by step count — external
        monitors judge staleness by wall-clock age, so a slow-but-healthy
        run (minutes per step) must still look alive.  The first beat
        always writes so monitors see the file immediately."""
        now = time.monotonic()
        self._last_beat = now
        self._last_step = int(step)
        self._stalled_since = None
        if (self._last_write is not None
                and now - self._last_write < self.beat_interval):
            return
        self._last_write = now
        self._write({"step": int(step), "time": time.time(),
                     "process": jax.process_index(),
                     **self._correlation(), **self._memory(), **extra})

    @staticmethod
    def _memory() -> dict:
        """Compact memory snapshot riding every heartbeat (host RSS +
        summed device used/peak when the backend exposes counters) — the
        monitor reads a dying host's memory trajectory from the
        heartbeat trail alone, no telemetry stream required.  Guarded:
        a heartbeat must never die because a memory probe did."""
        try:
            from dalle_pytorch_tpu.obs import mem
            return mem.heartbeat_snapshot()
        except Exception:  # graftlint: disable=EXC001 (liveness signal outranks the memory garnish; heartbeat_snapshot itself guards the backend probe, this catches import-time breakage)
            return {}

    def _sweep_stale_temps(self) -> None:
        """A process killed inside ``_write`` (between mkstemp and the
        rename) leaks one ``.hb-*`` temp file; over many preemption cycles
        a long-lived heartbeat dir fills with them.  On startup, remove
        temps older than a few beat intervals — anything that old cannot
        belong to a write still in flight."""
        # graftlint: disable=OBS002 (cross-clock by design: the cutoff compares against file mtimes, which live on the wall clock)
        cutoff = time.time() - 3 * self.beat_interval
        for tmp in self.dir.glob(".hb-*"):
            try:
                if tmp.stat().st_mtime < cutoff:
                    tmp.unlink()
            except OSError:  # racing another process's write or sweep
                pass

    def _write(self, payload: dict) -> None:
        fd, tmp = tempfile.mkstemp(dir=self.dir, prefix=".hb-")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f)
            os.replace(tmp, self.path)  # atomic on POSIX
        except BaseException:
            try:
                os.unlink(tmp)
            finally:
                raise

    def _watch(self) -> None:
        while not self._stop.wait(min(self._timeout / 4, 1.0)):
            if self._last_beat is None:  # still compiling step 1
                continue
            age = time.monotonic() - self._last_beat
            if age > self._timeout and self._stalled_since is None:
                self._stalled_since = time.monotonic()
                telemetry.note(
                    "run", "stall_warning",
                    f"possible stall: no training step for {age:.0f}s "
                    f"(timeout {self._timeout:.0f}s) — a hung collective "
                    "or device step?", prefix="[failure]",
                    age_s=age, step=self._last_step)

    def close(self, done: bool = False) -> None:
        """Stop the watchdog.  ``done=True`` stamps the heartbeat file with a
        done marker so external monitors can tell a *finished* run from a
        dead one (otherwise the aging heartbeat of a completed run reads as
        STALLED and an auto-restart wrapper would relaunch it forever).
        Interrupted/preempted runs close with ``done=False`` on purpose —
        there a restart is exactly what a supervisor should do."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if done:
            self._write({"step": self._last_step, "time": time.time(),
                         "process": jax.process_index(),
                         **self._correlation(), "done": True})

    def _correlation(self) -> dict:
        """run_id + telemetry last-seq + clock-beacon fields for every
        heartbeat write.  The clock payload (wall<->mono offset pair +
        boot nonce, obs/align.py's anchor material) rides here so a
        monitor can place this host on the fleet timebase even when the
        host died between telemetry rotations — and because the heartbeat
        file lands on the monitor's filesystem, its mtime doubles as a
        shared-clock rendezvous reference."""
        tel = telemetry.get()
        out = {"clock": telemetry.clock_beacon_payload()}
        run_id = self.run_id or (tel.run_id if tel is not None else None)
        if run_id is not None:
            out["run_id"] = run_id
        if tel is not None:
            out["telemetry_seq"] = tel.seq
        return out

    # --- external-monitor side ---

    @staticmethod
    def read(path) -> dict:
        return json.loads(Path(path).read_text())

    @staticmethod
    def is_stalled(path, timeout: float, now: Optional[float] = None) -> bool:
        """True if the heartbeat file is older than ``timeout`` seconds (or
        missing) — for an external supervisor scanning ``heartbeat-p*.json``
        to find dead/wedged hosts."""
        path = Path(path)
        if not path.exists():
            return True
        now = time.time() if now is None else now
        try:
            last = Heartbeat.read(path)["time"]
        except (json.JSONDecodeError, KeyError):  # mid-write torn read
            last = path.stat().st_mtime
        return (now - last) > timeout
