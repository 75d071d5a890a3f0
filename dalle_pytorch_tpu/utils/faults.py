"""Fault injection: a faultpoint registry driven by the ``GRAFT_FAULTS`` env.

The recovery paths this repo grew for preemptible pods (graceful shutdown,
manifest-validated checkpoints, quarantined samples) are exactly the code
nobody runs until a 3am preemption does — the untested-recovery failure
mode production checkpoint managers are built to close.  This module makes
the failures injectable so tests and the CI ``crash-resume`` job can rehearse
them deterministically on CPU:

    GRAFT_FAULTS="ckpt_write:fail_after=2,ckpt_write:truncate=3,\
sigterm:at_step=7,sample_read:every=50"

Grammar: comma-separated ``site:action=value`` entries.  Sites are named
call-points threaded through the real code (``ckpt_write`` in
``CheckpointManager.save``, ``sample_read`` in the dataset image/caption
reads, ``sigterm`` in the trainers' step loops).  Actions:

* ``fail_after=N`` — the (N+1)-th hit of the site raises
  :class:`InjectedFault` (an ``OSError``), once.  Exercises retry paths:
  the first N calls succeed, one fails, the retry lands.
* ``every=K`` — every K-th hit raises :class:`InjectedFault`.  Exercises
  degradation paths (sample quarantine) and retry exhaustion (``every=1``).
* ``truncate=N`` — the N-th hit returns the ``"truncate"`` action to the
  caller, once; the caller tears its own write (``CheckpointManager``
  halves the payload file *after* the manifest CRCs were computed —
  modeling a crash or bit-rot between the data landing and the next read).
* ``at_step=N`` — fires once when the caller passes ``step == N``;
  :func:`maybe_kill` turns it into a real ``SIGTERM`` to this process
  (the preemption notice, mid-training).
* ``at_tick=N`` — ``at_step`` for callers whose progress coordinate is a
  *tick counter*, not a training step (the serve fleet's replica driver
  loops).  Same one-shot semantics, distinct spelling so a chaos spec
  reads unambiguously: ``replica_down:at_tick=40`` kills a replica at
  its 40th driver tick, whatever training step anything else is on.
* ``grace_ms=N`` — configuration, not a trigger: the grace window (in
  milliseconds) the ``preempt`` site pairs with its ``at_step``.
* ``drop=N`` / ``conn_reset=N`` — network actions for the RPC transport
  sites (below): the N-th hit returns the action name to the caller,
  once — ``serve/wire.py`` turns ``drop`` into a vanished frame (the
  peer never sees it; the caller's deadline is what notices) and
  ``conn_reset`` into a torn TCP connection.  Same one-shot return
  semantics as ``truncate``.
* ``delay_ms=N`` — configuration like ``grace_ms``: the transport sleeps
  N milliseconds on every hit of the site (tail-latency injection, the
  slow-network shape that must surface as deadline misses, not hangs).

Preemption site (both trainers' step loops): ``preempt:at_step=N`` is the
full preemption drill — :func:`maybe_preempt` delivers a real SIGTERM
*and* arms a bounded grace window (``preempt:grace_ms=M``, default 30 s —
the shape of every real scheduler's notice-then-kill contract).  The
trainer's GracefulShutdown path gets exactly the window to write its
final checkpoint and exit cleanly; if the window expires first, the
process hard-exits ``ExitCode.PREEMPT_EXPIRED`` (74) mid-save, leaving
whatever the manifest commit protocol made durable — the supervisor
relaunches with ``--resume auto`` (possibly under a different
``--plan``).  Trainers cancel the window via
:func:`cancel_preempt_grace` once their final save has committed.

Training-health sites (utils/guardrails.py): ``grad_nan:at_step=N`` and
``loss_spike:at_step=N`` drive :func:`guardrails.fault_scale_for`, the
traced loss-scale port of the health-enabled train steps (NaN poisons the
real on-device gradients; a large finite factor lands a genuine spike);
``step_hang:at_step=N`` (:func:`maybe_hang`) wedges the step loop inside
the hung-step watchdog's armed window so its kill-and-relaunch path is
rehearsed end to end.

Streaming-ingestion site (data/stream.py): ``shard_read`` is hit once per
shard sample-read attempt.  ``fail_after``/``every`` model transient shard
I/O (retried once, then the SHARD is quarantined — logged, capped);
``truncate=N`` hands the reader a half-read image member (torn shard
bytes), which must end in the same retry/quarantine path.

Async-checkpoint site (utils/ckpt_manager.py): ``ckpt_async`` fires
between the checkpoint's data write and its manifest publish, with
``step`` = the checkpoint step.  ``at_step=N`` raises
:class:`InjectedKill` there — the background writer dies with the data on
disk and the commit record absent, the exact crash window invariant I1
exists for (`latest_valid()` must fall back to the previous checkpoint).

Serving site (serve/scheduler.py): ``serve_request`` is hit once per
occupied slot per decode tick (slot order; ``step`` carries the request's
decoded-token count, so ``at_step`` can target a progress milestone).  An
injected failure mid-decode fails THAT request — its future carries the
fault, its slot frees the same scheduler iteration — while co-batched
requests keep decoding (tests/test_serve.py pins the isolation).

Fleet-serving sites (serve/replica.py + serve/router.py):
``replica_down`` is hit once per replica driver-loop pass (``step`` =
that replica's completed DECODE-tick count, so ``at_tick=N`` lands
mid-stream after the Nth decode tick — an idle loop spins far faster
than it decodes); ``at_tick=N`` makes the driver thread
*vanish* mid-decode — no cleanup, no future resolution — so the router's
failure detectors (heartbeat staleness, ``/healthz``) are what find the
corpse, exactly like a killed pod; ``every=K`` models a crashy driver
loop instead.  ``router_submit`` is hit once per dispatch attempt inside
``FleetRouter``; ``every=K`` makes dispatches fail transiently, driving
the bounded-retry/backoff path (``every=1`` = retry exhaustion).
``replica_health`` is hit once per ``Replica.healthz()`` probe; ``every``
makes the probe fail while the driver keeps beating — the
probe-signal-without-heartbeat-signal case the router must treat as a
graceful quarantine, not an instant death.

Network sites (serve/wire.py): ``rpc_send`` fires once per frame a
``WireClient`` writes, ``rpc_recv`` once per response frame it reads —
CLIENT-side only, so one in-process fault registry shared by a test's
client and server injects deterministically at the caller's edge of the
wire.  ``drop``/``conn_reset``/``truncate`` are one-shot Nth-hit
actions; ``delay_ms`` is per-hit configuration.  A dropped *send*
models a lost request (the peer never executed); a dropped *recv*
models a lost response (the peer DID execute — the ambiguous timeout
the idempotent-retry contract exists for).

Counters are per-site and thread-safe (dataset reads run under the
prefetching DataLoader's thread pool).  The registry is parsed lazily from
the environment; trainers call :func:`install_from_env` at startup so
in-process reruns (tests call ``main()`` repeatedly) see the *current*
environment, not a cached one.
"""
from __future__ import annotations

import dataclasses
import os
import signal
import threading
from typing import Dict, FrozenSet, List, Optional

from ..obs import telemetry
from . import locks

_ACTIONS = ("fail_after", "every", "truncate", "at_step", "at_tick",
            "grace_ms", "drop", "delay_ms", "conn_reset")


class InjectedFault(OSError):
    """A deliberately injected transient I/O failure (``GRAFT_FAULTS``)."""


class InjectedKill(RuntimeError):
    """A deliberately injected *process death* at a faultpoint — unlike
    :class:`InjectedFault` it is NOT an ``OSError``, so retry loops that
    model transient I/O (``CheckpointManager.save``) let it escape: the
    code after the faultpoint never runs, exactly as if the scheduler had
    killed the process there.  The ``ckpt_async`` site uses it to abandon
    an async checkpoint between its data write and its manifest publish
    (the I1 crash window: data on disk, commit record absent)."""


@dataclasses.dataclass
class _Trigger:
    action: str
    value: int
    fired: bool = False


class FaultRegistry:
    """Parsed ``GRAFT_FAULTS`` spec + per-site hit counters."""

    def __init__(self, spec: str = ""):
        self._lock = locks.TracedLock("faults.registry")
        self._triggers: Dict[str, List[_Trigger]] = {}
        self._hits: Dict[str, int] = {}
        for entry in (e.strip() for e in (spec or "").split(",")):
            if not entry:
                continue
            site, sep, act = entry.partition(":")
            action, sep2, value = act.partition("=")
            if not sep or not sep2 or not site or action not in _ACTIONS:
                raise ValueError(
                    f"bad GRAFT_FAULTS entry {entry!r}: expected "
                    f"'site:action=value' with action in {_ACTIONS}")
            try:
                ivalue = int(value)
            except ValueError as e:
                raise ValueError(
                    f"bad GRAFT_FAULTS value in {entry!r}: {value!r} is not "
                    "an integer") from e
            if ivalue < 0:
                raise ValueError(f"bad GRAFT_FAULTS value in {entry!r}: "
                                 "must be >= 0")
            self._triggers.setdefault(site, []).append(
                _Trigger(action, ivalue))

    @property
    def empty(self) -> bool:
        return not self._triggers

    def hits(self, site: str) -> int:
        with self._lock:
            return self._hits.get(site, 0)

    def config(self, site: str, action: str) -> Optional[int]:
        """Value of a configuration action (``grace_ms``/``delay_ms``)
        on ``site``, or None when the spec doesn't carry one."""
        with self._lock:
            for t in self._triggers.get(site, ()):
                if t.action == action:
                    return t.value
        return None

    def fire(self, site: str, step: Optional[int] = None) -> FrozenSet[str]:
        """Register one hit of ``site``; raise or return triggered actions.

        ``fail_after``/``every`` raise :class:`InjectedFault`;
        ``truncate``/``at_step`` are returned for the caller to act on.
        """
        with self._lock:
            hits = self._hits[site] = self._hits.get(site, 0) + 1
            actions = set()
            for t in self._triggers.get(site, ()):
                if t.action in ("grace_ms", "delay_ms"):
                    continue  # configuration, read via config(), never fires
                if t.action == "fail_after":
                    if not t.fired and hits == t.value + 1:
                        t.fired = True
                        _record(site, "fail_after", hits, step)
                        raise InjectedFault(
                            f"injected fault: {site} hit {hits} "
                            f"(fail_after={t.value})")
                elif t.action == "every":
                    if t.value > 0 and hits % t.value == 0:
                        _record(site, "every", hits, step)
                        raise InjectedFault(
                            f"injected fault: {site} hit {hits} "
                            f"(every={t.value})")
                elif t.action in ("truncate", "drop", "conn_reset"):
                    # one-shot Nth-hit actions returned to the caller:
                    # the transport (or checkpoint writer) tears its own
                    # frame/connection so the failure is a REAL one
                    if not t.fired and hits == t.value:
                        t.fired = True
                        actions.add(t.action)
                elif t.action in ("at_step", "at_tick"):
                    # same one-shot progress trigger; at_tick is the
                    # spelling for tick-counter callers (replica drivers)
                    if not t.fired and step is not None and step == t.value:
                        t.fired = True
                        actions.add(t.action)
            for action in actions:
                _record(site, action, hits, step)
            return frozenset(actions)


def _record(site: str, action: str, hits: int, step: Optional[int]) -> None:
    """A TRIGGERED injection becomes a telemetry event, so chaos suites can
    assert cause→recovery ordering from the stream alone (the untriggered
    per-hit path emits nothing — ``fire`` runs per sample read and per
    serve slot per tick)."""
    telemetry.emit("fault", site, action=action, hits=hits, step=step)


_registry: Optional[FaultRegistry] = None
_registry_lock = locks.TracedLock("faults.active")


def install(spec: str) -> FaultRegistry:
    """Install an explicit spec (tests); returns the registry.  Any grace
    timer armed by a previous run's preemption drill is cancelled — an
    in-process rerun must never be hard-killed by its predecessor."""
    global _registry
    cancel_preempt_grace()
    with _registry_lock:
        _registry = FaultRegistry(spec)
        return _registry


def install_from_env() -> FaultRegistry:
    """(Re-)parse ``GRAFT_FAULTS``.  Trainers call this at startup so
    in-process reruns pick up the current environment, not a stale cache."""
    return install(os.environ.get("GRAFT_FAULTS", ""))


def reset() -> None:
    """Drop the registry (and cancel any armed preemption grace timer);
    the next :func:`fire` re-reads the environment."""
    global _registry
    cancel_preempt_grace()
    with _registry_lock:
        _registry = None


def get_registry() -> FaultRegistry:
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = FaultRegistry(os.environ.get("GRAFT_FAULTS", ""))
        return _registry


def fire(site: str, step: Optional[int] = None) -> FrozenSet[str]:
    """Hit a faultpoint.  No-op (empty set) when no faults are configured —
    cheap enough to leave in hot-ish paths like the dataset read."""
    reg = get_registry()
    if reg.empty:
        return frozenset()
    return reg.fire(site, step=step)


def maybe_kill(step: int) -> None:
    """The ``sigterm:at_step=N`` site: deliver a real SIGTERM to this
    process at step N — the preemption notice, so GracefulShutdown's
    checkpoint-and-stop path is rehearsed end to end."""
    if "at_step" in fire("sigterm", step=step):
        signal.raise_signal(signal.SIGTERM)


_PREEMPT_DEFAULT_GRACE_S = 30.0
_preempt_timers: List[threading.Timer] = []


def _grace_expired(step: int, grace_s: float) -> None:
    """The scheduler's hard kill: the grace window closed with the process
    still running.  ``os._exit`` (not sys.exit) — a real kill runs no
    finalizers, and the whole point is proving the manifest commit
    protocol needs none."""
    import os as _os

    from .failure import ExitCode

    telemetry.note(
        "fault", "preempt_expired",
        f"preemption grace window ({grace_s:.1f}s) expired before the "
        f"final checkpoint committed (step {step}); hard exit "
        f"{int(ExitCode.PREEMPT_EXPIRED)}", prefix="[faults]", step=step,
        grace_s=grace_s)
    _os._exit(int(ExitCode.PREEMPT_EXPIRED))


def maybe_preempt(step: int) -> None:
    """The ``preempt:at_step=N`` site: the full preemption drill.

    Delivers a real SIGTERM (the notice) AND arms a bounded grace window
    (``preempt:grace_ms=M`` on the same site, default 30 s) on a daemon
    timer: if the process is still alive when it expires — the final save
    stalled, a collective wedged — the timer hard-exits
    ``ExitCode.PREEMPT_EXPIRED`` exactly as the scheduler's follow-up
    SIGKILL would, mid-write, with no finalizers.  The graceful path
    (GracefulShutdown → final save → clean exit) must call
    :func:`cancel_preempt_grace` once its save has committed."""
    if "at_step" not in fire("preempt", step=step):
        return
    grace_ms = get_registry().config("preempt", "grace_ms")
    grace_s = (_PREEMPT_DEFAULT_GRACE_S if grace_ms is None
               else grace_ms / 1000.0)
    telemetry.note(
        "fault", "preempt",
        f"preemption notice at step {step}: SIGTERM delivered, "
        f"{grace_s:.1f}s grace window armed", prefix="[faults]",
        step=step, grace_s=grace_s)
    timer = threading.Timer(grace_s, _grace_expired, args=(step, grace_s))
    timer.daemon = True
    timer.name = f"preempt-grace-{step}"
    with _registry_lock:
        _preempt_timers.append(timer)
    timer.start()
    signal.raise_signal(signal.SIGTERM)


def cancel_preempt_grace() -> None:
    """Disarm any armed preemption grace timer: the final checkpoint
    committed inside the window (or an in-process rerun is starting).
    Trainers call this on their exit path; without it, a graceful stop
    that finished in time could still be hard-killed moments later."""
    with _registry_lock:
        timers, _preempt_timers[:] = list(_preempt_timers), []
    for t in timers:
        t.cancel()


def maybe_hang(step: int, cap: float = 3600.0) -> None:
    """The ``step_hang:at_step=N`` site: wedge the step loop at step N —
    a device call that never returns (the hung-device class, which raises
    no exception).  Sleeps inside the StepWatchdog's armed
    window so the watchdog's stack-dump + ``ExitCode.WEDGED`` exit is what
    ends it; ``cap`` bounds the sleep so a test that forgot to arm a
    watchdog still terminates eventually."""
    if "at_step" in fire("step_hang", step=step):
        import time

        telemetry.note(
            "fault", "step_hang_wedged",
            f"step_hang: wedging the step loop at step {step}",
            prefix="[faults]", step=step)
        deadline = time.monotonic() + cap
        while time.monotonic() < deadline:
            time.sleep(0.5)
